"""Training cells: the program's jitted, donated train step driven by its
``TrainLoop`` over its own token stream, as ``train_main`` builds them.
The step is given the stream's token ids alone, the batch that
``input_specs`` describes for a decoder, from which the program's loss
takes each position's next token as its target.

Set-up makes the weights from the seed, builds the one loop object and
drives it through its first three steps (the first compiles); their
losses, the first gradient (read back from AdamW's first moment) and the
change of every weight after the third step are kept for the check.  The
window then continues the same loop until ``--seconds`` have passed, and
ends when the last step's state is ready.  After the window the plain
reference follows the same three steps from the same seed and tokens.
A control run (``bench/control.py``) puts the reference, computed in a
lower precision or with a planted fault, in the program's place in the
comparison.
"""
from __future__ import annotations

import gc
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
from weights import make_params

CHECK_STEPS = 3
ADAM_B1 = 0.9
# what a control run puts in the program's place: train_readings options
CONTROLS = {"fp8": {"mode": "fp8"},
            "half_positions": {"positions": "first_half"}}


class _WindowClosed(Exception):
    pass


def step_input(batch):
    """What the step is given of the stream's batch: the token ids alone,
    the batch ``input_specs`` describes for a decoder, from which the
    program's loss takes each position's next token.  The stream's
    ``labels`` are already shifted by one, and the loss shifts them
    again."""
    return {"tokens": batch["tokens"]}


class TimedBatches:
    """The program's data source, with the host time of each
    ``next_batch`` recorded as a span.  Keeps host copies of the first
    ``keep`` batches' tokens for the reference."""

    def __init__(self, inner, rec, keep: int):
        self.inner, self.rec, self.keep = inner, rec, keep
        self.kept = []

    def next_batch(self):
        with self.rec.span("next_batch"):
            batch = step_input(self.inner.next_batch())
        if len(self.kept) < self.keep:
            self.kept.append(np.asarray(batch["tokens"]))
        return batch


def _change_norms(params, dm, seed) -> Dict[str, float]:
    """Norm of every slice's change from the seeded weights, one leaf at
    a time, so no float32 copy of the model is made."""
    p0 = make_params(dm, seed)
    per = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)),
        axis=tuple(range(1, a.ndim)))))
    whole = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    out: Dict[str, float] = {}
    flat_p = jax.tree_util.tree_flatten_with_path(params["periods"]["slot0"])[0]
    flat_0 = jax.tree.leaves(p0["periods"]["slot0"])
    for (path, a), b in zip(flat_p, flat_0):
        norms = np.asarray(per(a, b))
        key = jax.tree_util.keystr(path)
        for i in range(dm.layers):
            out[f"layer{i}{key}"] = float(norms[i])
    top_p = {k: v for k, v in params.items() if k != "periods"}
    top_0 = {k: v for k, v in p0.items() if k != "periods"}
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(top_p)[0],
                            jax.tree.leaves(top_0)):
        out["top" + jax.tree_util.keystr(path)] = float(whole(a, b))
    return out


def run(ctx, rec, *, fault: Optional[Callable] = None) -> None:
    """ctx: harness.Context; rec: record.Record, filled in place.
    ``fault`` (tests only) wraps the step function to break it."""
    from repro.launch.runtime import compile_stats
    from repro.launch.train import _LMDictBatches
    from repro.optim import constant, get_optimizer
    from repro.train import TrainLoop, TrainState, make_train_step

    mix, dm, seed = ctx.traffic, ctx.dims, ctx.seed
    cfg = ctx.arch
    batch, seq = int(mix["batch"]), int(mix["seq"])
    lr = float(mix["lr"])

    with compile_stats() as compiled:
        params = make_params(dm, seed)
        opt = get_optimizer(cfg.optimizer)
        state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
        del params
        step_fn = make_train_step(cfg, opt, lr_schedule=constant(lr))
        if fault is not None:
            step_fn = fault(step_fn)

        def step(state, batch):
            with rec.span("train_step"):
                return step_fn(state, batch)

        data = TimedBatches(_LMDictBatches(cfg.vocab, batch, seq, seed), rec,
                            keep=CHECK_STEPS)
        loop = TrainLoop(step, state, data, log_every=0)
        del state
        loop.run(1)
        # AdamW's first moment after one step is (1 - b1) g
        prog_grad = {k: v / (1.0 - ADAM_B1) for k, v in
                     R.slice_norms(loop.state.opt_state["m"], dm).items()}
        loop.start_step = 1
        loop.run(CHECK_STEPS)
        prog_change = _change_norms(loop.state.params, dm, seed)
        loop.start_step = CHECK_STEPS
        prog_losses = list(loop.losses)
        jax.block_until_ready(loop.state)
    rec.compile = dict(compiled)
    rec.spans.clear()

    trace_at = None
    if ctx.trace:
        trace_at = max(0.0, ctx.seconds - float(mix["trace_seconds"]))
    tracer = ctx.tracer
    t0 = time.perf_counter()
    rec.setup_s = t0 - ctx.t_start
    deadline = t0 + ctx.seconds

    def hook(i):
        now = time.perf_counter()
        if trace_at is not None and not tracer.on and now >= t0 + trace_at:
            tracer.start()
        if now >= deadline:
            raise _WindowClosed

    loop.fault_hook = hook
    try:
        loop.run(1 << 40)
    except _WindowClosed:
        pass
    jax.block_until_ready(loop.state)
    t1 = time.perf_counter()
    if tracer.on:
        tracer.stop(rec)
    rec.window_s = t1 - t0
    window_losses = loop.losses[CHECK_STEPS:]
    rec.steps = len(window_losses)
    rec.tokens = rec.steps * batch * seq
    rec.attempted = rec.steps
    rec.failed = int(sum(1 for x in window_losses if not np.isfinite(x)))
    rec.memory_peak_bytes = ctx.memory_peak()

    # free the program's state before the reference runs
    loop.state = None
    del loop, step_fn, step
    gc.collect()

    t_ref = time.perf_counter()
    ref = R.train_readings(dm, seed, data.kept, lr=lr)
    skip = R.rounding_only(ref["grad"])
    got = _gaps(prog_losses, prog_grad, prog_change, ref, skip)
    if ctx.control:
        rec.notes["program"] = got
        ctl = R.train_readings(dm, seed, data.kept, lr=lr,
                               **CONTROLS[ctx.control])
        got = _gaps(ctl["losses"], ctl["grad"], ctl["change"], ref, skip)
    for name in ("loss_gap", "grad_gap", "grad_gap_median", "change_gap"):
        rec.check(name, got[name], ctx.limits[name])
    rec.notes.update(
        setup_s=rec.setup_s, reference_s=time.perf_counter() - t_ref,
        losses=prog_losses, ref_losses=ref["losses"],
        loss_gaps=got["loss_gaps"], grad_slice=got["grad_slice"],
        change_slice=got["change_slice"], skipped_slices=len(skip))


def _gaps(losses, grad, change, ref, skip) -> Dict:
    """The numbers the check compares, of readings against the reference.
    The first step's loss is compared: after two AdamW steps the loss
    swings from seed to seed with the signs of the smallest gradient
    entries, which bfloat16 and float32 gradients set differently
    (PERF.md); the change of every weight after three steps covers them."""
    loss_gaps = [abs(p - r) / abs(r) for p, r in zip(losses, ref["losses"])]
    g = R.gap_of_norms(grad, ref["grad"])
    c = R.gap_of_norms(change, ref["change"], skip)
    return {"loss_gap": loss_gaps[0], "grad_gap": g["gap"],
            "grad_gap_median": g["median_gap"], "change_gap": c["gap"],
            "loss_gaps": loss_gaps, "grad_slice": g["slice"],
            "change_slice": c["slice"]}
