"""The data-parallel trainer: ``train_main``'s multi-process twin.

One ``dist_train_main`` call is ONE rank of a gang.  Rank 0 hosts the
``jax.distributed`` coordinator and owns checkpoint *writes*; every
rank restores from the same checkpoint dir on resume (writes are
atomic ``tmp -> rename`` publishes, so readers never see torn state)
and the loop asserts cross-rank agreement on the restored step before
any collective runs.  Loss/step trajectories at world=N are equal to a
single-process run at the same global batch — every rank draws the
identical global stream and keeps its rows, and the grad all-reduce is
the same mean the single process computes (the oracle test in
``tests/test_distributed.py`` pins this down to numerical identity on
one host).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np


class DistributedTrainLoop:
    """A :class:`repro.train.TrainLoop` whose resume re-replicates the
    restored host state onto the process mesh and cross-checks rank
    agreement.  (Constructed via :func:`make_loop` — the import of
    TrainLoop stays inside jax-using code paths.)"""

    def __new__(cls, *a, **kw):                 # pragma: no cover - guard
        raise TypeError("use DistributedTrainLoop.create(...)")

    @classmethod
    def create(cls, step_fn, state, data, *, ctx,
               checkpointer=None, preempt_at_step=None, log_every=10,
               sigterm_save=False):
        from repro.train import TrainLoop

        class _Loop(TrainLoop):
            def place_state(self, host_state):
                # the restored host arrays become fully-replicated global
                # arrays on the mesh
                return ctx.replicate(host_state)

            def resume(self) -> bool:
                restored = super().resume()
                ctx.agree(np.asarray(self.start_step, dtype=np.int64),
                          "resumed step")
                return restored

        return _Loop(step_fn, state, data, checkpointer=checkpointer,
                     preempt_at_step=preempt_at_step, log_every=log_every,
                     sigterm_save=sigterm_save)


def allreduce_bytes_per_step(param_bytes: int, world: int) -> int:
    """Analytic ring all-reduce traffic per step and per rank:
    ``2 * (N-1)/N * grad_bytes`` (reduce-scatter + all-gather), the
    FireCaffe reduction-bandwidth model this repo treats as the scaling
    contract.  Zero at world=1."""
    if world <= 1:
        return 0
    return int(2 * (world - 1) / world * param_bytes)


def dist_train_main(arch: str, *, world_size: int, dist_rank: int = 0,
                    coordinator: Optional[str] = None,
                    reduced: bool = True, steps: int = 100,
                    batch: int = 8, seq: int = 128, lr: float = 3e-4,
                    optimizer: str = None, seed: int = 0,
                    checkpoint_dir: str = None, s3_root: str = None,
                    log_every: int = 10, checkpoint_every: int = 0,
                    checkpoint_keep: int = 3, checkpoint_async: bool = True,
                    resume: bool = False, preempt_at_step: int = None,
                    precision: str = "f32", grad_clip: float = None,
                    microbatches: int = 1,
                    attention_backend: str = None,
                    mixer_backend: str = None) -> Dict[str, Any]:
    """Run one rank of a data-parallel training job.  ``batch`` is the
    GLOBAL batch; each rank computes ``batch / world_size`` rows.  The
    return dict is ``train_main``'s result plus a ``dist`` section
    (rank 0's report is the one the executor and gang launcher parse).
    """
    # distributed init must precede every other jax interaction
    from repro.distributed.context import init_distributed
    ctx = init_distributed(world_size, dist_rank, coordinator)

    import jax
    from repro.checkpoint import CheckpointManager, export_to_s3
    from repro.configs import get_config, get_reduced
    from repro.core.artifacts import S3Store
    from repro.data.inputs import SeekableSyntheticBatches
    from repro.data.tokens import SeekableTokenBatches
    from repro.distributed.data import ShardedBatches
    from repro.kernels.common import kernel_paths
    from repro.launch.runtime import compile_stats, device_report
    from repro.optim import get_optimizer, warmup_cosine
    from repro.sharding import ShardCtx, rules
    from repro.sharding.ctx import use_ctx
    from repro.train import init_train_state, make_train_step

    if batch % max(1, ctx.devices):
        raise ValueError(f"global batch {batch} must divide over "
                         f"{ctx.devices} devices")
    cfg = get_reduced(arch) if reduced else get_config(arch)
    backends = {}
    if attention_backend:
        backends["attention_backend"] = attention_backend
    if mixer_backend:
        backends["mixer_backend"] = mixer_backend
    if backends:
        cfg = dataclasses.replace(cfg, **backends)
    opt = get_optimizer(optimizer or cfg.optimizer)
    # through host memory, so one device copy exists at a time
    state = jax.tree.map(np.asarray, init_train_state(
        jax.random.PRNGKey(seed), cfg, opt))
    state = ctx.replicate(state)

    # the existing donated/bf16/Pallas step, bare (jit_compile=False is
    # documented for exactly this: sharded launchers add their own jit)
    bare_step = make_train_step(
        cfg, opt, lr_schedule=warmup_cosine(lr, steps,
                                            warmup_steps=max(steps // 10, 1)),
        precision=precision, grad_clip=grad_clip,
        microbatches=max(1, int(microbatches)), jit_compile=False)
    sctx = ShardCtx(ctx.mesh, rules.logical_axes(ctx.mesh, "dp"))

    def step_with_ctx(st, b):
        # trace-time activation constraints resolve batch -> "data"
        with use_ctx(sctx):
            return bare_step(st, b)

    step_fn = ctx.jit_step(step_with_ctx)

    text_lm = cfg.family in ("dense", "moe", "ssm", "hybrid")
    if text_lm:
        inner = SeekableTokenBatches(cfg.vocab, batch, seq, seed)
        to_named = lambda raw: {"tokens": raw[0], "labels": raw[1]}  # noqa: E731
    else:
        inner = SeekableSyntheticBatches(cfg, batch, seq, seed)
        to_named = None
    data = ShardedBatches(inner, ctx, to_named=to_named, global_rows=batch)

    ckpt = None
    if checkpoint_dir:
        # one shared dir: rank 0 writes on cadence, every rank restores.
        # Non-coordinators get a zero-cadence manager (restore-only).
        ckpt = CheckpointManager(
            checkpoint_dir, keep_last=max(int(checkpoint_keep), 1),
            every_steps=(int(checkpoint_every)
                         if ctx.is_coordinator else 0),
            async_saves=bool(checkpoint_async) and ctx.is_coordinator)
    # only the coordinator saves on SIGTERM (it owns checkpoint writes);
    # other ranks die with the signal and the gang requeues as one
    with compile_stats() as compiled:
        # the loop owns the only reference: resume frees it before
        # restoring
        loop = DistributedTrainLoop.create(
            step_fn, state, data, ctx=ctx, checkpointer=ckpt,
            preempt_at_step=preempt_at_step,
            log_every=log_every if ctx.is_coordinator else 0,
            sigterm_save=ctx.is_coordinator)
        del state
        if resume:
            loop.resume()
        try:
            run = loop.run(steps)
        finally:
            if ckpt is not None:
                ckpt.wait()

    param_bytes = sum(
        int(np.prod(p.shape)) * 4
        for p in jax.tree.leaves(loop.state.params))
    result: Dict[str, Any] = {
        "arch": cfg.name, "params": cfg.param_count(),
        **run,
        "device": device_report(),
        "kernels": kernel_paths(cfg, mesh_devices=ctx.devices),
        "compile": compiled,
        "dist": {
            "world_size": ctx.world_size,
            "rank": ctx.rank,
            "devices": ctx.devices,
            "global_batch": batch,
            "local_batch": batch // max(1, ctx.world_size),
            "microbatches": max(1, int(microbatches)),
            "grad_bytes": param_bytes,
            # per-rank ring traffic for the one grad reduction per step
            # (grads reduce in f32; microbatch accumulation is local)
            "allreduce_bytes_per_step": allreduce_bytes_per_step(
                param_bytes, ctx.world_size),
        },
    }
    if steps <= 512:
        # the oracle tests compare full trajectories; bounded so long
        # runs don't bloat their reports
        result["losses"] = list(loop.losses)
    if ckpt is not None:
        if ctx.is_coordinator:
            loop.save_final(extra={"arch": cfg.name,
                                   "final_loss": run.get("final_loss")})
        overhead = result.get("checkpoint", {}).get("overhead_frac", 0.0)
        result["checkpoint"] = {**ckpt.stats(), "overhead_frac": overhead}
        ckpt.close()
        if s3_root and ctx.is_coordinator:
            s3 = S3Store(s3_root)
            n = export_to_s3(checkpoint_dir, s3, f"models/{cfg.name}")
            result["s3_objects"] = n
    return result
