"""Serving cells: the program's ``ServeScheduler`` under an open-loop
stream of requests, greedy, driven one ``ServeScheduler.step`` at a time.

Set-up makes the weights from the seed, builds the scheduler and warms
exactly the shapes the mix uses: one request per prefill bucket its
prompts fall into, which also compiles the slot insert and the decode
step.  The window offers the mix's requests at their due times for
``--seconds``; afterwards the loop keeps running, with the uncounted tail
still arriving, until every request due in the window has finished (at
most ``drain_s`` more).  Each request is timed from its due time, on the
benchmark's clock, through its ``on_token`` callbacks.

The check samples finished requests from the seed, the one with the most
served tokens among them, and runs the plain reference over each prompt
with its served tokens: the widest gap by which a served token's logit
lies below the reference's best is the number compared.  A control run
(``bench/control.py``) puts the float8 reference in the program's place:
the gap of the token it puts first at each of the same positions.
"""
from __future__ import annotations

import gc
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import reference as R
from traffic import openloop
from weights import make_params

MIN_CHECK_TOKENS = 300
MIN_CHECK_REQUESTS = 4
MAX_CHECK_REQUESTS = 8


def _check_sample(reqs: List[Dict], seed: int) -> List[Dict]:
    """Finished requests drawn from the seed, the longest served first,
    until some hundreds of served tokens and a few requests are in it."""
    done = [r for r in reqs if r["done"]]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0xc4ec])
    longest = max(done, key=lambda r: len(r["tokens"]))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest["tokens"])
    for r in rest:
        if len(out) >= MAX_CHECK_REQUESTS or (
                n >= MIN_CHECK_TOKENS and len(out) >= MIN_CHECK_REQUESTS):
            break
        out.append(r)
        n += len(r["tokens"])
    return out


def run(ctx, rec, *, fault: Optional[Callable] = None) -> None:
    """ctx: harness.Context; rec: record.Record, filled in place.
    ``fault`` (tests only) is called with the scheduler to break it."""
    from repro.launch.runtime import compile_stats
    from repro.serve.engine import DONE, Request
    from repro.serve.scheduler import ServeScheduler

    if ctx.control not in (None, "fp8"):
        raise ValueError(f"serving has no control {ctx.control!r}")
    mix, dm, seed, cfg = ctx.traffic, ctx.dims, ctx.seed, ctx.arch
    slots = int(mix["slots"])
    # the served context is the configuration's
    cache_len = int(ctx.config["max_position_embeddings"])

    with compile_stats() as compiled:
        params = make_params(dm, seed)
        sched = ServeScheduler(cfg, params, slots=slots, cache_len=cache_len,
                               greedy=True, seed=seed & 0x7FFFFFFF)
        if fault is not None:
            fault(sched)
        lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
        shapes = sorted({sched.bucket(p) for p in range(lo, hi + 1)})
        for i, b in enumerate(shapes):
            sched.submit(Request(rid=-1 - i, prompt=np.ones(b, np.int32),
                                 max_tokens=2))
        while not sched.idle():
            sched.step()
        sched.completed.clear()
    rec.compile = dict(compiled)
    rec.counters["prefill_buckets"] = shapes

    plan = openloop.schedule(mix, seed, ctx.seconds, dm.vocab)
    info: Dict[int, Dict] = {}

    def on_token(req, tok, finished):
        r = info[req.rid]
        if tok >= 0:
            r["token_t"].append(time.perf_counter())
            r["tokens"].append(int(tok))
        if finished:
            r["done"] = req.status == DONE

    reqs = []
    for rid, p in enumerate(plan):
        info[rid] = {"rid": rid, "due": None, "prompt": p.prompt,
                     "max_tokens": p.max_tokens, "counted": p.counted,
                     "token_t": [], "tokens": [], "done": False,
                     "admit": None}
        reqs.append(Request(rid=rid, prompt=p.prompt, max_tokens=p.max_tokens,
                            on_token=on_token))

    tracer = ctx.tracer
    trace_s = float(mix["trace_seconds"])
    trace_at = max(0.0, (ctx.seconds - trace_s) / 2) if ctx.trace else None
    drain_s = float(mix["drain_s"])
    clock = sched.clock
    t0 = clock.now()
    rec.setup_s = t0 - ctx.t_start
    for req, p in zip(reqs, plan):
        info[req.rid]["due"] = t0 + p.offset_s
        sched.submit_at(req, t0 + p.offset_s)
    counted = [info[r.rid] for r in reqs if info[r.rid]["counted"]]
    deadline, give_up = t0 + ctx.seconds, t0 + ctx.seconds + drain_s
    decode_before = sched.stats["decode_steps"]
    while True:
        now = clock.now()
        if trace_at is not None and not tracer.on and now >= t0 + trace_at:
            tracer.start()
        if tracer.on and now >= t0 + trace_at + trace_s:
            tracer.stop(rec)
            trace_at = None
        if now >= give_up or (now >= deadline
                              and all(r["done"] for r in counted)):
            break
        ticks0 = sched.stats["decode_steps"]
        n_done0 = len(sched.completed)
        live = {r.rid for r in sched.active if r is not None}
        n_tok0 = {rid: len(info[rid]["tokens"]) for rid in live}
        s0 = time.perf_counter()
        with rec.span("sched_step"):
            progressed = sched.step()
        s1 = time.perf_counter()
        ticks = sched.stats["decode_steps"] - ticks0
        live |= {r.rid for r in sched.active if r is not None}
        live |= {r.rid for r in sched.completed[n_done0:]}
        dec_tok = kv = 0
        for rid in live:
            r = info[rid]
            # token j >= 1 comes from a decode step that attends the
            # prompt and the j tokens before it
            for j in range(max(n_tok0.get(rid, 0), 1), len(r["tokens"])):
                dec_tok += 1
                kv += len(r["prompt"]) + j
        rec.sched_steps.append((s0, s1, ticks, dec_tok, kv))
        if not progressed and not sched.queue:
            nxt = sched.next_arrival()
            if nxt is None:
                break
            with rec.span("wait_arrival"):
                clock.sleep_until(min(nxt, give_up))
    t_end = clock.now()
    if tracer.on:
        tracer.stop(rec)
    rec.window_s = ctx.seconds
    for req in reqs:
        if req.t_admit is not None:
            info[req.rid]["admit"] = req.t_admit
    rec.requests = counted
    rec.counters.update(
        decode_steps=sched.stats["decode_steps"] - decode_before,
        decode_compiles=sched.decode_compiles,
        prefill_compiles=sched.prefill_compiles,
        drain_s=max(0.0, t_end - deadline), t_end=t_end)
    rec.attempted = len(counted)
    rec.failed = sum(1 for r in counted if not r["done"])
    rec.memory_peak_bytes = ctx.memory_peak()

    sample = _check_sample(counted, seed)
    sched.params = None
    del sched, params, reqs
    gc.collect()
    if not sample:
        rec.check("logit_gap", float("inf"), ctx.limits["logit_gap"])
        return
    pad_to = int(mix["prompt"]["max"]) + int(mix["output"]["max"])
    t_ref = time.perf_counter()
    got = R.served_logit_gaps(
        dm, seed, [(r["prompt"], r["tokens"]) for r in sample], pad_to,
        control=bool(ctx.control))
    if ctx.control:
        rec.notes["program"] = {"logit_gap": got["logit_gap"]}
        rec.check("logit_gap", got["control_logit_gap"],
                  ctx.limits["logit_gap"])
    else:
        rec.check("logit_gap", got["logit_gap"], ctx.limits["logit_gap"])
    rec.notes.update(setup_s=rec.setup_s,
                     reference_s=time.perf_counter() - t_ref,
                     check_requests=len(sample), check_tokens=got["tokens"])
