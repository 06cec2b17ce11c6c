"""Serving launcher: batched decoding over synthetic requests.

``python -m repro.launch.serve --arch granite-3-2b --requests 16``

Decoding is greedy by default; ``--temperature``/``--top-k`` switch the
fused on-device sampling head (per-request knobs are available on
:class:`repro.serve.Request`).

Two serving modes share this entrypoint:

* **static batch** (default, ``--arrival-rate 0``): every request is
  queued up front and the :class:`~repro.serve.ServeEngine` drains them —
  the closed-loop throughput measurement.
* **continuous** (``--arrival-rate > 0`` requests/s): an open-loop
  Poisson or bursty arrival trace (``--trace``) drives the
  :class:`~repro.serve.ServeScheduler` — continuous admission into freed
  slots mid-decode, SLO shedding (``--slo-deadline-ms``), and paged-KV
  budgeting/eviction (``--max-kv-blocks``, ``--kv-block-size``).

Both modes report per-request service timing (TTFT / TPOT / queue-wait
percentiles) so campaign summaries can aggregate them.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.kernels.common import kernel_paths
from repro.launch.runtime import compile_stats, device_report
from repro.models import init_params
from repro.serve import Request, ServeEngine, ServeScheduler, make_trace


def _timing_metrics(stats_summary: dict) -> dict:
    keys = ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
            "queue_wait_p50_s", "queue_wait_p99_s", "evictions")
    return {k: stats_summary.get(k) for k in keys}


def _batch_use(engine) -> dict:
    """The share of prefilled positions that were padding (each prefill
    runs the full slots x bucket batch) and the share of decode slots
    that held a live request, from the engine's counters."""
    st = engine.stats
    padded, steps = st["prefill_padded_tokens"], st["decode_steps"]
    return {
        "prefill_pad_waste": (round(1 - st["prefill_tokens"] / padded, 4)
                              if padded else None),
        "decode_occupancy": (round(st["decode_active_slots"]
                                   / (steps * engine.slots), 4)
                             if steps else None),
    }


def serve_main(arch: str, *, full: bool = False, requests: int = 16,
               slots: int = 4, cache_len: int = 128, max_tokens: int = 16,
               seed: int = 0, temperature: float = 0.0,
               top_k: int = 0, arrival_rate: float = 0.0,
               trace: str = "poisson", slo_deadline_ms: float = 0.0,
               max_kv_blocks: int = 0, kv_block_size: int = 16) -> dict:
    cfg = get_config(arch) if full else get_reduced(arch)
    with compile_stats() as compiled:
        params = init_params(jax.random.PRNGKey(seed), cfg)
        if arrival_rate > 0:
            result = _serve_continuous(
                cfg, params, requests=requests, slots=slots,
                cache_len=cache_len, max_tokens=max_tokens, seed=seed,
                temperature=temperature, top_k=top_k,
                arrival_rate=arrival_rate, trace=trace,
                slo_deadline_ms=slo_deadline_ms,
                max_kv_blocks=max_kv_blocks, kv_block_size=kv_block_size)
        else:
            result = _serve_static(
                cfg, params, requests=requests, slots=slots,
                cache_len=cache_len, max_tokens=max_tokens, seed=seed,
                temperature=temperature, top_k=top_k)
    # prefill runs attn_apply's path; decode always attends in jnp
    return {**result, "device": device_report(),
            "kernels": {"prefill": kernel_paths(cfg), "decode": "jnp"},
            "compile": compiled}


def _serve_static(cfg, params, *, requests, slots, cache_len, max_tokens,
                  seed, temperature, top_k) -> dict:
    engine = ServeEngine(cfg, params, slots=slots, cache_len=cache_len,
                         seed=seed)
    rng = np.random.default_rng(seed)
    for i in range(requests):
        engine.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab,
                                       size=int(rng.integers(4, 24))),
            max_tokens=max_tokens, temperature=temperature, top_k=top_k))
    t0 = time.time()
    done = engine.run()
    wall = time.time() - t0
    tokens = sum(len(r.generated) for r in done)
    return {
        "arch": cfg.name, "mode": "static", "requests": len(done),
        "tokens": tokens,
        "wall_s": round(wall, 2),
        "tokens_per_s": round(tokens / wall, 2),
        "slots": slots,
        "decode_steps": engine.stats["decode_steps"],
        "prefill_compiles": engine.prefill_compiles,
        "decode_compiles": engine.decode_compiles,
        "host_transfer_bytes": engine.stats["host_transfer_bytes"],
        **_batch_use(engine),
        **_timing_metrics(engine.stats()),
    }


def _serve_continuous(cfg, params, *, requests, slots, cache_len,
                      max_tokens, seed, temperature, top_k, arrival_rate,
                      trace, slo_deadline_ms, max_kv_blocks,
                      kv_block_size) -> dict:
    sched = ServeScheduler(
        cfg, params, slots=slots, cache_len=cache_len, seed=seed,
        max_kv_blocks=max_kv_blocks or None, kv_block_size=kv_block_size,
        slo_deadline_ms=slo_deadline_ms or None)
    items = make_trace(trace, cfg.vocab, requests, arrival_rate,
                       seed=seed, max_tokens=max_tokens)
    for _, req in items:
        req.temperature, req.top_k = temperature, top_k
    t0 = sched.clock.now()
    sched.submit_trace([(t0 + t, r) for t, r in items])
    done = sched.run()
    wall = sched.clock.now() - t0
    s = sched.stats()
    tokens = sum(len(r.generated) for r in done)
    slo_tokens = sum(len(r.generated) for r in done if r.met_deadline())
    return {
        "arch": cfg.name, "mode": "continuous", "trace": trace,
        "arrival_rate_qps": arrival_rate,
        "requests": requests, "completed": s["completed"],
        "shed": s["shed"], "slo_met": s["slo_met"],
        "tokens": tokens,
        "wall_s": round(wall, 2),
        "tokens_per_s": round(tokens / max(wall, 1e-9), 2),
        "goodput_req_s": round(s["slo_met"] / max(wall, 1e-9), 3),
        "goodput_tok_s": round(slo_tokens / max(wall, 1e-9), 2),
        "slots": slots,
        "decode_steps": s["decode_steps"],
        "prefill_compiles": s["prefill_compiles"],
        "decode_compiles": s["decode_compiles"],
        "kv": s["kv"],
        **_batch_use(sched),
        **_timing_metrics(s),
    }


def main():
    # thin shim over the repro.api registry (RunSpec in, RunReport out)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--full", action="store_true",
                    help="full-size config instead of reduced")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop offered load in requests/s "
                         "(0 = static batch mode)")
    ap.add_argument("--trace", choices=("poisson", "bursty"),
                    default="poisson")
    ap.add_argument("--slo-deadline-ms", type=float, default=0.0,
                    help="TTFT SLO; queued requests past it are shed "
                         "(0 = no deadline)")
    ap.add_argument("--max-kv-blocks", type=int, default=0,
                    help="paged KV pool size in blocks "
                         "(0 = slots*cache_len, no oversubscription)")
    ap.add_argument("--kv-block-size", type=int, default=16)
    args = ap.parse_args()

    from repro.api import RunSpec, run
    report = run(RunSpec(kind="serve", arch=args.arch, overrides={
        "full": args.full, "requests": args.requests, "slots": args.slots,
        "cache_len": args.cache_len, "max_tokens": args.max_tokens,
        "temperature": args.temperature, "top_k": args.top_k,
        "arrival_rate": args.arrival_rate, "trace": args.trace,
        "slo_deadline_ms": args.slo_deadline_ms,
        "max_kv_blocks": args.max_kv_blocks,
        "kv_block_size": args.kv_block_size}))
    print(json.dumps(report.metrics, indent=1))
    if not report.ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
