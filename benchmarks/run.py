"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time per
benchmark unit; derived = the table's headline quantity reproduced) and
writes the same rows machine-readably to ``BENCH_paper.json`` so the
paper-table benchmarks feed the ``BENCH_*`` perf trajectory alongside
``BENCH_serve.json`` (compare the file across PRs).

  table1_pipeline      — Table I: data-pipeline stages as parallel jobs
  table3_detection     — Table III: 30-model detection campaign accounting
  table4_ba_models     — Table IV: U-Net family comparison (reduced, real)
  table5_totals        — Table V: 234-model / 4,040-hour campaign totals
  roofline_summary     — §Roofline figure: dominant terms from the dry-run
  kernel_micro         — kernel-path microbenchmarks (CPU, jnp paths)
  resume_overhead      — durable-checkpoint cost on the training hot path
                         (async cadence saves; contract: <5% steps/s)
"""
from __future__ import annotations

import json
import pathlib
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
ROWS = []


def row(name: str, us_per_call: float, derived: str):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}")


# ---------------------------------------------------------------- Table I
def table1_pipeline():
    """Paper Table I: Download/Norm/Label/Chip stages, #jobs and GB."""
    from repro.core import JobSpec, Orchestrator, PersistentVolume, Resources
    from repro.data.chipping import dedup_chips, make_chips
    from repro.data.normalize import percentile_stretch
    from repro.data.rasters import synth_raster

    n_scenes = 6
    t0 = time.time()
    with tempfile.TemporaryDirectory() as td:
        pvc = PersistentVolume(td)
        orch = Orchestrator(pvc)
        stage_bytes = {"download": 0, "norm": 0, "label": 0, "chip": 0}
        chips_all = []

        def dl(i="0", **kw):
            s = synth_raster(f"bench-{i}", 256, 256, seed=int(i))
            stage_bytes["download"] += s.raster.nbytes
            return s

        scenes = []
        for i in range(n_scenes):
            orch.submit(JobSpec(name=f"download-{i}", payload=dl,
                                env={"i": str(i)},
                                resources=Resources(gpus=0, cpus=2,
                                                    memory_gb=8)))
        orch.run_local()
        scenes = [r.result for r in orch.records.values()]

        for s in scenes:
            norm = percentile_stretch(s.raster)
            stage_bytes["norm"] += norm.nbytes
            stage_bytes["label"] += s.mask.nbytes
            cs = make_chips(norm[..., :3], s.mask, s.scene_id,
                            chip=64, overlap=0.25)
            chips_all.extend(cs)
            stage_bytes["chip"] += sum(c.image.nbytes for c in cs)
        chips_all = dedup_chips(chips_all)
    wall = time.time() - t0
    total_mb = sum(stage_bytes.values()) / 1e6
    row("table1_pipeline", wall * 1e6 / n_scenes,
        f"stages=4 jobs={n_scenes + 3 * n_scenes} data_mb={total_mb:.1f} "
        f"chips={len(chips_all)} (paper: 174 jobs / 992.6 GB / 5762 chips)")


# --------------------------------------------------------------- Table III
def table3_detection():
    """Paper Table III: 10 networks x 3 datasets, 4 GPUs each; reproduce the
    campaign's cluster accounting (1,402 GPU-h of training)."""
    from repro.core import ClusterSim
    from repro.launch.submit import build_campaign

    jobs = build_campaign("detection")
    t0 = time.time()
    res = ClusterSim().run(jobs)
    wall = time.time() - t0
    row("table3_detection", wall * 1e6 / len(jobs),
        f"models=30 gpu_hours={res.total_gpu_hours:.0f} "
        f"makespan_h={res.makespan_h:.1f} "
        f"(paper: 30 models / {4 * (241.2 + 580.4 + 580.6):.0f} GPU-h)")


# --------------------------------------------------------------- Table IV
def table4_ba_models():
    """Paper Table IV: U-Net vs U-Net++ vs DeepLabV3 vs DeepLabV3+ with the
    best hyperparameters — real (reduced) training on the synthetic BA set."""
    import jax
    import jax.numpy as jnp
    from repro.data.chipping import make_chips
    from repro.data.normalize import percentile_stretch
    from repro.data.rasters import synth_raster
    from repro.models.segmentation import (SEG_MODELS, seg_apply, seg_init,
                                           seg_loss, seg_metrics)
    from repro.optim import get_optimizer

    chips = []
    for i in range(3):
        s = synth_raster(f"t4-{i}", 192, 192, seed=i)
        img = percentile_stretch(s.raster)[..., :3]
        chips.extend(make_chips(img, s.mask, s.scene_id, chip=64,
                                overlap=0.25, min_frac=0.08))
    x = jnp.asarray(np.stack([c.image for c in chips]))
    m = jnp.asarray(np.stack([c.mask for c in chips]), jnp.int32)
    xtr, mtr, xte, mte = x[:-4], m[:-4], x[-4:], m[-4:]

    results = {}
    for name in sorted(SEG_MODELS):
        t0 = time.time()
        params = seg_init(name, jax.random.PRNGKey(0), width=8)
        opt = get_optimizer("lamb")   # paper's winning optimizer
        st = opt.init(params)

        @jax.jit
        def step(p, s, i):
            l, g = jax.value_and_grad(
                lambda p: seg_loss(name, p, xtr, mtr))(p)
            return *opt.update(g, s, p, i, 1e-2), l

        for i in range(25):
            params, st, loss = step(params, st, jnp.asarray(i))
        f1 = float(seg_metrics(seg_apply(name, params, xte), mte)["f1"])
        iou = float(seg_metrics(seg_apply(name, params, xte), mte)["iou"])
        wall = time.time() - t0
        results[name] = (f1, iou, wall)
        row(f"table4_{name}", wall * 1e6 / 25,
            f"f1={f1:.3f} iou={iou:.3f} "
            f"(paper full-scale: f1 0.82-0.84, iou 0.69-0.72)")
    best = max(results, key=lambda n: results[n][0])
    row("table4_best_model", 0.0,
        f"best={best} (paper: DeepLabV3 best IoU, DeepLabV3+ best Prec)")


# ---------------------------------------------------------------- Table V
def table5_totals():
    """Paper Table V: all three campaigns, 234 models / 4,040 h total."""
    from repro.core import ClusterSim
    from repro.launch.submit import build_campaign

    jobs = []
    for c in ("detection", "burned_area", "deforestation"):
        jobs.extend(build_campaign(c))
    t0 = time.time()
    res = ClusterSim().run(jobs)
    wall = time.time() - t0
    months_serial = res.total_wall_hours / (24 * 30)
    row("table5_totals", wall * 1e6 / len(jobs),
        f"models={len(jobs)} wall_hours={res.total_wall_hours:.0f} "
        f"makespan_h={res.makespan_h:.1f} serial_months={months_serial:.1f} "
        f"speedup={res.speedup_vs_serial():.0f}x "
        f"(paper: 234 models / 4040 h / '5.5+ months serial')")


# ----------------------------------------------------------- §Roofline
def _generate_dryrun_artifacts(d: pathlib.Path) -> bool:
    """Produce the dry-run records the roofline row aggregates.  Runs in a
    subprocess: the dryrun runner needs its 512-host-device XLA trick set
    *before* jax initializes, which is long gone in this process (table4
    already trained models)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    # the child is a CPU rehearsal: this process may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (str(ROOT / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "repro.launch", "run", "dryrun",
           "--arch", "stablelm-1.6b", "--shape", "train_4k",
           "--out", str(d)]
    try:
        proc = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True,
                              text=True, timeout=1800)
    except (OSError, subprocess.TimeoutExpired) as e:
        print(f"# dryrun generation failed: {e}")
        return False
    if proc.returncode != 0:
        print(f"# dryrun generation failed:\n{proc.stderr[-2000:]}")
    return proc.returncode == 0


def roofline_summary():
    d = ROOT / "experiments" / "dryrun"
    if not (d.exists() and any(d.glob("*.json"))):
        # no committed sweep: generate a single-cell sweep into a scratch
        # dir (NOT experiments/dryrun — that dir, when present, must hold
        # the complete sweep; tests/test_system.py enforces it)
        d = ROOT / "experiments" / "roofline_dryrun"
        have_scratch = d.exists() and any(d.glob("*.json"))
        if not have_scratch and not _generate_dryrun_artifacts(d):
            row("roofline_summary", 0.0,
                "dry-run artifacts missing and generation failed")
            return
    recs = [json.loads(p.read_text()) for p in sorted(d.glob("*.json"))]
    ok = [r for r in recs if r.get("status") == "ok" and "roofline" in r]
    if not ok:
        row("roofline_summary", 0.0,
            f"cells={len(recs)} ok=0 (no usable dry-run records)")
        return
    doms = {}
    for r in ok:
        doms[r["roofline"]["dominant"]] = doms.get(
            r["roofline"]["dominant"], 0) + 1
    mean_compile = float(np.mean([r["compile_s"] for r in ok]))
    row("roofline_summary", float(np.mean([r["total_s"] for r in ok])) * 1e6,
        f"cells={len(recs)} ok={len(ok)} dominant={doms} "
        f"mean_compile_s={mean_compile:.1f}")


# ---------------------------------------------------------- kernel micro
def kernel_micro():
    import jax
    import jax.numpy as jnp
    from repro.models.layers import flash_attention_jnp, naive_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, hd = 1, 1024, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, 2, hd))
    v = jax.random.normal(ks[2], (B, S, 2, hd))

    naive = jax.jit(lambda q, k, v: naive_attention(q, k, v, causal=True,
                                                    window=None))
    flash = jax.jit(lambda q, k, v: flash_attention_jnp(
        q, k, v, causal=True, window=None, q_chunk=256, k_chunk=256))

    for name, fn in [("attn_naive_1k", naive), ("attn_flash_jnp_1k", flash)]:
        fn(q, k, v).block_until_ready()
        t0 = time.time()
        n = 5
        for _ in range(n):
            fn(q, k, v).block_until_ready()
        row(f"kernel_{name}", (time.time() - t0) / n * 1e6,
            f"B{B}xS{S}xH{H}xhd{hd}")

    # MoE dispatch: argsort ranking vs (TK,E) cumsum ranking
    T, E, K = 8192, 64, 4
    eids = jax.random.randint(ks[0], (T, K), 0, E)

    @jax.jit
    def rank_argsort(eids):
        ef = eids.reshape(-1)
        order = jnp.argsort(ef, stable=True)
        se = ef[order]
        start = jnp.searchsorted(se, jnp.arange(E))
        rk = jnp.arange(T * K) - start[se]
        return jnp.zeros((T * K,), jnp.int32).at[order].set(
            rk.astype(jnp.int32))

    @jax.jit
    def rank_cumsum(eids):
        oh = jax.nn.one_hot(eids.reshape(-1), E, dtype=jnp.int32)
        ranks = jnp.cumsum(oh, axis=0) - oh
        return (ranks * oh).sum(-1)

    for name, fn in [("moe_rank_argsort", rank_argsort),
                     ("moe_rank_cumsum", rank_cumsum)]:
        out1 = fn(eids)
        out1.block_until_ready()
        t0 = time.time()
        n = 10
        for _ in range(n):
            fn(eids).block_until_ready()
        row(f"kernel_{name}", (time.time() - t0) / n * 1e6,
            f"T{T}xE{E}xK{K}")
    assert bool(jnp.all(rank_argsort(eids) == rank_cumsum(eids)))


# ------------------------------------------------------- resume overhead
def resume_overhead():
    """Cost of durable checkpointing on the training hot path: the same
    reduced run with and without cadence checkpoints (async saves).  The
    subsystem's contract is < 5% steps/s regression — saves happen on a
    background thread, the loop only pays the host snapshot.

    Conditions run interleaved (base, ckpt, base, ckpt) and each takes
    its best repetition: single-shot wall comparisons on a shared host
    drift more than the effect being measured (the hot-path blocked
    time, reported separately, is the ground truth).  On hosts with
    fewer cores than compute threads + 1 the wall delta also includes
    the background writer competing for cores — a cost the async design
    trades for durability, amortized by the save cadence (every 8 steps
    here; preemption-test runs use stress cadences instead)."""
    import tempfile

    from repro.launch.train import train_main

    steps = 32
    kw = dict(steps=steps, batch=4, seq=64, log_every=0, seed=0)
    base_runs, ck_runs = [], []
    with tempfile.TemporaryDirectory() as td:
        for rep in range(2):
            base_runs.append(train_main("stablelm-1.6b", **kw))
            ck_runs.append(train_main("stablelm-1.6b",
                                      checkpoint_dir=f"{td}/rep{rep}",
                                      checkpoint_every=8, **kw))
    base = max(base_runs, key=lambda r: r["steps_per_s"])
    ck = max(ck_runs, key=lambda r: r["steps_per_s"])
    regression = 1.0 - ck["steps_per_s"] / base["steps_per_s"]
    st = ck["checkpoint"]
    row("resume_overhead", ck["wall_s"] * 1e6 / steps,
        f"steps_per_s base={base['steps_per_s']:.2f} "
        f"ckpt={ck['steps_per_s']:.2f} regression={regression * 100:.1f}% "
        f"saves={st['saves']} save_s={st['save_s']:.2f} "
        f"hot_path_blocked_s={st['blocked_s']:.3f} "
        f"overhead_frac={st['overhead_frac']:.4f} (contract: <5%)")


def write_json(path=None) -> dict:
    """name -> {us_per_call, derived} for every row emitted so far."""
    path = path or ROOT / "BENCH_paper.json"
    report = {
        "schema": 1,
        "bench": "paper_tables",
        "rows": {name: {"us_per_call": round(us, 1), "derived": derived}
                 for name, us, derived in ROWS},
    }
    pathlib.Path(path).write_text(json.dumps(report, indent=1) + "\n")
    return report


def main() -> None:
    print("name,us_per_call,derived")
    table1_pipeline()
    table3_detection()
    table4_ba_models()
    table5_totals()
    roofline_summary()
    kernel_micro()
    resume_overhead()
    write_json()
    print(f"# {len(ROWS)} benchmark rows -> {ROOT / 'BENCH_paper.json'}")


if __name__ == "__main__":
    main()
