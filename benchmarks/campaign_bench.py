"""Campaign execution benchmark -> BENCH_campaign.json.

Reproduces the paper's parallel-campaign accounting on *real processes*:
a tiny-config train campaign (default 12 runs) executed through
``Orchestrator.run_cluster`` at workers ∈ {1, 2, 4}, measuring the real
wall-clock makespan (the paper's "five and a half months on a single
server" vs cluster-parallel argument, at laptop scale), queue-wait
p50/p95, and — with injected SIGKILL preemption — goodput and the steps
salvaged by checkpoint resume.

Every subprocess is pinned to one XLA host thread (see
``SINGLE_THREAD_ENV``) so workers scale across cores instead of fighting
over them; that makes the workers=N sweep an honest strong-scaling
measurement on any core count.

    PYTHONPATH=src python benchmarks/campaign_bench.py \
        [--runs 12] [--steps 4] [--workers 1,2,4] [--kill 2] \
        [--evict-runs 2] [--workdir DIR] [--out BENCH_campaign.json]

Exits nonzero if any campaign run fails to complete — CI uses that as
the completion assertion for its preempt-one-run smoke.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.api import RunSpec                                  # noqa: E402
from repro.core import ChaosSpec, JobState, NodeSpec, Orchestrator, \
    PersistentVolume, Resources                                # noqa: E402

# One XLA/BLAS thread per worker subprocess (including LLVM codegen,
# which XLA otherwise parallelizes): the sweep then measures scheduling,
# not intra-op thread contention.
SINGLE_THREAD_ENV = {
    "XLA_FLAGS": ("--xla_cpu_multi_thread_eigen=false "
                  "intra_op_parallelism_threads=1 "
                  "--xla_cpu_parallel_codegen_split_count=1"),
    "OMP_NUM_THREADS": "1",
    "OPENBLAS_NUM_THREADS": "1",
}

ARCH = "stablelm-1.6b"


# Campaign workers share jax's persistent compile cache: `python -m
# repro.launch` points every attempt at one fixed directory
# (repro/launch/runtime.py), so a retried or resumed attempt reads the
# compiled step back instead of compiling it again.


def build_runs(n: int, steps: int, batch: int, seq: int,
               ckpt_root: Path, ckpt_every: int = 1):
    # checkpoint_async=False: durable synchronous saves (fsynced before
    # the step continues) — the strict-durability regime, and the real
    # disk I/O that concurrent workers overlap with other runs' compute.
    # cpus=1 + run_cluster(pin_cpus=True) turns the request into a real
    # affinity limit (k8s CPU-limit semantics), so workers=1 means one
    # core and the sweep measures scheduling, not thread contention.
    return [RunSpec(kind="train", arch=ARCH, seed=i, name=f"run{i:02d}",
                    resources=Resources(gpus=0, cpus=1, memory_gb=4),
                    overrides={"steps": steps, "batch": batch, "seq": seq,
                               "log_every": 0,
                               "checkpoint_dir": str(ckpt_root / f"ck{i:02d}"),
                               "checkpoint_every": ckpt_every,
                               "checkpoint_async": False})
            for i in range(n)]


def run_campaign(workdir: Path, tag: str, runs, workers: int,
                 chaos=None, **exec_kw) -> dict:
    pvc = PersistentVolume(workdir / tag)
    orch = Orchestrator(pvc)
    orch.submit_runs(runs)
    t0 = time.time()
    recs = orch.run_cluster(workers=workers, chaos=chaos,
                            worker_env=SINGLE_THREAD_ENV, pin_cpus=True,
                            attempt_timeout_s=600, **exec_kw)
    wall = time.time() - t0
    summary = orch.last_campaign_summary
    ok = all(r.state == JobState.SUCCEEDED for r in recs.values())
    return {"tag": tag, "ok": ok, "wall_s": round(wall, 2), **summary}


def _final_tree(ckpt_dir: Path):
    from repro.checkpoint import list_checkpoints, load_checkpoint
    ckpts = list_checkpoints(ckpt_dir)
    if not ckpts:
        return None, None
    tree, step = load_checkpoint(ckpts[-1][1])
    return tree, int(step)


def straggler_leg(workdir: Path, args) -> dict:
    """One victim run stalled REPRO_STEP_DELAY_S per step (wall-only:
    the math is untouched).  The same campaign runs FIFO and with
    ``speculate`` — the duplicate races the victim at full speed and
    first-finisher-wins; the victim's final checkpoint must be bitwise
    identical across both legs."""
    import numpy as np
    legs = {}
    for tag, speculate in (("straggler_fifo", False),
                           ("straggler_spec", True)):
        runs = build_runs(args.straggler_runs, args.steps, args.batch,
                          args.seq, workdir / f"ckpt-{tag}")
        legs[tag] = run_campaign(
            workdir, tag, runs, args.straggler_workers,
            speculate=speculate,
            straggler_env={"run00": {"REPRO_STEP_DELAY_S":
                                     str(args.straggler_delay_s)}})
        print(f"{tag}: makespan={legs[tag]['makespan_s']}s "
              f"speculation={legs[tag]['speculation']} "
              f"ok={legs[tag]['ok']}", flush=True)

    a, step_a = _final_tree(workdir / "ckpt-straggler_fifo" / "ck00")
    b, step_b = _final_tree(workdir / "ckpt-straggler_spec" / "ck00")
    bitwise = (a is not None and b is not None and step_a == step_b
               and set(a) == set(b)
               and all(np.array_equal(a[k], b[k]) for k in a))
    fifo, spec = legs["straggler_fifo"], legs["straggler_spec"]
    return {
        "victim": "run00",
        "step_delay_s": args.straggler_delay_s,
        "runs": args.straggler_runs,
        "workers": args.straggler_workers,
        "ok": fifo["ok"] and spec["ok"] and bitwise,
        "fifo_makespan_s": fifo["makespan_s"],
        "speculate_makespan_s": spec["makespan_s"],
        "makespan_improvement": round(
            fifo["makespan_s"] / spec["makespan_s"], 3)
        if spec["makespan_s"] else None,
        "speculation": spec["speculation"],   # launches/wins/losses/wall
        "victim_bitwise_identical": bool(bitwise),
    }


def sched_kill_leg(workdir: Path, args) -> dict:
    """SIGKILL the *scheduler process* mid-campaign (the driver is
    ``python -m repro.launch campaign run``), restart it with
    ``--resume-campaign``, and account recovery: completed jobs are
    never re-executed, live orphans are adopted or re-queued, and the
    campaign finishes."""
    root = workdir / "schedkill"
    root.mkdir(parents=True, exist_ok=True)
    runs = build_runs(args.sched_kill_runs, args.steps, args.batch,
                      args.seq, root / "ckpt")
    jobs_file = root / "jobs.json"
    jobs_file.write_text(json.dumps([r.to_dict() for r in runs]))
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ, **SINGLE_THREAD_ENV, "PYTHONPATH": src}
    argv = [sys.executable, "-m", "repro.launch", "campaign", "run",
            "--jobs", str(jobs_file), "--workdir", str(root),
            "--workers", "2", "--retry-backoff-base", "0.2"]
    events_path = root / "repro-data" / "campaign" / "events.jsonl"

    def succeeded_jobs():
        try:
            lines = events_path.read_text(errors="replace").splitlines()
        except OSError:
            return set()
        out = set()
        for ln in lines:
            try:
                e = json.loads(ln)
            except ValueError:
                continue
            if e.get("event") == "succeeded":
                out.add(e["job"])
        return out

    with open(root / "sched1.log", "wb") as log:
        proc = subprocess.Popen(argv, env=env, stdout=log, stderr=log)
    deadline = time.time() + 600
    done_before = set()
    while time.time() < deadline and proc.poll() is None:
        done_before = succeeded_jobs()
        if len(done_before) >= 2:
            break
        time.sleep(0.5)
    proc.kill()
    proc.wait()

    t0 = time.time()
    res = subprocess.run(argv + ["--resume-campaign"], env=env,
                         capture_output=True, timeout=1200)
    resume_wall = time.time() - t0
    lines = events_path.read_text(errors="replace").splitlines()
    events = []
    for ln in lines:
        try:
            events.append(json.loads(ln))
        except ValueError:
            pass
    resume_idx = max((i for i, e in enumerate(events)
                      if e.get("event") == "campaign_resume"), default=0)
    re_executed = sorted({e["job"] for e in events[resume_idx:]
                          if e.get("event") == "started"
                          and e.get("job") in done_before})
    succeeded = succeeded_jobs()
    from repro.core import replay_events
    state = replay_events(lines)
    ok = (res.returncode == 0 and len(succeeded) == len(runs)
          and not re_executed and state["consistent"])
    row = {
        "runs": args.sched_kill_runs,
        "killed_scheduler_after_done": len(done_before),
        "resume_wall_s": round(resume_wall, 2),
        "re_executed_completed_jobs": re_executed,
        "orphans_adopted": sum(1 for e in events
                               if e.get("event") == "adopted"),
        "orphans_requeued": sum(1 for e in events
                                if e.get("event") == "orphan_requeued"),
        "succeeded": len(succeeded),
        "replay_consistent": state["consistent"],
        "ok": ok,
    }
    if not ok:
        sys.stderr.write(res.stdout.decode(errors="replace")[-2000:])
        sys.stderr.write(res.stderr.decode(errors="replace")[-2000:])
    print(f"schedkill: killed after {row['killed_scheduler_after_done']} "
          f"done, resume adopted={row['orphans_adopted']} "
          f"requeued={row['orphans_requeued']} "
          f"re_executed={re_executed} ok={ok}", flush=True)
    return row


def placement_leg(workdir: Path, args) -> dict:
    """The same job set executed once per placement policy on the same
    heterogeneous two-node inventory, reporting each policy's makespan
    and the event-log-derived utilization ledger (busy vs goodput AUC
    per node) — the BENCH surface for `campaign run --placement`.

    Each policy gets a fresh checkpoint root: a shared one would let a
    later policy resume the earlier policy's checkpoints and measure
    nothing."""
    inventory = [
        NodeSpec("small", gpus=0, gpu_memory_gb=0.0, cpus=2,
                 memory_gb=8.0),
        NodeSpec("big", gpus=0, gpu_memory_gb=0.0, cpus=4,
                 memory_gb=16.0),
    ]
    policies = [p for p in args.placement_sweep.split(",") if p]
    legs = {}
    for pol in policies:
        runs = build_runs(args.placement_runs, args.steps, args.batch,
                          args.seq, workdir / f"ckpt-place-{pol}")
        row = run_campaign(workdir, f"placement_{pol}", runs,
                           args.placement_workers, inventory=inventory,
                           placement=pol)
        util = (row.get("utilization") or {}).get("cluster") or {}
        legs[pol] = {
            "ok": row["ok"],
            "makespan_s": row["makespan_s"],
            "queue_wait_s": row["queue_wait_s"],
            "utilization": row.get("utilization"),
        }
        print(f"placement={pol}: makespan={row['makespan_s']}s "
              f"cpu_busy_util={util.get('busy_cpu_util')} "
              f"cpu_goodput_util={util.get('goodput_cpu_util')} "
              f"ok={row['ok']}", flush=True)
    return {
        "runs": args.placement_runs,
        "workers": args.placement_workers,
        "inventory": [n.to_dict() for n in inventory],
        "policies": legs,
        "ok": all(l["ok"] for l in legs.values()) if legs else False,
    }


def evict_leg(workdir: Path, args) -> dict:
    """Graceful vs hard preemption: the same chaos campaign run twice,
    once with SIGKILL victims (lose everything since the last cadence
    checkpoint) and once with SIGTERM victims (the in-process handler
    salvages a final checkpoint inside the grace window, so the resume
    restarts from the exact preempted step).  Reports the steps each
    signal class salvaged — the measured value of the SIGTERM
    contract."""
    import signal as _sig
    legs = {}
    for tag, sig in (("evict_sigkill", _sig.SIGKILL),
                     ("evict_sigterm", _sig.SIGTERM)):
        runs = build_runs(args.evict_runs, args.steps, args.batch,
                          args.seq, workdir / f"ckpt-{tag}",
                          ckpt_every=args.evict_ckpt_every)
        names = [r.run_name for r in runs]
        chaos = ChaosSpec.sample(names, fraction=1.0, seed=7,
                                 after_checkpoints=1, signal=int(sig))
        legs[tag] = run_campaign(workdir, tag, runs, args.evict_workers,
                                 chaos=chaos, grace_s=60.0)
        print(f"{tag}: salvaged="
              f"{legs[tag]['steps_salvaged_by_resume']} "
              f"preemptions={legs[tag]['preemptions']} "
              f"goodput={legs[tag]['wall_goodput']} "
              f"ok={legs[tag]['ok']}", flush=True)
    kill, term = legs["evict_sigkill"], legs["evict_sigterm"]
    return {
        "runs": args.evict_runs,
        "workers": args.evict_workers,
        "checkpoint_every": args.evict_ckpt_every,
        "ok": kill["ok"] and term["ok"],
        "sigkill_salvaged_steps": kill["steps_salvaged_by_resume"],
        "sigterm_salvaged_steps": term["steps_salvaged_by_resume"],
        "sigterm_extra_steps_salvaged":
            term["steps_salvaged_by_resume"]
            - kill["steps_salvaged_by_resume"],
        "sigkill_goodput": kill["wall_goodput"],
        "sigterm_goodput": term["wall_goodput"],
    }


# Two calibration burns: ALU-bound, and memory-streaming — training
# steps/compiles are memory-bound, so the memory burn is the ceiling
# that actually binds a train campaign.
_BURNS = {
    "alu": "x=0\nfor i in range(20_000_000): x += i",
    "mem": "b = bytes(60_000_000)\nn = 0\nfor _ in range(10): n += b.count(0)",
}


def host_parallel_ceiling(nproc: int = 4) -> dict:
    """Calibrate what concurrent-process speedup this host can
    physically deliver (cloud containers are often oversubscribed
    and/or memory-bandwidth-bound: this repo's 2-vCPU dev container
    measures ~1.2-1.4x for memory-streaming work, which is what caps a
    concurrent train campaign).  The campaign speedup is reported
    alongside these ceilings so the number is interpretable on any
    host."""
    def burn(src, n):
        t0 = time.time()
        ps = [subprocess.Popen([sys.executable, "-c", src])
              for _ in range(n)]
        for p in ps:
            p.wait()
        return time.time() - t0

    out = {"cpus_visible": len(os.sched_getaffinity(0))
           if hasattr(os, "sched_getaffinity") else os.cpu_count(),
           "procs": nproc}
    for name, src in _BURNS.items():
        burn(src, 1)                           # warm the interpreter path
        serial = burn(src, 1)
        t_par = burn(src, nproc)
        out[name] = {"serial_s": round(serial, 2),
                     "parallel_s": round(t_par, 2),
                     "speedup_ceiling":
                         round(nproc * serial / t_par, 3) if t_par else 0.0}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=12)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--workers", default="1,2,4",
                    help="comma-separated worker counts to sweep")
    ap.add_argument("--kill", type=int, default=2,
                    help="runs to SIGKILL (after their first checkpoint) "
                         "in the chaos campaign; 0 disables")
    ap.add_argument("--chaos-workers", type=int, default=2)
    ap.add_argument("--straggler-runs", type=int, default=0,
                    help="straggler leg: campaign size (0 disables); one "
                         "victim is stalled per step and raced FIFO vs "
                         "--speculate")
    ap.add_argument("--straggler-delay-s", type=float, default=5.0)
    ap.add_argument("--straggler-workers", type=int, default=3)
    ap.add_argument("--sched-kill-runs", type=int, default=0,
                    help="scheduler-kill leg: campaign size (0 disables); "
                         "SIGKILLs the 'campaign run' scheduler process "
                         "and recovers with --resume-campaign")
    ap.add_argument("--evict-runs", type=int, default=0,
                    help="eviction leg: campaign size (0 disables); runs "
                         "the same chaos campaign under SIGKILL and "
                         "SIGTERM and reports the steps each salvaged")
    ap.add_argument("--evict-workers", type=int, default=2)
    ap.add_argument("--placement-sweep", default="",
                    help="comma-separated placement policies (e.g. "
                         "best_fit,worst_fit,pack) to race on the same "
                         "job set + heterogeneous inventory; empty "
                         "disables the leg")
    ap.add_argument("--placement-runs", type=int, default=6)
    ap.add_argument("--placement-workers", type=int, default=4)
    ap.add_argument("--evict-ckpt-every", type=int, default=3,
                    help="cadence for the eviction leg (sparser than "
                         "the sweep's 1, so the SIGTERM salvage has "
                         "steps to save)")
    ap.add_argument("--workdir", default=None,
                    help="campaign work root (default: a temp dir); CI "
                         "passes an explicit dir to upload the event log")
    ap.add_argument("--out", default="BENCH_campaign.json")
    args = ap.parse_args(argv)

    workdir = Path(args.workdir or tempfile.mkdtemp(prefix="campbench-"))
    workdir.mkdir(parents=True, exist_ok=True)
    worker_counts = [int(w) for w in args.workers.split(",") if w]

    host = host_parallel_ceiling()
    print(f"host ceilings: alu={host['alu']['speedup_ceiling']}x "
          f"mem={host['mem']['speedup_ceiling']}x over "
          f"{host['cpus_visible']} visible cpus", flush=True)

    # warm the OS page cache (interpreter + jax imports) so the first
    # sweep isn't penalized with cold disk reads the others skip
    warm = build_runs(1, args.steps, args.batch, args.seq,
                      workdir / "ckpt-warm")
    run_campaign(workdir, "warmup", warm, 1)
    print("warmup done", flush=True)

    rows = []
    for w in worker_counts:
        runs = build_runs(args.runs, args.steps, args.batch, args.seq,
                          workdir / f"ckpt-w{w}")
        row = run_campaign(workdir, f"workers{w}", runs, w)
        rows.append(row)
        print(f"workers={w}: makespan={row['makespan_s']}s "
              f"goodput={row['wall_goodput']} "
              f"queue_p50={row['queue_wait_s']['p50']}s "
              f"p95={row['queue_wait_s']['p95']}s ok={row['ok']}",
              flush=True)

    base = next((r for r in rows if r["workers"] == 1), rows[0])
    if base["workers"] != 1:
        print(f"note: --workers omits 1; speedups are vs the "
              f"workers={base['workers']} row", file=sys.stderr)
    for row in rows:
        row["speedup_vs_baseline"] = round(
            base["makespan_s"] / row["makespan_s"], 3) \
            if row["makespan_s"] else 0.0

    chaos_row = None
    if args.kill > 0:
        runs = build_runs(args.runs, args.steps, args.batch, args.seq,
                          workdir / "ckpt-chaos")
        names = [r.run_name for r in runs]
        chaos = ChaosSpec.sample(names, fraction=args.kill / len(names),
                                 seed=7, after_checkpoints=1)
        chaos_row = run_campaign(workdir, "chaos", runs,
                                 args.chaos_workers, chaos=chaos)
        chaos_row["killed_jobs"] = list(chaos.kill_jobs)
        ref = next((r for r in rows
                    if r["workers"] == args.chaos_workers), None)
        if ref:
            chaos_row["makespan_overhead_vs_no_chaos"] = round(
                chaos_row["makespan_s"] / ref["makespan_s"], 3)
        print(f"chaos(workers={args.chaos_workers}, "
              f"kill={len(chaos.kill_jobs)}): "
              f"makespan={chaos_row['makespan_s']}s "
              f"preemptions={chaos_row['preemptions']} "
              f"goodput={chaos_row['wall_goodput']} "
              f"salvaged_steps={chaos_row['steps_salvaged_by_resume']} "
              f"ok={chaos_row['ok']}", flush=True)

    straggler_row = (straggler_leg(workdir, args)
                     if args.straggler_runs > 0 else None)
    sched_kill_row = (sched_kill_leg(workdir, args)
                      if args.sched_kill_runs > 0 else None)
    evict_row = evict_leg(workdir, args) if args.evict_runs > 0 else None
    placement_row = (placement_leg(workdir, args)
                     if args.placement_sweep else None)

    fastest = min(rows, key=lambda r: r["makespan_s"])
    ceiling = host["mem"]["speedup_ceiling"]
    out = {
        "benchmark": "campaign_exec",
        "config": {"runs": args.runs, "steps": args.steps,
                   "batch": args.batch, "seq": args.seq, "arch": ARCH,
                   "worker_env": SINGLE_THREAD_ENV, "pin_cpus": True},
        "host": host,
        "rows": rows,
        "chaos": chaos_row,
        "straggler": straggler_row,
        "sched_kill": sched_kill_row,
        "evict_signal": evict_row,
        "placement": placement_row,
        "headline": {
            "baseline_workers": base["workers"],
            "best_speedup_vs_baseline": fastest["speedup_vs_baseline"],
            "best_workers": fastest["workers"],
            "baseline_makespan_s": base["makespan_s"],
            # fraction of the host's physically-available concurrency
            # (memory-streaming ceiling — what binds a train campaign)
            # the executor converts into makespan reduction; >= 2x
            # absolute speedup is expected wherever the host's own
            # ceiling exceeds 2x (e.g. 4-core CI runners), while
            # oversubscribed 2-vCPU dev boxes measure a ceiling well
            # under 2
            "speedup_vs_host_ceiling":
                round(fastest["speedup_vs_baseline"] / ceiling, 3)
                if ceiling else None,
            "goodput_under_preemption":
                chaos_row["wall_goodput"] if chaos_row else None,
        },
    }
    Path(args.out).write_text(json.dumps(out, indent=1, sort_keys=True)
                              + "\n")
    print(f"wrote {args.out}: best speedup "
          f"{out['headline']['best_speedup_vs_baseline']}x at "
          f"workers={out['headline']['best_workers']}")
    extra = [("straggler", straggler_row), ("sched_kill", sched_kill_row),
             ("evict_signal", evict_row), ("placement", placement_row)]
    failed = [r["tag"] for r in rows + ([chaos_row] if chaos_row else [])
              if not r["ok"]]
    failed += [tag for tag, r in extra if r is not None and not r["ok"]]
    if failed:
        print(f"FAILED campaigns: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
