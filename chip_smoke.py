#!/usr/bin/env python3
"""Smoke test of the main path on a TPU chip, through the entry points a
user calls, at the published widths of the configurations.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # one host with four chips
    python3 chip_smoke.py --rehearse  # the one-chip flow on CPU, reduced

On one chip it runs, in turn:

1. the Pallas kernels (flash attention forward and gradient at the
   stablelm-1.6b and granite-3-2b head layouts, the SSD scan forward at
   mamba2-2.7b widths) against their ``ref.py`` oracles;
2. ``run train`` of full-width stablelm-1.6b (batch 4 x 512): six steps
   uninterrupted, then a run that checkpoints at step 3 and is preempted
   before step 4, then ``--resume`` to step 6; the resumed losses must
   equal the uninterrupted ones, and the second process must read the
   first one's compiled step from the persistent compile cache;
3. ``campaign run`` of two such jobs on one worker, one of them killed
   with SIGKILL after its first checkpoint; both must succeed;
4. ``run serve`` of full-width granite-3-2b answering greedy requests
   with one decode compilation.

With ``--chips 4`` it runs only the data-parallel path and its
reference: ``run train --world_size 4`` (one chip per rank) against one
process at the same global batch with ``attention_backend=jnp``; the
losses must agree within rtol 5e-4.

This script never imports jax.  Every phase is a child process, run one
after the other, so one process holds the chip at a time.  Each phase's
result is printed on its own line; the last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed
on a TPU.  Without a TPU the first phase fails and the script exits
nonzero.  Work files (checkpoints, campaign, logs) go to ``.chip_smoke/``
and checkpoints are deleted as soon as their phase ends.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.api.spec import _encode_scalar              # noqa: E402
from repro.core.executor import parse_trailing_report  # noqa: E402
from repro.launch.runtime import use_compile_cache     # noqa: E402

WORK = ROOT / ".chip_smoke"
DEADLINE_S = 1140            # the whole run, compilation included
TRAIN_ARCH, SERVE_ARCH = "stablelm-1.6b", "granite-3-2b"
STEPS, CKPT_AT, PREEMPT_AT = 6, 3, 4
DP_RTOL = 5e-4               # world-N vs world-1 losses (grad-mean order)


class PhaseError(RuntimeError):
    pass


# ---------------------------------------------------------------- children
class Runner:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.t0 = time.monotonic()
        self.env = dict(os.environ)
        src = str(ROOT / "src")
        self.env["PYTHONPATH"] = os.pathsep.join(
            p for p in (src, self.env.get("PYTHONPATH")) if p)
        (WORK / "logs").mkdir(parents=True, exist_ok=True)

    def child(self, name: str, argv, *, expect_rc=(0,)) -> str:
        """Run one child process to its end (or kill its whole process
        group at the deadline); return its stdout."""
        left = DEADLINE_S - (time.monotonic() - self.t0)
        if left <= 10:
            raise PhaseError(f"{name}: no time left before the deadline")
        out_p, err_p = WORK / "logs" / f"{name}.out", WORK / "logs" / f"{name}.err"
        t = time.monotonic()
        with open(out_p, "wb") as out, open(err_p, "wb") as err:
            proc = subprocess.Popen([sys.executable, *argv], cwd=ROOT,
                                    env=self.env, stdout=out, stderr=err,
                                    start_new_session=True)
            try:
                rc = proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                rc = None
            finally:
                try:                      # no process of the phase outlives it
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        text = out_p.read_text(errors="replace")
        print(f"# {name}: rc={rc} in {time.monotonic() - t:.1f}s", flush=True)
        if rc not in expect_rc:
            tail = err_p.read_text(errors="replace")[-3000:]
            raise PhaseError(f"{name}: exit {rc} (expected {expect_rc})\n"
                             f"{text[-2000:]}\n{tail}")
        return text

    def run_kind(self, name: str, kind: str, arch: str, expect_ok=True,
                 **overrides) -> dict:
        argv = ["-m", "repro.launch", "run", kind, "--arch", arch,
                "--name", name]
        argv += [f"--{k}={_encode_scalar(v)}" for k, v in overrides.items()]
        report = parse_trailing_report(self.child(
            name, argv, expect_rc=(0,) if expect_ok else (1,)))
        if report is None:
            raise PhaseError(f"{name}: printed no RunReport")
        if report.get("status") == ("failed" if expect_ok else "succeeded"):
            raise PhaseError(f"{name}: {report.get('error')}")
        return report

    def check_device(self, name: str, device: dict, count=None) -> dict:
        if not self.rehearse and (device or {}).get("platform") != "tpu":
            raise PhaseError(f"{name} ran on {device}, not on a TPU")
        if count is not None and device.get("count") != count:
            raise PhaseError(f"{name}: {device.get('count')} devices, "
                             f"expected {count}")
        return device

    # sizes: the published widths on the chip, reduced for a rehearsal
    def train_size(self) -> dict:
        if self.rehearse:
            return {"full": False, "batch": 4, "seq": 64}
        return {"full": True, "batch": 4, "seq": 512}


def show(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields, sort_keys=True, default=str)}",
          flush=True)


def _losses_match(a, b) -> dict:
    import math
    diff = max((abs(x - y) / max(abs(y), 1e-30) for x, y in zip(a, b)),
               default=math.inf)
    return {"bitwise": list(a) == list(b), "max_rel_diff": diff,
            "ok": len(a) == len(b) and diff <= 1e-6}


# ------------------------------------------------------------------ phases
def phase_kernels(r: Runner) -> dict:
    argv = [str(Path(__file__).resolve()), "--phase", "kernels"]
    if r.rehearse:
        argv.append("--rehearse")
    res = json.loads(r.child("kernels", argv).strip().splitlines()[-1])
    show("kernels", **res)
    bad = [k for k, v in res["checks"].items() if not v["ok"]]
    if bad:
        raise PhaseError(f"kernels disagree with ref.py: {bad}")
    return r.check_device("kernels", res["device"])


def phase_train(r: Runner) -> dict:
    size = r.train_size()
    ck = WORK / "ckpt-train"
    shutil.rmtree(ck, ignore_errors=True)
    common = dict(size, steps=STEPS, log_every=1)
    a = r.run_kind("train-a", "train", TRAIN_ARCH, **common)["metrics"]
    b_rep = r.run_kind("train-b", "train", TRAIN_ARCH, expect_ok=False,
                       checkpoint_dir=str(ck), checkpoint_every=CKPT_AT,
                       checkpoint_keep=1, preempt_at_step=PREEMPT_AT,
                       **common)
    if "Preemption" not in (b_rep.get("error") or ""):
        raise PhaseError(f"train-b: expected a preemption, got "
                         f"{b_rep.get('error')}")
    c = r.run_kind("train-c", "train", TRAIN_ARCH, checkpoint_dir=str(ck),
                   checkpoint_keep=1, resume=True, **common)["metrics"]
    shutil.rmtree(ck, ignore_errors=True)
    device = r.check_device("train", a["device"])
    losses = a["losses"]
    resume = _losses_match(c["losses"], losses[CKPT_AT:])
    # the preempted run cannot report its losses: its checkpoint is the
    # evidence, and the resumed run starts from it
    warm = c["compile"]
    show("train", arch=a["arch"], params=a["params"], kernels=a["kernels"],
         losses=losses, finite=all(map(_finite, losses)),
         resumed_from_step=c["resumed_from_step"],
         resumed_losses=c["losses"], resume_match=resume,
         compile_cold=a["compile"], compile_warm=warm,
         peak_bytes_in_use=device.get("peak_bytes_in_use"),
         checkpoint=c.get("checkpoint"), device=device)
    if not all(map(_finite, losses)):
        raise PhaseError("train: non-finite loss")
    if c["resumed_from_step"] != CKPT_AT or not resume["ok"]:
        raise PhaseError(f"train: resume from {c['resumed_from_step']} "
                         f"does not match: {resume}")
    if not r.rehearse and a["kernels"].get("attention") != "pallas":
        raise PhaseError(f"train: attention path {a['kernels']}, "
                         f"expected the Pallas kernel")
    want_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
    if warm["cache_dir"] != want_dir or warm["cache_hits"] < 1:
        raise PhaseError(f"train: the resumed process did not hit the "
                         f"compile cache in {want_dir}: {warm}")
    return device


def phase_campaign(r: Runner) -> dict:
    size = r.train_size()
    wd = WORK / "campaign"
    shutil.rmtree(wd, ignore_errors=True)
    wd.mkdir(parents=True)
    jobs = []
    # camp-a checkpoints at step 2 and is killed after it; camp-b saves
    # only at its end
    for name, every in (("camp-a", 2), ("camp-b", 0)):
        jobs.append({"kind": "train", "arch": TRAIN_ARCH, "name": name,
                     "overrides": {**size, "steps": 4, "log_every": 1,
                                   "checkpoint_dir": str(wd / f"ck-{name}"),
                                   "checkpoint_every": every,
                                   "checkpoint_keep": 1}})
    (wd / "jobs.json").write_text(json.dumps(jobs))
    r.child("campaign", ["-m", "repro.launch", "campaign", "run",
                         "--jobs", str(wd / "jobs.json"),
                         "--workdir", str(wd), "--workers", "1",
                         "--chaos-kill", "camp-a"])
    results = {n: json.loads((wd / "repro-data" / "results" / f"{n}.json")
                             .read_text()) for n in ("camp-a", "camp-b")}
    for n in ("camp-a", "camp-b"):
        shutil.rmtree(wd / f"ck-{n}", ignore_errors=True)
    summary = {n: {"state": v["state"], "attempts": v["attempts"],
                   "chaos_kills": v["chaos_kills"],
                   "resumed_from_step": v["result"]["metrics"].get(
                       "resumed_from_step"),
                   "final_loss": v["result"]["metrics"].get("final_loss"),
                   "device": v["result"]["metrics"].get("device")}
               for n, v in results.items()}
    # same seed and config: the killed-and-resumed job should land where
    # the undisturbed one did
    show("campaign", jobs=summary, final_loss_match=(
        summary["camp-a"]["final_loss"] == summary["camp-b"]["final_loss"]))
    if any(v["state"].lower() != "succeeded" for v in results.values()):
        raise PhaseError(f"campaign: not every job succeeded: {summary}")
    if summary["camp-a"]["chaos_kills"] < 1 or \
            not summary["camp-a"]["resumed_from_step"]:
        raise PhaseError(f"campaign: camp-a was not killed and resumed: "
                         f"{summary['camp-a']}")
    for n, v in summary.items():
        r.check_device(f"campaign {n}", v["device"])
    return summary["camp-a"]["device"]


def phase_serve(r: Runner) -> dict:
    requests, max_tokens = 4, 8
    m = r.run_kind("serve", "serve", SERVE_ARCH, full=not r.rehearse,
                   requests=requests, max_tokens=max_tokens,
                   slots=4)["metrics"]
    show("serve", arch=m["arch"], requests=m["requests"],
         tokens=m["tokens"], decode_compiles=m["decode_compiles"],
         prefill_compiles=m["prefill_compiles"], kernels=m["kernels"],
         ttft_p50_s=m["ttft_p50_s"], tpot_p50_s=m["tpot_p50_s"],
         compile=m["compile"], device=m["device"])
    if m["requests"] != requests or m["tokens"] < requests:
        raise PhaseError(f"serve: answered {m['requests']} of {requests} "
                         f"requests with {m['tokens']} tokens")
    if m["decode_compiles"] != 1:
        raise PhaseError(f"serve: {m['decode_compiles']} decode compiles")
    return r.check_device("serve", m["device"])


def phase_data_parallel(r: Runner, world: int) -> dict:
    size = dict(r.train_size(), steps=4, log_every=1)
    ref = r.run_kind("dp-world1", "train", TRAIN_ARCH,
                     attention_backend="jnp", **size)["metrics"]
    gang = r.run_kind(f"dp-world{world}", "train", TRAIN_ARCH,
                      world_size=world, **size)["metrics"]
    rank_devices = gang["gang"]["rank_devices"]
    chips = [tuple(d["local_ids"]) for d in rank_devices]
    rel = max(abs(x - y) / abs(y)
              for x, y in zip(gang["losses"], ref["losses"]))
    show("data_parallel", world=world, losses_world1=ref["losses"],
         losses_worldN=gang["losses"], max_rel_diff=rel, rtol=DP_RTOL,
         kernels={"world1": ref["kernels"], "worldN": gang["kernels"]},
         rank_devices=rank_devices, compile=gang["compile"],
         peak_bytes_in_use=[d.get("peak_bytes_in_use")
                            for d in rank_devices])
    if len(set(chips)) != world:
        raise PhaseError(f"data_parallel: ranks share devices: {chips}")
    if len(gang["losses"]) != len(ref["losses"]) or rel > DP_RTOL:
        raise PhaseError(f"data_parallel: world {world} losses differ from "
                         f"world 1 by {rel} > {DP_RTOL}")
    for d in rank_devices:
        r.check_device("data_parallel rank", d, count=world)
    return rank_devices[0]


def _finite(x) -> bool:
    return x == x and abs(x) != float("inf")


# ------------------------------------------------------- the kernel child
def kernel_checks(rehearse: bool) -> int:
    """Child process: compare the Pallas kernels with ref.py on the
    first device, at the configurations' head layouts and widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        print(f"no TPU found: jax's first device is {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_ref

    def err(a, b):              # max error relative to the reference's scale
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))

    checks = {}
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    B, S = (1, 256) if rehearse else (2, 1024)
    # (H, Kh, hd) of stablelm-1.6b (MHA) and granite-3-2b (GQA 4:1)
    for arch, (H, Kh, hd) in {"stablelm-1.6b": (32, 32, 64),
                              "granite-3-2b": (32, 8, 64)}.items():
        if rehearse:
            H, Kh = H // 8, Kh // 8 or 1
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, Kh, hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, Kh, hd), jnp.bfloat16)
        co = jax.random.normal(ks[3], (B, S, H, hd), jnp.float32)

        def loss(attn):
            return lambda q, k, v: jnp.sum(attn(q, k, v).astype(
                jnp.float32) * co)
        kern = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, k, v: attention_ref(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True))
            g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
            out_ref = ref(q, k, v)
        g = jax.jit(jax.grad(loss(kern), argnums=(0, 1, 2)))(q, k, v)
        e = {"fwd": err(kern(q, k, v), out_ref)}
        e.update({n: err(a, b) for n, a, b in zip(("dq", "dk", "dv"), g,
                                                   g_ref)})
        checks[f"flash_attention[{arch} H={H} Kh={Kh}]"] = {
            "max_err": e, "tol": 2e-2, "ok": max(e.values()) <= 2e-2}

    # mamba2-2.7b: d_inner 5120 -> 80 heads of 64, d_state 128, chunk 256
    Bs, S, nh, hp, N, chunk = ((1, 128, 8, 64, 32, 32) if rehearse
                               else (1, 512, 80, 64, 128, 256))
    x = jax.random.normal(ks[4], (Bs, S, nh, hp), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[5], (Bs, S, nh)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[6], (nh,)) * 0.3)
    Bm, Cm = (jax.random.normal(kk, (Bs, S, 1, N), jnp.bfloat16) * N ** -0.5
              for kk in jax.random.split(ks[7]))
    y, h = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk, return_state=True))(
        x, dt, A, Bm, Cm)
    with jax.default_matmul_precision("highest"):
        y_ref, h_ref = jax.jit(lambda x, dt, A, B, C: ssd_ref(
            x.astype(jnp.float32), dt, A, B.astype(jnp.float32),
            C.astype(jnp.float32)))(x, dt, A, Bm, Cm)
    e = {"y": err(y, y_ref), "h_final": err(h, h_ref)}
    checks[f"ssd_scan[mamba2-2.7b nh={nh} N={N} chunk={chunk}]"] = {
        "max_err": e, "tol": 2e-2, "ok": max(e.values()) <= 2e-2}

    from repro.launch.runtime import device_report
    print(json.dumps({"checks": checks, "device": device_report()}))
    return 0


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="the same flow on CPU at reduced sizes; checks "
                         "control flow only and prints no chip result")
    ap.add_argument("--phase", choices=("kernels",), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.phase == "kernels":
        return kernel_checks(args.rehearse)

    r = Runner(args.rehearse)
    try:
        if args.chips == 4:
            device = phase_data_parallel(r, 4)
            device = {**device, "count": 4}
        else:
            device = phase_kernels(r)
            phase_train(r)
            phase_campaign(r)
            phase_serve(r)
    except PhaseError as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        for d in WORK.glob("ck*"):
            shutil.rmtree(d, ignore_errors=True)
    if args.rehearse:
        print("rehearsal passed: control flow only, not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
