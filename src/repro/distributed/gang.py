"""Local gang launcher: run a world-N training job as N rank processes.

This is the path ``repro.launch run train --world_size N`` takes when
invoked *without* ``--dist_rank`` (a user at a shell, or CI): the
parent process stays jax-free, spawns one ``run train`` subprocess per
rank with ``--dist_rank i --coordinator 127.0.0.1:<port>`` appended,
and adopts rank 0's RunReport as the job's result.  The campaign
executor does the same spawn itself (gang admission needs per-rank
process handles) — see ``core/executor.py``.
"""
from __future__ import annotations

import glob
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Mapping, Optional

# libtpu process bounds for a gang of N one-chip ranks on one 2x2 host
_PROCESS_BOUNDS = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1"}


def free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port (bind-to-0).  Racy by nature, but
    the coordinator binds immediately after."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def rank_argv(base_argv: List[str], rank: int, coordinator: str
              ) -> List[str]:
    """Append the per-rank distributed flags to a ``run train`` argv."""
    return list(base_argv) + [f"--dist_rank={rank}",
                              f"--coordinator={coordinator}"]


def tpu_chips() -> int:
    """TPU chips this host exposes, from their device nodes (no jax
    import: the caller spawns the processes that will hold them)."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def rank_envs(env: Mapping[str, str], world: int) -> List[Dict[str, str]]:
    """One environment per rank of a ``world``-rank gang.  Where the
    ranks will run on TPU (a host with chips, and ``JAX_PLATFORMS`` not
    excluding tpu), each rank is bound to its own chip before jax loads,
    through libtpu's per-process environment: its visible chip, the
    gang's process bounds and addresses, its port and task id.  Without
    that every rank would open every chip of the host.  Elsewhere each
    rank gets ``env`` as it is."""
    platforms = env.get("JAX_PLATFORMS", "")
    chips = tpu_chips()
    if not chips or (platforms and "tpu" not in platforms.split(",")):
        return [dict(env) for _ in range(world)]
    if world > chips or world not in _PROCESS_BOUNDS:
        raise ValueError(f"cannot bind a gang of {world} ranks to one chip "
                         f"each on a host with {chips} chips")
    ports = [free_port() for _ in range(world)]
    # a per-process chip bound smaller than the host lets libtpu load
    # once per rank; the host-wide load lock stays on
    gang = {"TPU_PROCESS_BOUNDS": _PROCESS_BOUNDS[world],
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": ",".join(f"localhost:{p}"
                                              for p in ports)}
    return [{**env, **gang, "TPU_VISIBLE_CHIPS": str(r),
             "TPU_PROCESS_PORT": str(ports[r]), "CLOUD_TPU_TASK_ID": str(r)}
            for r in range(world)]


def _src_path() -> str:
    # .../src/repro/distributed/gang.py -> .../src
    return str(Path(__file__).resolve().parents[2])


def run_gang_local(spec, world: int, *,
                   log_dir: Optional[str] = None,
                   timeout_s: Optional[float] = None,
                   grace_s: float = 5.0) -> Dict[str, Any]:
    """Spawn ``world`` rank subprocesses for ``spec`` (a train RunSpec
    whose overrides carry ``world_size``), wait for the gang, and
    return rank 0's report metrics plus a ``gang`` section.  Any rank
    failing kills the rest — gang semantics, not straggler tolerance.
    """
    from repro.api.spec import _encode_scalar
    from repro.core.executor import parse_trailing_report

    coordinator = f"127.0.0.1:{free_port()}"
    base = [sys.executable, "-m", "repro.launch", "run", spec.kind,
            "--arch", spec.arch, "--seed", str(spec.seed),
            "--name", spec.run_name]
    for key, val in sorted(spec.overrides.items()):
        if key in ("dist_rank", "coordinator"):
            continue
        base.append(f"--{key}={_encode_scalar(val)}")

    env = dict(os.environ)
    src = _src_path()
    existing = env.get("PYTHONPATH", "")
    if src not in existing.split(os.pathsep):
        env["PYTHONPATH"] = src + os.pathsep + existing if existing else src

    logs = Path(log_dir) if log_dir else Path(tempfile.mkdtemp(
        prefix=f"gang-{spec.run_name}-"))
    logs.mkdir(parents=True, exist_ok=True)
    procs, outs = [], []
    for r, rank_env in enumerate(rank_envs(env, world)):
        out_p = logs / f"rank{r}.out"
        err_p = logs / f"rank{r}.err"
        outs.append(out_p)
        procs.append(subprocess.Popen(
            rank_argv(base, r, coordinator), env=rank_env,
            stdout=open(out_p, "wb"), stderr=open(err_p, "wb")))
    rcs: List[Optional[int]] = [None] * world
    try:
        # rank 0 finishes last in the happy path (it writes the final
        # checkpoint); wait for it first, then reap the rest
        for r in range(world):
            rcs[r] = procs[r].wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        pass
    finally:
        # graceful teardown of stragglers: SIGTERM (the coordinator's
        # handler flushes a final checkpoint), a shared grace deadline,
        # then SIGKILL — the same escalation the executor applies
        live = [r for r, p in enumerate(procs) if p.poll() is None]
        for r in live:
            procs[r].send_signal(signal.SIGTERM)
        deadline = time.monotonic() + max(0.0, grace_s)
        for r in live:
            try:
                rcs[r] = procs[r].wait(
                    timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                procs[r].send_signal(signal.SIGKILL)
                rcs[r] = procs[r].wait()
        for r, p in enumerate(procs):
            if rcs[r] is None:
                rcs[r] = p.returncode
    if any(rc != 0 for rc in rcs):
        bad = next(r for r, rc in enumerate(rcs) if rc != 0)
        err_tail = ""
        try:
            err_tail = (logs / f"rank{bad}.err").read_text(
                errors="replace")[-2000:]
        except OSError:
            pass
        raise RuntimeError(
            f"gang rank {bad}/{world} exited rc={rcs[bad]} "
            f"(all rcs={rcs}); stderr tail:\n{err_tail}")
    reports = [parse_trailing_report(o.read_text(errors="replace"))
               for o in outs]
    for r, rep in enumerate(reports):
        if rep is None or rep.get("status") == "failed":
            raise RuntimeError(f"gang rank {r} produced no usable "
                               f"RunReport (see {outs[r]})")
    metrics = dict(reports[0].get("metrics") or {})
    metrics["gang"] = {"world_size": world, "coordinator": coordinator,
                       "returncodes": rcs, "log_dir": str(logs),
                       "rank_devices": [(rep.get("metrics") or {}).get(
                           "device") for rep in reports]}
    return metrics
