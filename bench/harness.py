"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name (``spec.py``); its traffic's ``kind``
names the driver, ``bench/drivers/<kind>.py``.  With ``--trace 0`` the
line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, each read by ``bench/metrics/<name>.py``.  The
numbers the check compared, each with its limit, are the last lines on
standard error and the last key of the result line, which is the last
line on standard output.

The run needs the accelerator the cell asks for: without it, it prints
why on standard error and exits with code 3.  JAX's persistent compile
cache is kept in ``.bench_cache/jax`` at the checkout root, whatever the
environment says, so only a cell's first run in a checkout compiles.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import shutil
import sys
import time
import traceback
from typing import Any, Callable, Dict, Optional

import spec
from model_conf import dims
from record import Record

CACHE_DIR = spec.ROOT / ".bench_cache"
EXIT_NO_CHIP = 3
EXIT_BAD_ARGS = 2
EXIT_FAILED = 1


class NoChip(RuntimeError):
    pass


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def use_cache_dir() -> None:
    """Fix the compile cache inside the checkout and keep the TPU
    runtime's logs off fixed paths; must run before jax is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR / "jax")
    os.environ["TPU_LOG_DIR"] = "disabled"


def cache_every_program() -> None:
    """Keep every compiled program in the checkout's cache, however quick
    its compile, so a cell's later runs compile nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def arch_config(conf: Dict[str, Any]):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig
    prog = conf["program"]
    dm = dims(conf)
    return ArchConfig(
        name=conf["name"], family="dense", source=conf["source"],
        n_layers=dm.layers, d_model=dm.d_model, n_heads=dm.heads,
        n_kv_heads=dm.kv_heads, d_ff=dm.d_ff, vocab=dm.vocab,
        tie_embeddings=dm.tied, norm=dm.norm, rope_theta=dm.rope_theta,
        sliding_window=None, param_dtype=prog["param_dtype"],
        optimizer=prog["optimizer"],
        attention_backend=prog["attention_backend"])


class Tracer:
    """Profiler on and off around a traced window; the window itself is
    the host span ``bench.traced``."""

    def __init__(self, logdir):
        self.logdir = str(logdir)
        self.on = False
        self._ann = None
        self._t0 = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        jax.profiler.start_trace(self.logdir)
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        self.on = True

    def stop(self, rec: Record) -> None:
        import jax
        import trace
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
        rec.traced = (self._t0, t1)
        rec.trace = trace.load(trace.find_xplane(self.logdir))


@dataclasses.dataclass
class Context:
    cell: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    dims: Any
    arch: Any
    tracer: Tracer
    control: Optional[str] = None

    def memory_peak(self) -> Optional[int]:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()[:self.chips]]
        peaks = [p for p in peaks if p is not None]
        return int(max(peaks)) if peaks else None


def device_info(chips: int, require_chip: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found only {devs[0].platform} "
                         f"devices; this benchmark runs on the chip only")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(args: argparse.Namespace, *, t_start: float,
             require_chip: bool = True, fault: Optional[Callable] = None,
             overrides: Optional[Dict[str, Any]] = None,
             control: Optional[str] = None) -> Dict[str, Any]:
    """Run one cell and return the result line as a dict.  ``overrides``
    (tests) replaces parts of the resolved cell: "config", "traffic",
    "limits", "peak".  ``control`` (bench/control.py) names what the
    driver puts in the program's place in the check: "fp8", the
    reference a precision below the configuration's, or (training)
    "half_positions", the reference with half the positions left out of
    the loss."""
    cell = spec.resolve(args.workload)
    cell.update(overrides or {})
    wl = cell["workload"]
    chips = int(wl["chips"])
    device = device_info(chips, require_chip)
    import flops
    peak = cell.get("peak") or flops.peaks(device["kind"])
    bench = spec.benchmark()
    group = "per_layer" if args.trace else "end_to_end"
    wanted = spec.cell_metrics(bench, args.workload, group)
    readers = {m["name"]: spec.metric_reader(m["name"]) for m in wanted}
    kind = cell["traffic"]["kind"]
    driver = importlib.import_module(f"drivers.{kind}")
    conf = cell["config"]
    ctx = Context(
        cell=args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), chips=chips, t_start=t_start, config=conf,
        traffic=cell["traffic"], limits=cell.get("limits", wl["limits"]),
        dims=dims(conf), arch=arch_config(conf),
        tracer=Tracer(CACHE_DIR / "trace" / args.workload), control=control)
    rec = Record(cell=args.workload, kind=kind, seed=args.seed,
                 seconds=args.seconds, chips=chips, dims=ctx.dims,
                 traffic=ctx.traffic, peak=peak)
    driver.run(ctx, rec, fault=fault)

    metrics = {}
    for m in wanted:
        v = readers[m["name"]](rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device["memory_peak_bytes"] = rec.memory_peak_bytes
    line: Dict[str, Any] = {
        "correct": rec.correct, "attempted": rec.attempted,
        "failed": rec.failed, "metrics": metrics, "device": device}
    if args.trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s()
        device["window_s"] = rec.trace.window_s
        line["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                             "idle_gaps": rec.trace.labelled_gaps(10)}
    line["notes"] = rec.notes
    line["checks"] = rec.checks
    return line


def main(argv, *, t_start: float) -> int:
    args = parse(argv)
    use_cache_dir()
    try:
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"bench: cannot import the program ({e}); run from a "
              f"checkout that holds src/", file=sys.stderr)
        return EXIT_FAILED
    cache_every_program()
    try:
        line = run_cell(args, t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    except FileNotFoundError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_BAD_ARGS
    except Exception:
        traceback.print_exc()
        return EXIT_FAILED
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
