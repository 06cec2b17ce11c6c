"""What one run saw, in the form every metric reader takes.

A driver fills a :class:`Record` while it sets up, runs the window and
checks the result; ``bench/metrics/<name>.py`` reads one number from it,
or ``None`` where the run had nothing to read."""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class Record:
    cell: str
    kind: str                       # the traffic's driver: "train" | "serve"
    seed: int
    seconds: float
    chips: int
    dims: Any = None                # model_conf.Dims
    traffic: Dict[str, Any] = dataclasses.field(default_factory=dict)
    peak: Dict[str, float] = dataclasses.field(default_factory=dict)
    setup_s: Optional[float] = None
    window_s: Optional[float] = None
    compile: Dict[str, Any] = dataclasses.field(default_factory=dict)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # host spans of the benchmark's own wrappers: (name, t0, t1) on
    # time.perf_counter()
    spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    # training: steps and tokens in the window
    steps: int = 0
    tokens: int = 0
    # serving: one dict per request due in the window (see drivers/serve)
    requests: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    # serving: one entry per scheduler step (t0, t1, decode ticks, decode
    # tokens, key positions attended)
    sched_steps: List[Tuple[float, float, int, int, int]] = \
        dataclasses.field(default_factory=list)
    # the reduced profiler trace (trace.Trace) of a --trace 1 run, and the
    # host-clock bounds of the traced interval
    trace: Any = None
    traced: Optional[Tuple[float, float]] = None
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: Optional[int] = None
    checks: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span, kept here and written into the profiler trace."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def span_total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.spans if n == name)

    def span_count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.spans if n == name)

    def check(self, name: str, value: float, limit: float) -> None:
        """A number the correctness comparison holds against its limit."""
        self.checks[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["value"] <= c["limit"] for c in self.checks.values())
