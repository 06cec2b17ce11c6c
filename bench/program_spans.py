"""The program's own host spans, ``repro.<name>`` (``src/repro/obs.py``),
with their metadata, from a traced run's profile, on the device's clock.

The program writes them with ``jax.profiler.TraceAnnotation`` on the host
plane; ``ProfileEvent.stats`` holds each span's metadata (counters such as
a prefill's real tokens, or a decode step's live slots).  The profiler
puts the device's events on the host's clock with an offset of its own,
a millisecond or more (the device's events sit early), as large as the
gaps between programs; ``clock_offset`` estimates it per trace and
``of`` moves the spans onto the device's events by it.  A program that
writes no spans, as one older than them, gives an empty list, and the
readers then read nothing.  The names are kept here, as ``metric_util.py``
keeps the program and kernel names, so that the benchmark does not import
the program's.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

import harness
import trace
from trace import Event, Interval

PREFIX = "repro."
TRAIN_NEXT_BATCH = "train.next_batch"
SERVE_PREFILL = "serve.prefill"
SERVE_DECODE = "serve.decode"
# the engine's own host work between two decode programs
SERVE_HOST_WORK = ("serve.admit", "serve.capacity", "serve.decode",
                   "serve.emit")
# host events that bound a program run: it starts after one of the first
# kind begins (the runtime hands it to the device; the program dispatches
# it) and ends before one of the second kind ends (the runtime sees it
# done; the program has read its output back)
HANDED = ("DoEnqueueProgram", PREFIX + "serve.decode", PREFIX + "train.step")
SEEN = ("tpu::System::Execute=>Done", PREFIX + "serve.readback",
        PREFIX + "train.loss_readback")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str                   # without the prefix
    start: float                # ns
    end: float
    meta: Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Profile:
    spans: Tuple[Span, ...]     # on the host's clock, by start
    handed: Tuple[float, ...]   # starts of the HANDED events, ns
    seen: Tuple[float, ...]     # ends of the SEEN events, ns


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float) -> Profile:
    from jax.profiler import ProfileData

    spans, handed, seen = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for e in (e for line in plane.lines for e in line.events):
            if e.name in HANDED:
                handed.append(e.start_ns)
            if e.name in SEEN:
                seen.append(e.end_ns)
            if e.name.startswith(PREFIX):
                spans.append(Span(e.name[len(PREFIX):], e.start_ns,
                                  e.end_ns, dict(e.stats)))
    return Profile(tuple(sorted(spans, key=lambda s: s.start)),
                   tuple(sorted(handed)), tuple(sorted(seen)))


def load(path: str) -> Profile:
    """The ``repro.*`` spans and the bounding host events of one
    ``.xplane.pb`` file; one load per file."""
    return _load(path, os.path.getmtime(path))


def clock_offset(programs: Sequence[Event], handed: Sequence[float],
                 seen: Sequence[float]) -> float:
    """How far (ns) the device's events sit early on the host's clock.

    A program run starts after the host handed it over and ends before
    the host sees it end, so the offset d keeps ``h - start <= d <= s -
    end`` for each run and its nearest handed event h and seen event s.
    A run queued behind another is nearest to another run's host event;
    the bounds that lie beyond the median of the other side's are left
    out.  Gives the middle of the bounds left, or 0 with none."""
    def nearest(ts, t):
        return min(ts, key=lambda x: abs(x - t))

    lo = [nearest(handed, p.start) - p.start for p in programs if handed]
    hi = [nearest(seen, p.end) - p.end for p in programs if seen]
    if lo and hi:
        lo, hi = ([x for x in lo if x <= np.median(hi)],
                  [y for y in hi if y >= np.median(lo)])
    bounds = ([max(lo)] if lo else []) + ([min(hi)] if hi else [])
    return float(np.mean(bounds)) if bounds else 0.0


def of(rec) -> Optional[List[Span]]:
    """The spans of a traced run, on the device's clock, clipped to its
    window, or None for a run with no trace.  The file is where
    ``harness.Tracer`` wrote it."""
    if rec.trace is None:
        return None
    w0, w1 = rec.trace.window
    prof = load(trace.find_xplane(str(harness.CACHE_DIR / "trace" / rec.cell)))
    d = clock_offset(rec.trace.module_runs(r"."), prof.handed, prof.seen)
    return [dataclasses.replace(s, start=max(s.start - d, w0),
                                end=min(s.end - d, w1))
            for s in prof.spans if s.end - d > w0 and s.start - d < w1]


def named(spans: Optional[Iterable[Span]], *names: str) -> List[Span]:
    return [s for s in spans or () if s.name in names]


def union(spans: Iterable[Span]) -> List[Interval]:
    """The time the spans cover, as disjoint intervals by start."""
    out: List[List[float]] = []
    for a, b in sorted((s.start, s.end) for s in spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(a: float, b: float, cover: Sequence[Interval]) -> float:
    """Length of [a, b] that the disjoint intervals ``cover`` cover."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in cover)
