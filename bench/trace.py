"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.  Only this file knows how a trace is laid out.

A trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``) holds one
plane per device (``/device:TPU:<n>``) and one for the host
(``/host:CPU``).  On a device plane the line ``XLA Ops`` has one event per
operation run, named by its HLO instruction (``%name = type op(...)``; a
Pallas kernel is a ``tpu_custom_call`` named after the jitted function
around it), and ``XLA Modules`` one per program run, named after the
jitted function (``jit_<name>(<id>)``).  The host plane carries the
benchmark's own spans, ``bench.<name>``, written with
``jax.profiler.TraceAnnotation``; ``bench.traced`` brackets the traced
window.  All times are nanoseconds on the trace's one clock; host and device
events on it can lie about a millisecond apart.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"


KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
CONTAINER = re.compile(r"^%(while|conditional|call)(\.\d+)*$")


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns
    kernel: bool = False    # a Pallas (Mosaic) kernel

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: Interval                       # ns, the traced window
    ops: Dict[int, List[Event]]            # device id -> ops, by start
    modules: Dict[int, List[Event]]        # device id -> program runs
    spans: List[Event]                     # host spans "bench.*"

    # ------------------------------------------------------------ basic
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def devices(self) -> List[int]:
        return sorted(self.ops)

    def _clip(self, evs: Sequence[Event]) -> List[Interval]:
        w0, w1 = self.window
        return [(max(e.start, w0), min(e.end, w1)) for e in evs
                if e.end > w0 and e.start < w1]

    def busy_intervals(self, dev: int) -> List[Interval]:
        """The union of the device's operation intervals in the window."""
        out: List[List[float]] = []
        for a, b in sorted(self._clip(self.ops[dev])):
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        tot = sum(b - a for d in devs for a, b in self.busy_intervals(d))
        return tot / len(devs) / 1e9

    def idle_gaps(self, dev: int) -> List[Interval]:
        w0, w1 = self.window
        gaps, t = [], w0
        for a, b in self.busy_intervals(dev):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < w1:
            gaps.append((t, w1))
        return gaps

    # --------------------------------------------------------- by name
    def kernel_time_s(self, pattern: str, t0: float, t1: float,
                      dev: Optional[int] = None) -> float:
        """Summed device seconds, within [t0, t1] (ns), of the Pallas
        kernels whose instruction name matches ``pattern``."""
        rx = re.compile(pattern)
        if not self.devices():
            return 0.0
        d = self.devices()[0] if dev is None else dev
        return sum(min(e.end, t1) - max(e.start, t0) for e in self.ops[d]
                   if e.kernel and rx.search(e.name)
                   and e.end > t0 and e.start < t1) / 1e9

    @staticmethod
    def matches(pattern: str, ev: Event) -> bool:
        return re.search(pattern, ev.name) is not None

    def module_runs(self, pattern: str, dev: Optional[int] = None
                    ) -> List[Event]:
        """Program runs wholly inside the window whose name matches."""
        rx = re.compile(pattern)
        if not self.devices():
            return []
        d = self.devices()[0] if dev is None else dev
        w0, w1 = self.window
        return [e for e in self.modules.get(d, [])
                if rx.search(e.name) and e.start >= w0 and e.end <= w1]

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device operations by summed seconds (first device), names with
        their trailing instance numbers folded together.  Loops and calls,
        whose events enclose the operations they run, are left out."""
        tot: Dict[str, float] = {}
        if not self.devices():
            return []
        d = self.devices()[0]
        for a, b, name in ((max(e.start, self.window[0]),
                            min(e.end, self.window[1]), e.name)
                           for e in self.ops[d]):
            if b > a and not CONTAINER.match(name):
                key = re.sub(r"(\.\d+)+$", "", name)
                tot[key] = tot.get(key, 0.0) + (b - a) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def labelled_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of the first device, each named by the
        innermost benchmark span open on the host at its middle."""
        if not self.devices():
            return []
        d = self.devices()[0]
        out = []
        for a, b in self.idle_gaps(d):
            mid = (a + b) / 2
            inside = [s for s in self.spans
                      if s.start <= mid <= s.end and s.name != WINDOW_SPAN]
            label = (min(inside, key=lambda s: s.dur).name[len(SPAN_PREFIX):]
                     if inside else "host")
            out.append((label, (b - a) / 1e9))
        return sorted(out, key=lambda kv: -kv[1])[:n]


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def _op(e) -> Event:
    text = e.name
    return Event(text.split(" = ", 1)[0], e.start_ns, e.end_ns,
                 KERNEL_MARK in text)


def load(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = sorted((_op(e) for e in line.events),
                                      key=lambda e: e.start)
                elif line.name == MODULES_LINE:
                    modules[dev] = sorted((Event(e.name, e.start_ns, e.end_ns)
                                           for e in line.events),
                                          key=lambda e: e.start)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns, e.end_ns))
    spans.sort(key=lambda e: e.start)
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[0].end)
    elif spans:
        window = (spans[0].start, spans[-1].end)
    else:
        raise ValueError(f"{path}: no benchmark spans in the trace")
    return Trace(window=window, ops=ops, modules=modules, spans=spans)
