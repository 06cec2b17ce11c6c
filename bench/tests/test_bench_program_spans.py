"""The readers of the program's own spans (``program_spans.py`` and the
four metrics built on it), on hand-built traces and span lists whose
numbers can be worked out by hand, and the loader on a trace recorded
here on the CPU.  All times are in ms, written as ns."""
import jax
import pytest

import harness
import program_spans as ps
import spec
import trace
from record import Record
from trace import Event, Trace

MS = 1e6


def _trace(ops, modules, window=(0, 200)):
    return Trace(window=(window[0] * MS, window[1] * MS),
                 ops={0: [Event("%op", a * MS, b * MS) for a, b in ops]},
                 modules={0: [Event(n, a * MS, b * MS)
                              for n, a, b in modules]},
                 spans=[])


def _span(name, a, b, **meta):
    return ps.Span(name, a * MS, b * MS, meta)


def _read(monkeypatch, metric, kind, tr, spans, offset_ms=0.0):
    """The metric of a run whose host events are recorded late by
    ``offset_ms``: the spans, and a handing-over 0.1 ms before and a
    completion 0.1 ms after each program run."""
    d = offset_ms * MS
    runs = tr.modules[0] if tr else []
    prof = ps.Profile(
        tuple(ps.Span(s.name, s.start + d, s.end + d, s.meta)
              for s in spans or ()),
        tuple(r.start + d - 0.1 * MS for r in runs),
        tuple(r.end + d + 0.1 * MS for r in runs))
    monkeypatch.setattr(ps, "load", lambda path: prof)
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: "x.xplane.pb")
    rec = Record(cell="cell", kind=kind, seed=1, seconds=1.0, chips=1)
    rec.trace = tr
    return spec.metric_reader(metric)(rec)


def test_exposed_input_is_the_idle_part_of_next_batch(monkeypatch):
    # two steps, idle from 80 to 120 ms; the batch is built from 100 to
    # 130 ms, so half the gap (20 ms) waits on it: 10 ms a step
    tr = _trace(ops=[(0, 80), (120, 200)],
                modules=[("jit_train_step(1)", 0, 80),
                         ("jit_train_step(1)", 120, 200)])
    spans = [_span(ps.TRAIN_NEXT_BATCH, 100, 130, step=1)]
    got = _read(monkeypatch, "exposed_input_ms.train", "train", tr, spans)
    assert got == pytest.approx(10.0)


def test_prefill_pad_waste_of_two_prefills(monkeypatch):
    # 6 real tokens in 4 x 8, 10 in 4 x 16: 16 of 96 positions are real
    spans = [_span(ps.SERVE_PREFILL, 10, 20, bucket=8, slots=4, rows=1,
                   tokens=6, rids="3"),
             _span(ps.SERVE_PREFILL, 30, 40, bucket=16, slots=4, rows=2,
                   tokens=10, rids="4 5")]
    got = _read(monkeypatch, "prefill_pad_waste.serve", "serve",
                _trace([], []), spans)
    assert got == pytest.approx(100.0 * (1 - 16 / 96))


def test_decode_occupancy_of_known_active_slots(monkeypatch):
    spans = [_span(ps.SERVE_DECODE, 10 * i, 10 * i + 1, tick=i, active=n,
                   slots=4) for i, n in enumerate((1, 2, 3))]
    got = _read(monkeypatch, "decode_occupancy.serve", "serve",
                _trace([], []), spans)
    assert got == pytest.approx(50.0)


@pytest.mark.parametrize("offset_ms", [0.0, 1.5, 3.0])
def test_decode_gap_split_between_program_and_the_rest(monkeypatch,
                                                        offset_ms):
    # decode-to-decode gaps of 4 ms (10-14) and 3 ms (40-43); the gaps
    # around the prefill do not count.  The first gap is covered by the
    # bookkeeping from 10.5 to 12 (the readback before it is not the
    # engine's own work, the tail to 14 is the caller's); the second by
    # admission (0.5 ms) and the decode dispatch (41.5-43): 1.5 and 2 ms,
    # whatever the offset of the host's clock, which ``of`` takes out
    tr = _trace(ops=[], modules=[("jit_fused_decode(1)", 0, 10),
                                 ("jit_fused_decode(1)", 14, 24),
                                 ("jit_fn(2)", 26, 30),
                                 ("jit_fused_decode(1)", 32, 40),
                                 ("jit_fused_decode(1)", 43, 50)])
    spans = [_span("serve.readback", 9, 10.5, tick=1),
             _span("serve.emit", 10.5, 12, retired=0),
             _span("serve.admit", 40.5, 41, admitted=0, queued=0),
             _span("serve.decode", 41.5, 43.2, tick=4, active=1, slots=4)]
    got = _read(monkeypatch, "decode_gap_program_ms.serve", "serve", tr,
                spans, offset_ms)
    assert got == pytest.approx((1.5 + 2.0) / 2)
    assert _read(monkeypatch, "decode_gap_ms.serve", "serve", tr,
                 spans) == pytest.approx((4 + 3) / 2)


def test_clock_offset_leaves_out_a_queued_run():
    # the device's events sit 1 ms early: each run is handed over 0.3 ms
    # before it starts and seen 0.4 ms after it ends, on the device's
    # clock.  B waits behind A and was handed over at 2; C waits behind B
    # and was handed over at 12, nearest to B's start: a bound of 3 ms
    # that the median of the others leaves out
    runs = [Event("a", 0, 10 * MS), Event("b", 10 * MS, 20 * MS),
            Event("c", 20 * MS, 30 * MS)]
    handed = [0.7 * MS, 3 * MS, 13 * MS]
    seen = [11.4 * MS, 21.4 * MS, 31.4 * MS]
    assert ps.clock_offset(runs, handed, seen) == pytest.approx(1.05 * MS)
    assert ps.clock_offset(runs, handed, []) == pytest.approx(3 * MS)
    assert ps.clock_offset(runs, [], seen) == pytest.approx(1.4 * MS)
    assert ps.clock_offset(runs, [], []) == 0.0
    assert ps.clock_offset([], handed, seen) == 0.0


@pytest.mark.parametrize("metric,kind", [
    ("exposed_input_ms.train", "train"),
    ("prefill_pad_waste.serve", "serve"),
    ("decode_occupancy.serve", "serve"),
    ("decode_gap_program_ms.serve", "serve"),
])
def test_a_program_without_spans_reads_nothing(monkeypatch, metric, kind):
    """A program older than its spans, traced: the readers give None and
    do not raise."""
    tr = _trace(ops=[(0, 80), (120, 200)],
                modules=[("jit_train_step(1)", 0, 80),
                         ("jit_fused_decode(1)", 100, 110),
                         ("jit_fused_decode(1)", 120, 200)])
    assert _read(monkeypatch, metric, kind, tr, []) is None
    assert _read(monkeypatch, metric, kind, None, None) is None


def test_loader_finds_clips_and_keeps_metadata(tmp_path, monkeypatch):
    """A trace recorded where ``harness.Tracer`` writes it: the program's
    spans inside the window, clipped to it, with their metadata; the
    benchmark's own spans are not among them."""
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path)
    logdir = tmp_path / "trace" / "cell"
    ann = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(logdir))
    with ann("repro.serve.step", tick=1):
        with ann("bench.traced"):
            with ann("repro.serve.prefill", bucket=8, rids="1 2"):
                pass
    with ann("repro.serve.decode", tick=2):
        pass
    jax.profiler.stop_trace()
    rec = Record(cell="cell", kind="serve", seed=1, seconds=1.0, chips=1)
    rec.trace = trace.load(trace.find_xplane(str(logdir)))
    got = ps.of(rec)
    assert [(s.name, s.meta) for s in got] == [
        ("serve.step", {"tick": 1}),
        ("serve.prefill", {"bucket": 8, "rids": "1 2"})]
    w0, w1 = rec.trace.window
    assert got[0].start == w0 and got[0].end == w1
    assert w0 <= got[1].start <= got[1].end <= w1
    rec.trace = None
    assert ps.of(rec) is None
