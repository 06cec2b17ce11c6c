"""The program's host spans (``repro.obs``) as the profiler records them
on the CPU, read back with the benchmark's loader
(``bench/program_spans.py``): their names and nesting in a train step
and a serve tick, one of each train span per step, prefill counters that
add up to the prompts served, and nothing built while no profiler runs.
Also the engine's counters and the launcher's shares made from them."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.configs import get_reduced
from repro.launch.serve import _batch_use
from repro.launch.train import _LMDictBatches
from repro.models import init_params
from repro.optim import constant, get_optimizer
from repro.serve import Request, ServeEngine, ServeScheduler
from repro.train import TrainLoop, init_train_state, make_train_step

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
import program_spans  # noqa: E402
import trace  # noqa: E402  (bench/trace.py)

SERVE_CFG = get_reduced("granite-3-2b")
TRAIN_SPANS = (obs.TRAIN_NEXT_BATCH, obs.TRAIN_STEP, obs.TRAIN_LOSS_READBACK)
SERVE_CHILDREN = (obs.SERVE_ADMIT, obs.SERVE_PREFILL, obs.SERVE_CAPACITY,
                  obs.SERVE_DECODE, obs.SERVE_READBACK, obs.SERVE_EMIT)


def _traced(logdir, fn):
    """Run ``fn`` under a profiler session; the ``repro.*`` spans it
    wrote, and what it returned."""
    jax.profiler.start_trace(str(logdir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return program_spans.load(trace.find_xplane(str(logdir))).spans, out


def _parent(span, spans):
    """The innermost other span open over the whole of ``span``."""
    around = [s for s in spans if s is not span
              and s.start <= span.start and span.end <= s.end]
    return min(around, key=lambda s: s.end - s.start) if around else None


@pytest.fixture(scope="module")
def serve_params():
    return init_params(jax.random.PRNGKey(0), SERVE_CFG)


# ----------------------------------------------------------------- train
def test_train_loop_spans_each_step(tmp_path):
    cfg = get_reduced("stablelm-1.6b")
    opt = get_optimizer("adamw")
    loop = TrainLoop(
        make_train_step(cfg, opt, lr_schedule=constant(1e-3)),
        init_train_state(jax.random.PRNGKey(0), cfg, opt),
        _LMDictBatches(cfg.vocab, 2, 16, 0),
        checkpointer=CheckpointManager(tmp_path / "ck", every_steps=2,
                                       async_saves=False),
        log_every=0)
    spans, result = _traced(tmp_path / "trace", lambda: loop.run(3))

    assert "first_step_s" not in result
    assert {s.name for s in spans} == set(TRAIN_SPANS) | {obs.TRAIN_CHECKPOINT}
    for name in TRAIN_SPANS:
        steps = [s.meta["step"] for s in spans if s.name == name]
        assert steps == [0, 1, 2], name
    ck = [s for s in spans if s.name == obs.TRAIN_CHECKPOINT]
    assert [s.meta["step"] for s in ck] == [2]
    # each step: the batch, then the dispatch, then the wait for its loss,
    # none of them inside another
    for i in range(3):
        nb, st, rb = (next(s for s in spans
                           if s.name == n and s.meta["step"] == i)
                      for n in TRAIN_SPANS)
        assert nb.end <= st.start and st.end <= rb.start
        assert all(_parent(s, spans) is None for s in (nb, st, rb))


# ----------------------------------------------------------------- serve
def test_serve_tick_spans_nest_and_count_the_prompts(tmp_path, serve_params):
    lens = [5, 9, 12, 3]
    sched = ServeScheduler(SERVE_CFG, serve_params, slots=2, cache_len=64)
    rng = np.random.default_rng(1)
    for rid, n in enumerate(lens):
        sched.submit(Request(rid=rid, prompt=rng.integers(0, SERVE_CFG.vocab,
                                                          size=n),
                             max_tokens=4))
    spans, done = _traced(tmp_path, sched.run)
    assert len(done) == len(lens)

    steps = [s for s in spans if s.name == obs.SERVE_STEP]
    assert {s.name for s in spans} == {obs.SERVE_STEP, *SERVE_CHILDREN}
    for s in spans:
        if s.name != obs.SERVE_STEP:
            assert _parent(s, spans).name == obs.SERVE_STEP, s.name
    # within a tick: admission, its prefills, capacity, then the decode
    # dispatch, the readback of its tokens and the bookkeeping
    order = {n: i for i, n in enumerate(SERVE_CHILDREN)}
    for st in steps:
        kids = [s for s in spans if _parent(s, spans) is st]
        assert [order[k.name] for k in kids] == sorted(
            order[k.name] for k in kids)
        assert kids[0].name == obs.SERVE_ADMIT

    prefill = [s for s in spans if s.name == obs.SERVE_PREFILL]
    assert sum(s.meta["tokens"] for s in prefill) == sum(lens)
    assert sum(s.meta["rows"] for s in prefill) == len(lens)
    rids = [int(r) for s in prefill for r in str(s.meta["rids"]).split()]
    assert sorted(rids) == list(range(len(lens)))
    assert all(s.meta["slots"] == 2 and s.meta["bucket"] >= 8
               for s in prefill)
    admitted = sum(s.meta["admitted"] for s in spans
                   if s.name == obs.SERVE_ADMIT)
    assert admitted == len(lens)

    decode = [s for s in spans if s.name == obs.SERVE_DECODE]
    readback = [s for s in spans if s.name == obs.SERVE_READBACK]
    assert [s.meta["tick"] for s in readback] == \
        [s.meta["tick"] for s in decode]
    assert all(1 <= s.meta["active"] <= s.meta["slots"] == 2 for s in decode)
    retired = sum(s.meta["retired"] for s in spans
                  if s.name == obs.SERVE_EMIT)
    assert retired == len(lens)

    st = sched.stats
    assert st["prefill_tokens"] == sum(lens)
    assert st["prefill_padded_tokens"] == sum(s.meta["slots"]
                                              * s.meta["bucket"]
                                              for s in prefill)
    assert st["decode_active_slots"] == sum(s.meta["active"] for s in decode)
    assert st["decode_steps"] == len(decode)


# -------------------------------------------------------------- switch
def test_span_builds_metadata_only_while_tracing(tmp_path):
    calls = []

    def rids():
        calls.append(1)
        return "1 2"

    assert not TraceAnnotation.is_enabled()
    with obs.span("test.lazy", rids=rids) as sp:
        sp.set_metadata(n=1)
    assert calls == []
    assert obs.span("test.lazy", rids=rids) is obs.span("test.other")

    def traced():
        with obs.span("test.lazy", rids=rids) as sp:
            sp.set_metadata(n=1)

    spans, _ = _traced(tmp_path, traced)
    assert calls == [1]
    assert [(s.name, s.meta) for s in spans] == [
        ("test.lazy", {"rids": "1 2", "n": 1})]


# ------------------------------------------------------------ counters
def test_engine_counters_give_pad_waste_and_occupancy(serve_params):
    eng = ServeEngine(SERVE_CFG, serve_params, slots=4, cache_len=64)
    for rid, n in enumerate((5, 6)):
        eng.submit(Request(rid=rid, prompt=np.arange(1, n + 1),
                           max_tokens=3))
    eng.run()
    # one prefill of 4 slots x bucket 8 holding 11 real tokens; two decode
    # steps with 2 of 4 slots live
    assert eng.stats["prefill_tokens"] == 11
    assert eng.stats["prefill_padded_tokens"] == 32
    assert eng.stats["decode_active_slots"] == 4
    assert _batch_use(eng) == {"prefill_pad_waste": round(1 - 11 / 32, 4),
                               "decode_occupancy": 0.5}
