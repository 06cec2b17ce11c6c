"""Continuous-batching scheduler: submit validation, greedy equivalence
with the legacy oracle, priority/SLO admission, paged-KV eviction with
token-identical resume, streaming, and service-timing stats."""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import init_params
from repro.serve import (LegacyServeEngine, Request, ServeEngine,
                         ServeScheduler, VirtualClock, poisson_trace)

CFG = get_reduced("granite-3-2b")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _requests(n, seed=0, max_tokens=8, plo=4, phi=12):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, CFG.vocab,
                                        size=int(rng.integers(plo, phi))),
                    max_tokens=max_tokens)
            for i in range(n)]


# ------------------------------------------------------------ validation
@pytest.mark.parametrize("make", [
    lambda p: ServeEngine(CFG, p, slots=1, cache_len=32),
    lambda p: LegacyServeEngine(CFG, p, slots=1, cache_len=32),
    lambda p: ServeScheduler(CFG, p, slots=1, cache_len=32),
])
def test_submit_rejects_invalid_prompts(params, make):
    eng = make(params)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(Request(rid=0, prompt=np.array([], np.int32)))
    with pytest.raises(ValueError, match="cache_len"):
        eng.submit(Request(rid=1, prompt=np.arange(32) % CFG.vocab))
    # the boundary case fits: cache_len - 1 prompt tokens + 1 generated
    eng.submit(Request(rid=2, prompt=np.arange(31) % CFG.vocab,
                       max_tokens=4))
    done = eng.run()
    assert len(done) == 1 and len(done[0].generated) >= 1


def test_submit_at_validates_before_queueing(params):
    sched = ServeScheduler(CFG, params, slots=1, cache_len=32)
    with pytest.raises(ValueError):
        sched.submit_at(Request(rid=0, prompt=np.array([], np.int32)), 0.0)
    assert sched.next_arrival() is None


def test_pool_too_small_for_one_request_raises(params):
    with pytest.raises(ValueError, match="deadlock"):
        ServeScheduler(CFG, params, slots=2, cache_len=64,
                       max_kv_blocks=2, kv_block_size=8)


# ------------------------------------------- greedy equivalence (oracle)
def test_scheduler_matches_legacy_on_fixed_trace(params):
    """Token-for-token: the continuous scheduler on a fixed arrival trace
    must generate exactly what the seed engine generates for the same
    prompts — admission plumbing must never change greedy decode."""
    trace = poisson_trace(CFG.vocab, 9, rate_qps=1e6, seed=13,
                          max_tokens=7)
    sched = ServeScheduler(CFG, params, slots=3, cache_len=64)
    sched.submit_trace(trace)
    sched.run()
    new = {r.rid: tuple(r.generated) for r in sched.completed}

    legacy = LegacyServeEngine(CFG, params, slots=3, cache_len=64)
    for _, r in trace:
        legacy.submit(Request(rid=r.rid, prompt=np.asarray(r.prompt),
                              max_tokens=r.max_tokens))
    old = {r.rid: tuple(r.generated) for r in legacy.run()}
    assert new == old
    assert sched.stats["shed"] == 0 and sched.stats["evictions"] == 0


def test_eviction_resume_is_token_identical(params):
    """Oversubscribed pool: LRU eviction + requeue + re-prefill of
    prompt+generated must resume greedy decode exactly where it left
    off — outputs identical to an unconstrained run."""
    mk = lambda: _requests(6, seed=23, max_tokens=20)
    ref = ServeScheduler(CFG, params, slots=3, cache_len=64)
    for r in mk():
        ref.submit(r)
    want = {r.rid: tuple(r.generated) for r in ref.run()}

    # pool of exactly cache_len tokens shared by 3 slots: ~3x oversubscribed
    tight = ServeScheduler(CFG, params, slots=3, cache_len=64,
                           max_kv_blocks=8, kv_block_size=8)
    for r in mk():
        tight.submit(r)
    got = {r.rid: tuple(r.generated) for r in tight.run()}
    assert got == want
    assert tight.stats["evictions"] > 0            # pressure was real
    assert tight.kv.stats["failed_grows"] > 0
    assert tight.kv.used_blocks == 0               # everything recycled


# --------------------------------------------------- priority / SLO / KV
def test_priority_orders_admission(params):
    sched = ServeScheduler(CFG, params, slots=1, cache_len=64)
    for r in _requests(3, seed=2, max_tokens=3):
        r.priority = r.rid                 # rid 2 most urgent
        sched.submit(r)
    sched.run()
    assert [r.rid for r in sched.completed] == [2, 1, 0]
    admits = [r.t_admit for r in sorted(sched.completed,
                                        key=lambda r: -r.priority)]
    assert admits == sorted(admits)


def test_slo_shedding_is_deterministic(params):
    """With a virtual clock (10ms per decode step) a queued request whose
    TTFT deadline lapses behind a long-running one is shed, not served."""
    clock = VirtualClock(dt_per_step=0.01)
    sched = ServeScheduler(CFG, params, slots=1, cache_len=64,
                           clock=clock, slo_deadline_ms=50.0)
    hog, victim = _requests(2, seed=4, max_tokens=20)
    hog.deadline_ms = None                  # the hog never expires
    events = []
    victim.on_token = lambda r, tok, fin: events.append((tok, fin))
    sched.submit(hog)
    sched.submit(victim)
    sched.run()
    assert victim.status == "shed"
    assert victim in sched.shed and victim.t_done is not None
    assert events == [(-1, True)]           # shed notification fired
    assert sched.stats["shed"] == 1
    assert len(hog.generated) == 20
    s = sched.stats()
    assert s["shed"] == 1 and s["completed"] == 1


def test_open_loop_arrivals_release_by_clock(params):
    clock = VirtualClock(dt_per_step=0.01)
    sched = ServeScheduler(CFG, params, slots=2, cache_len=64, clock=clock)
    a, b = _requests(2, seed=6, max_tokens=4)
    sched.submit_at(a, 0.0)
    sched.submit_at(b, 5.0)                 # far in the virtual future
    assert sched.next_arrival() == 0.0
    sched.run()                             # sleeps the clock forward to b
    assert len(sched.completed) == 2
    assert b.t_submit == 5.0 and b.t_admit >= 5.0
    assert a.t_done < b.t_admit             # b really arrived later


def test_admit_is_stamped_before_the_prefill(params):
    """A request is admitted when it is given a slot: its queue wait is
    the wait behind the full slot, and its own prefill (0.5 s here)
    falls between admission and its first token."""
    clock = VirtualClock(dt_per_step=0.01)
    sched = ServeScheduler(CFG, params, slots=1, cache_len=64, clock=clock)
    prefill_fn = sched._prefill_fn

    def slow_prefill(bucket):
        fn = prefill_fn(bucket)

        def run(*args):
            clock.advance(0.5)
            return fn(*args)
        return run

    sched._prefill_fn = slow_prefill
    hog, waiter = _requests(2, seed=7, max_tokens=4)
    sched.submit(hog)
    sched.submit(waiter)
    sched.run()
    for r in (hog, waiter):
        assert r.t_submit <= r.t_admit <= r.t_first
        assert r.t_first - r.t_admit == pytest.approx(0.5)
    assert hog.queue_wait_s == 0.0
    # the hog's prefill and its three decode steps, then the slot frees
    assert waiter.t_admit == pytest.approx(hog.t_done)
    assert waiter.queue_wait_s == pytest.approx(0.5 + 3 * 0.01)


# -------------------------------------------------------------- streaming
def test_stream_yields_tokens_and_ttft(params):
    sched = ServeScheduler(CFG, params, slots=2, cache_len=64)
    background = _requests(1, seed=8, max_tokens=10)[0]
    sched.submit(background)
    star = _requests(2, seed=8, max_tokens=6)[1]
    star.rid = 99
    got = []
    for tok in sched.stream(star):
        got.append(tok)
        assert star.t_first is not None     # TTFT stamped by first yield
    assert got == star.generated and len(got) == 6
    sched.run()                             # drain the co-batched request
    assert background.done


def test_on_token_callback_sees_every_token(params):
    sched = ServeScheduler(CFG, params, slots=1, cache_len=64)
    req = _requests(1, seed=12, max_tokens=5)[0]
    seen = []
    req.on_token = lambda r, tok, fin: seen.append((tok, fin))
    sched.submit(req)
    sched.run()
    assert [t for t, _ in seen] == req.generated
    assert [f for _, f in seen] == [False] * 4 + [True]


# --------------------------------------------------- prefill bucket edges
def test_bucket_boundary_prompts(params):
    """Prompt lengths sitting exactly on bucket boundaries (8, 16), a
    single-token prompt, and the largest admissible prompt all decode
    and compile at most one prefill program per bucket."""
    sched = ServeScheduler(CFG, params, slots=2, cache_len=64)
    plens = [1, 8, 16, 63]                  # 63 == cache_len - 1
    for i, plen in enumerate(plens):
        sched.submit(Request(rid=i, prompt=(np.arange(plen) * 3) % CFG.vocab,
                             max_tokens=2))
    done = sched.run()
    assert len(done) == len(plens)
    assert all(len(r.generated) >= 1 for r in done)
    assert sched.prefill_compiles <= sched.n_buckets() <= 4   # 8/16/32/64


def test_prefill_cache_bounded_under_mixed_trace(params):
    """A scheduler workload mixing many prompt lengths, priorities and
    mid-decode admissions keeps the prefill jit cache bucket-bounded and
    never retraces decode."""
    rng = np.random.default_rng(31)
    sched = ServeScheduler(CFG, params, slots=3, cache_len=64)
    for i, plen in enumerate(rng.permutation(np.arange(2, 40))):
        sched.submit(Request(rid=i,
                             prompt=(np.arange(plen) * 5) % CFG.vocab,
                             max_tokens=3, priority=int(i % 3)))
    done = sched.run(max_steps=5000)
    assert len(done) == 38
    assert sched.prefill_compiles <= sched.n_buckets()
    assert sched.decode_compiles == 1


# ------------------------------------------------------------ stats wiring
def test_timing_stats_surface_in_summary(params):
    clock = VirtualClock(dt_per_step=0.01)
    sched = ServeScheduler(CFG, params, slots=2, cache_len=64, clock=clock)
    for r in _requests(4, seed=14, max_tokens=6):
        sched.submit(r)
    sched.run()
    s = sched.stats()
    for key in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
                "queue_wait_p50_s", "queue_wait_p99_s"):
        assert s[key] is not None and s[key] >= 0.0, key
    assert s["ttft_p50_s"] <= s["ttft_p99_s"]
    assert s["kv"]["used_blocks"] == 0
    # mapping access (the pre-existing counter contract) still works
    assert sched.stats["decode_steps"] == s["decode_steps"]
    for r in sched.completed:
        assert r.tpot_s is not None and r.queue_wait_s is not None


def test_serve_runner_reports_continuous_metrics():
    """RunSpec -> RunReport round trip through the continuous path: the
    report must carry goodput and latency percentiles."""
    from repro.api import RunSpec, run

    report = run(RunSpec(kind="serve", arch="granite-3-2b", overrides={
        "requests": 4, "slots": 2, "cache_len": 32, "max_tokens": 4,
        "arrival_rate": 200.0, "trace": "bursty",
        "slo_deadline_ms": 60_000.0}))
    assert report.ok
    m = report.metrics
    assert m["mode"] == "continuous" and m["trace"] == "bursty"
    assert m["completed"] + m["shed"] == 4
    for key in ("goodput_req_s", "goodput_tok_s", "ttft_p50_s",
                "tpot_p50_s", "queue_wait_p99_s", "evictions", "kv"):
        assert key in m, key
    assert m["decode_compiles"] == 1
