"""The open-loop generator: the same work on every seed, in another order."""
import numpy as np
import pytest

from traffic import openloop

MIX = {"arrivals": "poisson", "rate_rps": 3.0, "tail_s": 10,
       "prompt": {"median": 256, "sigma": 0.8, "min": 16, "max": 1024},
       "output": {"median": 64, "sigma": 0.8, "min": 8, "max": 512}}


def test_same_sizes_every_seed_in_another_order():
    a = openloop.schedule(MIX, 7, 30.0, 1000)
    b = openloop.schedule(MIX, 2**31 + 11, 30.0, 1000)
    for sched in (a, b):
        counted = [p for p in sched if p.counted]
        assert len(counted) == 90
        assert all(0.0 <= p.offset_s < 30.0 for p in counted)
        assert all(p.offset_s >= 30.0 for p in sched if not p.counted)
    la = sorted(len(p.prompt) for p in a if p.counted)
    lb = sorted(len(p.prompt) for p in b if p.counted)
    assert la == lb
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    oa = sorted(p.max_tokens for p in a if p.counted)
    assert oa == sorted(p.max_tokens for p in b if p.counted)
    assert min(la) >= 16 and max(la) <= 1024 and np.median(la) == 256


def test_seed_fixes_the_schedule():
    a = openloop.schedule(MIX, 5, 10.0, 1000)
    b = openloop.schedule(MIX, 5, 10.0, 1000)
    assert [p.offset_s for p in a] == [p.offset_s for p in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_poisson_alone_is_known():
    """Poisson is the one arrival kind; another is refused, not guessed."""
    s = [p.offset_s for p in openloop.schedule(MIX, 3, 16.0, 100)
         if p.counted]
    assert len(s) == 48 and s == sorted(s) and s[0] == 0.0
    with pytest.raises(ValueError, match="arrivals"):
        openloop.schedule(dict(MIX, arrivals="bursty"), 3, 16.0, 100)
