import numpy as np
import pytest

from bench.harness.registry import Benchmark
from bench.harness.traffic import POOL, lognormal_lengths, make_schedule

CHAT = Benchmark().mix("chat")
@pytest.mark.parametrize("seconds", [10, 30, 51])
def test_the_schedule_is_the_mixs_own(seconds):
    a = make_schedule(CHAT, seconds)
    b = make_schedule(CHAT, seconds)
    assert a.requests == b.requests
    # the gaps are exponential quantiles, in an order that is not sorted
    mean_gap = a.requests[-1].due_s / (len(a.requests) - 1)
    assert mean_gap == pytest.approx(1 / CHAT["rate_rps"], rel=0.1)
    gaps = np.diff([r.due_s for r in a.requests])
    assert not (np.all(np.diff(gaps) >= 0) or np.all(np.diff(gaps) <= 0))


def test_open_loop_lengths_and_arrivals_in_range():
    s = make_schedule(CHAT, 30)
    p, o = CHAT["prompt_tokens"], CHAT["output_tokens"]
    for r in s.requests:
        assert p["min"] <= r.prompt_tokens <= p["max"]
        assert o["min"] <= r.output_tokens <= o["max"]
        assert 0.0 <= r.due_s < 30
    dues = [r.due_s for r in s.requests]
    assert dues == sorted(dues) and dues[0] == 0.0
    # the offered rate is the mix's, to within the stratified draw
    assert abs(len(s.requests) / 30 - CHAT["rate_rps"]) < 0.1 * CHAT["rate_rps"]
    # prompt lengths are served as drawn, not rounded onto a grid
    lengths = s.lengths("prompt_tokens")
    assert len(lengths) > 0.8 * len(s.requests)
    assert lengths == sorted({r.prompt_tokens for r in s.requests})


def test_lognormal_median_and_clip():
    d = {"median": 512, "sigma": 0.8, "min": 32, "max": 1536}
    x = lognormal_lengths(d, 1001)
    assert x.min() >= 32 and x.max() == 1536
    assert abs(np.median(x) - 512) <= 2


def test_closed_loop_pool_cycles():
    mix = {"loop": "closed", "clients": 4, "prompt_tokens": CHAT["prompt_tokens"]}
    s = make_schedule(mix, 10)
    assert s.clients == 4 and len(s.requests) == POOL
    assert s.spec(3).index == 3 and s.spec(POOL + 3).index == POOL + 3
    assert s.spec(POOL + 3).prompt_tokens == s.spec(3).prompt_tokens


def test_unknown_loop_is_refused():
    with pytest.raises(ValueError):
        make_schedule({"loop": "sideways"}, 10)
