"""The traffic generator: reproducible from the seed, log-normal lengths
with the file's moments, and every seed given the same work in the
window in another order."""

import numpy as np
import pytest

from bench import traffic as T

MIX = {"prompt": {"mean": 213, "std": 508, "min": 0, "max": 10**9},
       "output": {"mean": 321, "std": 214, "min": 0, "max": 10**9},
       "arrivals": {"kind": "poisson", "rate": 50.0},
       "lead_in_s": 5.0, "drain_s": 5.0, "base_seed": 3}


def test_same_seed_same_requests():
    a = T.generate(MIX, 2**31 + 7, 20.0, 1000)
    b = T.generate(MIX, 2**31 + 7, 20.0, 1000)
    assert [(r.due, r.prompt_len, r.output_len) for r in a] == \
        [(r.due, r.prompt_len, r.output_len) for r in b]
    assert all((x.tokens == y.tokens).all() for x, y in zip(a, b))
    assert min(int(r.tokens.min()) for r in a) >= T.FIRST_TOKEN_ID


def test_seeds_permute_the_same_window_work():
    a = T.generate(MIX, 1, 20.0, 1000)
    b = T.generate(MIX, 2, 20.0, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    for key in ("prompt_len", "output_len"):
        win = [[getattr(r, key) for r in rs if 5.0 <= r.due < 25.0]
               for rs in (a, b)]
        assert sorted(win[0]) == sorted(win[1]) and win[0] != win[1]


@pytest.mark.parametrize("which", ["prompt", "output"])
def test_lengths_hit_their_lognormal_moments(which):
    d = MIX[which]
    x = T.lengths(np.random.default_rng(0), d, 400_000)
    assert x.mean() == pytest.approx(d["mean"], rel=0.03)
    assert x.std() == pytest.approx(d["std"], rel=0.15)


def test_clip_bounds_hold():
    d = {"mean": 213, "std": 508, "min": 8, "max": 2048}
    x = T.lengths(np.random.default_rng(1), d, 10_000)
    assert x.min() >= 8 and x.max() <= 2048


def test_poisson_arrivals_keep_their_rate():
    arr = {"kind": "poisson", "rate": 10.0}
    t = T.arrival_times(arr, 2000.0, np.random.default_rng(0))
    assert len(t) / 2000.0 == pytest.approx(10.0, rel=0.05)
    with pytest.raises(ValueError):
        T.arrival_times({"kind": "bursty", "rate": 1.0}, 1.0,
                        np.random.default_rng(0))
