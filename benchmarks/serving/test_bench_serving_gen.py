"""The traffic generator and the tail and rate arithmetic."""
from __future__ import annotations

import math

import numpy as np
import pytest

from serving import gen, stats

MIX = {"loop": "open",
       "prompt": {"median": 256, "sigma": 0.8, "min": 32, "max": 512},
       "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 1024},
       "order_seed": 0}


def test_same_seed_same_prompts_other_seed_other_prompts():
    a = gen.prompt_tokens(2 ** 31 + 11, 5, 64, 49152)
    b = gen.prompt_tokens(2 ** 31 + 11, 5, 64, 49152)
    c = gen.prompt_tokens(2 ** 31 + 12, 5, 64, 49152)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 49152


def test_schedule_is_the_same_work_for_every_run():
    segs = [("fill", 10.0), ("window", 30.0), ("drain", 20.0)]
    a = gen.open_schedule(MIX, 2.0, segs)
    b = gen.open_schedule(MIX, 2.0, segs)
    assert a == b
    window = [x for x in a if x.segment == "window"]
    assert len(window) == 60                 # round(rate * seconds)
    assert all(10.0 <= x.t < 40.0 for x in window)
    assert [x.t for x in a] == sorted(x.t for x in a)


def test_lognormal_median_and_clips():
    rng = np.random.default_rng(0)
    x = gen.lognormal_lengths(MIX["prompt"], 1001, rng)
    assert x.min() == 32 and x.max() == 512
    assert np.median(x) == 256
    y = gen.lognormal_lengths(MIX["output"], 1001, rng)
    assert np.median(y) == 128 and y.min() >= 16 and y.max() <= 1024
    # quantiles: the share clipped at the top is the lognormal's tail
    tail = 1 - 0.5 * (1 + math.erf(math.log(2) / 0.8 / math.sqrt(2)))
    assert abs(np.mean(x == 512) - tail) < 0.01


def test_bursts_raise_the_rate_inside_them():
    mix = dict(MIX, bursts={"factor": 4, "length_s": 2, "every_s": 10})
    arr = gen.open_schedule(mix, 2.0, [("window", 30.0)])
    # 30 s at 2/s plus 3 bursts of 2 s at 3 x 2/s more
    assert len(arr) == round(2.0 * 30 + 3 * 2 * 3 * 2.0)
    rng = np.random.default_rng([0, 0])
    starts = gen._burst_starts(30.0, mix["bursts"], rng)
    inside = sum(any(s <= x.t < s + 2 for s in starts) for x in arr)
    # 6 s of bursts at 4x the rate of the other 24 s
    assert (inside / 6) / ((len(arr) - inside) / 24) > 3


def test_closed_streams():
    mix = dict(MIX, loop="closed")
    s = gen.closed_streams(mix, 6, 10)
    assert len(s) == 6 and all(len(c) == 10 for c in s)
    assert s == gen.closed_streams(mix, 6, 10)
    flat = [p for c in s for p, _ in c]
    assert min(flat) >= 32 and max(flat) <= 512


def test_percentile_counts_failures_as_infinite():
    assert stats.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert stats.percentile(list(range(1, 11)), 90) == pytest.approx(9.1)
    xs = [1.0] * 9 + [math.inf]
    assert stats.percentile(xs, 80) == 1.0
    assert stats.percentile(xs, 90) == math.inf
    assert stats.percentile([math.inf] * 3, 50) == math.inf


def test_itl_and_tokens_in_window():
    d = [(0.0, 1), (0.1, 1), (0.3, 2), (0.35, 1)]
    assert stats.itl_samples(d) == pytest.approx([0.1, 0.1, 0.1, 0.05])
    assert stats.tokens_between(d, 0.05, 0.35) == 3
    assert stats.itl_samples([(1.0, 1)]) == []
