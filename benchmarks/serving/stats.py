"""Tail and rate arithmetic of the end-to-end metrics."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """q-th percentile with linear interpolation between closest ranks
    (numpy's default). +inf entries (requests that never got their token)
    sort last; a percentile that interpolates towards one is +inf."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    frac = pos - lo
    if frac == 0 or xs[hi] == xs[lo]:
        return float(xs[lo])
    if math.isinf(xs[hi]):
        return math.inf
    return float(xs[lo] + (xs[hi] - xs[lo]) * frac)


def itl_samples(deliveries) -> list[float]:
    """Gaps between a request's token deliveries, ``[(time, tokens)]``:
    each delivery after the first adds ``tokens`` samples of gap/tokens
    (the definition of `repro.serve.metrics`: a multi-token delivery shows
    as lower per-token latency, not as fewer gaps)."""
    out = []
    for (t_prev, _), (t, n) in zip(deliveries, deliveries[1:]):
        out.extend([(t - t_prev) / n] * n)
    return out


def tokens_between(deliveries, t0: float, t1: float) -> int:
    """Tokens of the deliveries that landed in [t0, t1)."""
    return sum(n for t, n in deliveries if t0 <= t < t1)
