"""The one traffic generator. A mix (`traffic/<name>.json`) is data:

    {"loop": "open" | "closed",
     "prompt": {"median": 256, "sigma": 0.8, "min": 32, "max": 512},
     "output": {"median": 256, "sigma": 0.8, "min": 32, "max": 1024},
     "bursts": {"factor": 4, "length_s": 2, "every_s": 10},   # optional
     "order_seed": 0}

Lengths are lognormal (median, sigma of the log), clipped to [min, max].
The rate (open loop) or the clients per decode row (closed loop) come from
the cell file, since one mix runs at different rates on different
deployments.

Every run of a cell does the same work whatever its seed: the lengths of
a segment are the lognormal's quantiles at (i + 0.5) / n, the gaps between
arrivals the exponential's, both put in one fixed order drawn from
``order_seed``. The run's seed draws the prompts' token ids and the
weights, never the sizes or the arrival times. A window of ``seconds``
therefore always holds ``round(rate * seconds)`` scheduled arrivals
(bursts add theirs) with the same lengths.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float            # scheduled arrival, seconds from the start of traffic
    prompt: int         # prompt tokens
    output: int         # tokens to generate
    segment: str        # "fill", "window" or "drain"


def lognormal_lengths(dist: dict, n: int, rng: np.random.Generator):
    """n lengths: the clipped lognormal's quantiles at (i + 0.5) / n, in
    an order drawn from ``rng``."""
    if n <= 0:
        return np.zeros(0, np.int64)
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    x = np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    return x[rng.permutation(n)]


def _gaps(n: int, rng: np.random.Generator):
    """n unit-mean exponential quantiles, rescaled to sum to exactly n,
    in an order drawn from ``rng``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    g *= n / g.sum()
    return g[rng.permutation(n)]


def _burst_starts(duration: float, bursts: dict, rng) -> list[float]:
    """One burst in each slot of ``every_s`` seconds, at an offset drawn
    from ``rng`` that keeps it inside the slot."""
    n = max(1, round(duration / bursts["every_s"]))
    slot = duration / n
    room = max(0.0, slot - bursts["length_s"])
    return [i * slot + float(rng.uniform(0, room)) for i in range(n)]


def _intensity(duration: float, rate: float, bursts, rng):
    """Piecewise-linear cumulative intensity of one segment: knots
    (t, Lambda(t)). Inside a burst the rate is ``factor * rate``."""
    knots = [(0.0, 0.0)]
    if bursts:
        f, b = bursts["factor"], bursts["length_s"]
        for s in _burst_starts(duration, bursts, rng):
            t0, l0 = knots[-1]
            e = min(s + b, duration)
            knots.append((s, l0 + rate * (s - t0)))
            knots.append((e, knots[-1][1] + f * rate * (e - s)))
    t0, l0 = knots[-1]
    knots.append((duration, l0 + rate * (duration - t0)))
    return np.array(knots)


def open_schedule(mix: dict, rate: float,
                  segments: list[tuple[str, float]]) -> list[Arrival]:
    """Open-loop arrivals for consecutive segments, e.g.
    ``[("fill", 15), ("window", 30), ("drain", 60)]``. Each segment holds
    ``round(Lambda(duration))`` arrivals spread by the same quantile gaps,
    with their own quantile lengths."""
    out: list[Arrival] = []
    t_base = 0.0
    for si, (name, duration) in enumerate(segments):
        rng = np.random.default_rng([mix.get("order_seed", 0), si])
        knots = _intensity(duration, rate, mix.get("bursts"), rng)
        total = knots[-1, 1]
        n = int(round(total))
        if n:
            lam = np.cumsum(_gaps(n, rng)) * (total / n)
            lam -= lam[0] * 0.5          # first arrival half a gap in
            times = np.interp(lam, knots[:, 1], knots[:, 0])
            prompts = lognormal_lengths(mix["prompt"], n, rng)
            outputs = lognormal_lengths(mix["output"], n, rng)
            out.extend(Arrival(t_base + float(t), int(p), int(o), name)
                       for t, p, o in zip(times, prompts, outputs))
        t_base += duration
    return out


def closed_streams(mix: dict, clients: int, per_client: int):
    """Closed loop: client c's j-th request is entry ``j * clients + c``
    of one pool of quantile lengths. Returns a list per client of
    ``(prompt, output)``."""
    n = clients * per_client
    rng = np.random.default_rng([mix.get("order_seed", 0), 0])
    prompts = lognormal_lengths(mix["prompt"], n, rng)
    outputs = lognormal_lengths(mix["output"], n, rng)
    return [[(int(prompts[j * clients + c]), int(outputs[j * clients + c]))
             for j in range(per_client)] for c in range(clients)]


def prompt_tokens(seed: int, index: int, length: int, vocab: int):
    """Token ids of request ``index`` of a run: uniform over the vocabulary,
    from the run's seed (the same seed gives the same prompts)."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(0, vocab, length).astype(np.int32)


def describe(lengths) -> dict:
    """Quantiles of a length sample, for the run's report."""
    a = np.asarray(lengths)
    if not a.size:
        return {}
    q = np.quantile(a, [0.0, 0.5, 0.9, 1.0])
    return {"n": int(a.size), "mean": float(a.mean()), "min": int(q[0]),
            "median": float(q[1]), "p90": float(q[2]), "max": int(q[3])}
