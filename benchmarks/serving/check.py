"""Whether what the timed path served is right: a sample of the finished
requests, run once through the plain float32 reference.

For each sampled request the reference reads its prompt followed by the
tokens the program served, in one causal pass. At every served position it
gives the best logit and the logit of the token the program chose; their
difference is how far below the reference's best the served token lies.
The program decodes greedily in bf16, so near-ties may flip and a gap is
rarely zero; a wrong KV page, a stale recurrent state or a corrupted token
makes it large. The number compared is the widest gap over the sample.

The control (`control_gap`) puts the same reference in the program's
place at fp8 (the precision below the configuration's bf16): at the same
positions, the reference's gap of the token the fp8 pass ranks first.
"""
from __future__ import annotations

import numpy as np


def sample(finished, seed: int, min_tokens: int, min_requests: int,
           max_requests: int):
    """``finished``: [(prompt, served)] of completed requests. The one with
    the most served tokens, then others drawn from the seed, until the
    sample holds both ``min_tokens`` served tokens and ``min_requests``
    requests, or ``max_requests``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -len(finished[i][1]))
    first, rest = order[0], order[1:]
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x5a5a])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for i in [first] + rest:
        if (n >= min_tokens and len(out) >= min_requests) \
                or len(out) >= max_requests:
            break
        out.append(finished[i])
        n += len(finished[i][1])
    return out


def _padded(prompt, served, length: int):
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served, np.int64)])
    if seq.size > length:
        raise ValueError(f"sequence of {seq.size} tokens exceeds the "
                         f"reference length {length}")
    out = np.zeros(length, np.int32)
    out[:seq.size] = seq
    return out


def _served_positions(prompt, served):
    """Positions whose next token is a served one."""
    n, m = len(prompt), len(served)
    return np.arange(n - 1, n + m - 1)


def served_gap(ref, cfg: dict, params, samples, length: int) -> float:
    """Widest gap (reference best minus reference logit of the served
    token) over every served token of the sample."""
    worst = 0.0
    for prompt, served in samples:
        if not len(served):
            continue
        best, got, _ = ref.stats(cfg, params,
                                 _padded(prompt, served, length))
        pos = _served_positions(prompt, served)
        gap = np.asarray(best)[pos] - np.asarray(got)[pos]
        worst = max(worst, float(gap.max()))
    return worst


def control_gap(ref, cfg: dict, params, samples, length: int) -> float:
    """Widest gap of the fp8 control's first choices at the same
    positions."""
    worst = 0.0
    for prompt, served in samples:
        if not len(served):
            continue
        best, got = ref.control(cfg, params, _padded(prompt, served, length))
        pos = _served_positions(prompt, served)
        gap = np.asarray(best)[pos] - np.asarray(got)[pos]
        worst = max(worst, float(gap.max()))
    return worst
