"""Arithmetic shared by the plain references in `references/`: float32
matmuls at the highest precision, their fp8 control, RMSNorm, the
weight draws and the logit statistics the correctness check compares.

Nothing here imports the program under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def key_from_seed(seed: int):
    """A threefry key from any whole-number seed (wider than 32 bits)."""
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _fp8(x, axis):
    """x rounded to float8_e4m3 with one scale per slice along ``axis``
    (the contraction axis), returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def matmul(x, w, fp8: bool = False):
    """x (..., k) @ w (k, n) in float32. ``fp8`` is the control: both
    operands rounded to fp8 first (activations per row, weights per
    output column), the product still summed in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, delta, eps: float):
    """RMSNorm with weight ``1 + delta`` (the stored deltas are drawn
    around zero)."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + delta.astype(jnp.float32))


def uniform(key, shape, std: float, dtype):
    """Uniform weights of standard deviation ``std`` in ``dtype``."""
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def stacked(key, layers: int, shape, std: float, dtype):
    """A (layers, *shape) leaf drawn one layer at a time, so no float32
    temporary of the whole leaf is ever held."""
    return jax.lax.map(lambda k: uniform(k, shape, std, dtype),
                       jax.random.split(key, layers))


def logit_stats(logits, tokens):
    """Per position i: the best logit, the logit of ``tokens[i + 1]`` (the
    token that followed) and the argmax. logits: (P, V) float32."""
    nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return best, got, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def control_stats(ref_logits, ctl_logits):
    """Per position: the reference's best logit and the reference's logit
    of the token the control puts first."""
    arg = jnp.argmax(ctl_logits, axis=-1)
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, arg[:, None], axis=-1)[:, 0]
    return best, got
