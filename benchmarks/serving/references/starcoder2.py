"""Plain float32 reference of the starcoder2 decoder as the program serves
it, and the seeded weights both of them read.

Follows StarCoder2 (arXiv:2402.19173; huggingface.co/bigcode/starcoder2-7b):
pre-norm decoder, grouped-query attention with rotary embeddings (rotate
half), biased q/k/v projections, GELU (tanh) MLP. It departs from the
published model where the served program does, so that the comparison is
about serving and not about the architecture (each departure is listed in
the config file): RMSNorm with weight ``1 + delta`` instead of LayerNorm
with bias, no bias on the output and MLP projections, global attention
(the published 4096-token window never binds at the cell's lengths).

No cache, no batching, no kernels: one sequence of ``P`` tokens at a time,
every layer over every position, causal softmax in full, all in float32
at the highest matmul precision. Imports nothing of the program.

The weights are drawn here, on the device, in one compiled call, in the
layout of the program's parameter tree, so the program and this reference
read the same arrays and neither makes them.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from serving import refmath as rm


class Dims(NamedTuple):
    d: int
    ff: int
    hq: int
    hkv: int
    hd: int
    layers: int
    vocab: int
    theta: float
    eps: float

    @property
    def vocab_padded(self) -> int:
        """Rows of the embedding and columns of the head as the program
        lays them out (the vocabulary rounded up to 256)."""
        return -(-self.vocab // 256) * 256


def dims(cfg: dict) -> Dims:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return Dims(d=d, ff=cfg["intermediate_size"], hq=hq,
                hkv=cfg["num_key_value_heads"], hd=d // hq,
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["as_run"]["norm_epsilon"]))


@functools.partial(jax.jit, static_argnums=(0,))
def _make(n: Dims, key):
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(key, 16))
    d, L = n.d, n.layers
    inv = 1.0 / math.sqrt(d)
    layer = {
        "norm1": rm.uniform(next(ks), (L, d), 0.1, f32),
        "attn": {
            "wq": rm.stacked(next(ks), L, (d, n.hq, n.hd), inv, bf),
            "wk": rm.stacked(next(ks), L, (d, n.hkv, n.hd), inv, bf),
            "wv": rm.stacked(next(ks), L, (d, n.hkv, n.hd), inv, bf),
            "wo": rm.stacked(next(ks), L, (n.hq, n.hd, d),
                             1.0 / math.sqrt(n.hq * n.hd), bf),
            "bq": rm.uniform(next(ks), (L, n.hq, n.hd), 0.02, bf),
            "bk": rm.uniform(next(ks), (L, n.hkv, n.hd), 0.02, bf),
            "bv": rm.uniform(next(ks), (L, n.hkv, n.hd), 0.02, bf),
        },
        "norm2": rm.uniform(next(ks), (L, d), 0.1, f32),
        "mlp": {
            "up": rm.stacked(next(ks), L, (d, n.ff), inv, bf),
            "down": rm.stacked(next(ks), L, (n.ff, d),
                               1.0 / math.sqrt(n.ff), bf),
        },
    }
    return {
        "embed": {"tok": rm.uniform(next(ks), (n.vocab_padded, d), 0.02, bf),
                  "lm_head": rm.uniform(next(ks), (d, n.vocab_padded), inv,
                                        bf)},
        "groups": {"l0": layer},
        "final_norm": rm.uniform(next(ks), (d,), 0.1, f32),
    }


def make_params(cfg: dict, seed: int):
    """The served weights, bf16 (norm deltas float32), from the seed."""
    return _make(dims(cfg), rm.key_from_seed(seed))


def _rope(x, pos, theta):
    """x: (P, heads, hd), rotate-half convention."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                           / x.shape[-1]))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _logits(n: Dims, params, tokens, fp8: bool):
    P = tokens.shape[0]
    pos = jnp.arange(P)
    g = n.hq // n.hkv
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def mm(a, w):
        return rm.matmul(a, w, fp8)

    def layer(x, p):
        a = p["attn"]
        h = rm.rms_norm(x, p["norm1"], n.eps)
        q = mm(h, a["wq"].reshape(n.d, -1)).reshape(P, n.hq, n.hd) \
            + a["bq"].astype(jnp.float32)
        k = mm(h, a["wk"].reshape(n.d, -1)).reshape(P, n.hkv, n.hd) \
            + a["bk"].astype(jnp.float32)
        v = mm(h, a["wv"].reshape(n.d, -1)).reshape(P, n.hkv, n.hd) \
            + a["bv"].astype(jnp.float32)
        q, k = _rope(q, pos, n.theta), _rope(k, pos, n.theta)
        q = q.reshape(P, n.hkv, g, n.hd)
        s = jnp.einsum("qngd,knd->ngqk", q, k,
                       precision=rm.HIGHEST) / math.sqrt(n.hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), v,
                       precision=rm.HIGHEST).reshape(P, n.hq * n.hd)
        x = x + mm(o, a["wo"].reshape(n.hq * n.hd, n.d))
        h = rm.rms_norm(x, p["norm2"], n.eps)
        u = jax.nn.gelu(mm(h, p["mlp"]["up"]), approximate=True)
        return x + mm(u, p["mlp"]["down"]), None

    x, _ = jax.lax.scan(layer, x, params["groups"]["l0"])
    x = rm.rms_norm(x, params["final_norm"], n.eps)
    return mm(x, params["embed"]["lm_head"][:, :n.vocab])


@functools.partial(jax.jit, static_argnums=(0,))
def _stats(n: Dims, params, tokens):
    return rm.logit_stats(_logits(n, params, tokens, False), tokens)


@functools.partial(jax.jit, static_argnums=(0,))
def _control(n: Dims, params, tokens):
    ref = _logits(n, params, tokens, False)
    return rm.control_stats(ref, _logits(n, params, tokens, True))


def stats(cfg: dict, params, tokens):
    """(best, logit of the next token, argmax) per position of one
    sequence of token ids."""
    return _stats(dims(cfg), params, jnp.asarray(tokens, jnp.int32))


def control(cfg: dict, params, tokens):
    """(reference best, reference logit of the fp8 control's argmax)."""
    return _control(dims(cfg), params, jnp.asarray(tokens, jnp.int32))


def flops_per_token(cfg: dict) -> float:
    """Forward FLOPs of one token outside attention's context term:
    every projection, the MLP and the head (2 per multiply-add)."""
    n = dims(cfg)
    per_layer = n.d * (n.hq + 2 * n.hkv) * n.hd + n.hq * n.hd * n.d \
        + 2 * n.d * n.ff
    return 2.0 * (n.layers * per_layer + n.d * n.vocab)


def flops_per_context_token(cfg: dict) -> float:
    """Attention FLOPs one query spends per token of context it attends
    (q.k and p.v over every layer)."""
    n = dims(cfg)
    return 4.0 * n.layers * n.hq * n.hd


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> float:
    """Bytes of K and V one token holds in one layer at the compute
    dtype (bf16): what attention has to read per token of context."""
    n = dims(cfg)
    return 2.0 * n.hkv * n.hd * dtype_bytes


def attention_layers(cfg: dict) -> int:
    return dims(cfg).layers


def q_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> float:
    """Bytes of one query row in and one output row out of attention."""
    n = dims(cfg)
    return 2.0 * n.hq * n.hd * dtype_bytes


def page_row(cfg: dict) -> tuple[int, int]:
    """(kv heads, head_dim) of one token's row in a KV page."""
    n = dims(cfg)
    return n.hkv, n.hd
