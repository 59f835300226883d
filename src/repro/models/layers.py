"""Norms, RoPE, MLPs, embeddings."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec


def rms_norm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def norm_spec(d: int) -> ParamSpec:
    # stored as delta around 1 (zeros init) in fp32
    return ParamSpec((d,), (None,), init="zeros", dtype="float32")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    dim = x.shape[-1]
    inv = rope_freqs(dim, theta)                       # (dim/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv   # (..., seq, dim/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU, or a classic 2-matmul FFN with GELU or squared ReLU)
# ---------------------------------------------------------------------------
def gated(cfg: ModelConfig) -> bool:
    """Whether the FFN has a gate projection (SwiGLU)."""
    return cfg.mlp_act == "swiglu"


def ffn_act(cfg: ModelConfig, up, gate=None):
    """The FFN's hidden activation of the up projection ``up`` (and the
    gate projection, SwiGLU only)."""
    if cfg.mlp_act == "swiglu":
        return jax.nn.silu(gate) * up
    if cfg.mlp_act == "gelu":
        return jax.nn.gelu(up)
    if cfg.mlp_act == "relu2":
        return jnp.square(jax.nn.relu(up))
    raise ValueError(f"mlp_act {cfg.mlp_act!r}")


def mlp_spec(cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    s = {
        "up": ParamSpec((d, f), ("embed", "ffn"), init="fan_in"),
        "down": ParamSpec((f, d), ("ffn", "embed"), init="fan_in"),
    }
    if gated(cfg):
        s = {"gate": ParamSpec((d, f), ("embed", "ffn"), init="fan_in"), **s}
    return s


def mlp_apply(cfg: ModelConfig, p, x):
    from repro.sharding.partition import constrain
    h = ffn_act(cfg, x @ p["up"], x @ p["gate"] if gated(cfg) else None)
    h = constrain(h, ("batch", "seq", "ffn"))
    return h @ p["down"]


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------
def embed_spec(cfg: ModelConfig):
    vp = cfg.padded_vocab_size
    out = {"lm_head": ParamSpec((cfg.d_model, vp), ("embed", "vocab"),
                                init="fan_in")}
    if not cfg.external_embed:
        out["tok"] = ParamSpec((vp, cfg.d_model), ("vocab", "embed"))
    return out


def embed_apply(cfg: ModelConfig, p, tokens):
    return jnp.take(p["tok"], tokens, axis=0).astype(jnp.dtype(cfg.compute_dtype))


def lm_head_apply(cfg: ModelConfig, p, x):
    logits = x @ p["lm_head"]
    vp = cfg.padded_vocab_size
    if vp != cfg.vocab_size:  # mask padded vocab entries
        valid = jnp.arange(vp) < cfg.vocab_size
        logits = jnp.where(valid, logits, jnp.asarray(-1e30, logits.dtype))
    return logits
