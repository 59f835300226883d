"""Mixture-of-experts: top-k routing with capacity-bucketed grouped matmuls.

Dispatch is done *per batch row* (tokens stay in their data shard), so the
partitioner keeps routing local: buckets are (batch, experts, capacity, d)
with batch -> data axes and experts -> model axis. Grouped FFN is three
einsums over the expert dim — a clean EP pattern for SPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec
from repro.models.layers import ffn_act, gated

def moe_spec(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = {
        "router": ParamSpec((d, e), ("embed", None), init="fan_in",
                            dtype="float32"),
        "up": ParamSpec((e, d, f), ("experts", "embed", "ffn"), init="fan_in"),
        "down": ParamSpec((e, f, d), ("experts", "ffn", "embed"), init="fan_in"),
    }
    if gated(cfg):
        s["gate"] = ParamSpec((e, d, f), ("experts", "embed", "ffn"),
                              init="fan_in")
    return s


def expert_capacity(cfg: ModelConfig, seq: int) -> int:
    cap = int(seq * cfg.top_k * cfg.moe_capacity_factor / cfg.num_experts)
    return max(8, -(-cap // 8) * 8)   # round up to 8


def moe_apply(cfg: ModelConfig, p, x):
    """x: (b, s, d) -> (y, aux) with aux = load-balancing loss (scalar)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    cap = expert_capacity(cfg, s)

    logits = (x.astype(jnp.float32) @ p["router"])            # (b, s, e)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                      # (b, s, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): e * sum_e frac_tokens_e * frac_prob_e
    me = probs.mean(axis=(0, 1))                              # (e,)
    ce = jax.nn.one_hot(topi, e, dtype=jnp.float32).sum(2).mean(axis=(0, 1))
    aux = e * jnp.sum(me * ce / k)

    # -- per-row dispatch: position of each (token, slot) within its expert --
    def route_row(xi, ti, wi):                                # (s,d),(s,k),(s,k)
        flat_e = ti.reshape(-1)                               # (s*k,)
        order = jnp.argsort(flat_e, stable=True)              # sorted by expert
        e_sorted = flat_e[order]
        tok_sorted = order // k
        # position within expert group
        starts = jnp.searchsorted(e_sorted, jnp.arange(e), side="left")
        pos = jnp.arange(s * k) - starts[e_sorted]
        keep = pos < cap
        buckets = jnp.zeros((e, cap, d), xi.dtype)
        buckets = buckets.at[
            jnp.where(keep, e_sorted, 0),
            jnp.where(keep, pos, 0)].add(
                jnp.where(keep[:, None], xi[tok_sorted], 0))
        # combine metadata: for each (token, slot) its (expert, pos, kept)
        inv = jnp.zeros((s * k,), jnp.int32).at[order].set(
            jnp.arange(s * k, dtype=jnp.int32))
        pos_tok = pos[inv].reshape(s, k)
        keep_tok = keep[inv].reshape(s, k)
        return buckets, pos_tok, keep_tok

    buckets, pos_tok, keep_tok = jax.vmap(route_row)(x, topi, topw)
    # buckets: (b, e, cap, d)
    from repro.sharding.partition import constrain
    buckets = constrain(buckets, ("batch", "experts", "capacity", None))

    up = jnp.einsum("becd,edf->becf", buckets, p["up"])
    h = ffn_act(cfg, up, jnp.einsum("becd,edf->becf", buckets, p["gate"])
                if gated(cfg) else None)
    h = constrain(h, ("batch", "experts", "capacity", "ffn"))
    out_b = jnp.einsum("becf,efd->becd", h, p["down"])        # (b, e, cap, d)
    out_b = constrain(out_b, ("batch", "experts", "capacity", None))

    # gather back per row
    def combine_row(ob, ti, pt, kt, wi):
        # ob: (e, cap, d); ti/pt/kt/wi: (s, k)
        vals = ob[ti, pt]                                     # (s, k, d)
        vals = vals * (kt[..., None] * wi[..., None]).astype(vals.dtype)
        return vals.sum(axis=1)

    y = jax.vmap(combine_row)(out_b, topi, pos_tok, keep_tok,
                              topw.astype(x.dtype))
    return y.astype(x.dtype), aux
