"""Attention variants: GQA/MQA/MHA, sliding-window, cross-attention, MLA.

The core is a chunked online-softmax ("flash"-style) attention written in
pure jnp — memory-safe for 32k prefill under remat, and it doubles as the
oracle for the Pallas flash_attention kernel (see repro/kernels/flash_attention).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec
from repro.models.layers import apply_rope, norm_spec, rms_norm
from repro.sharding.partition import constrain

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Core: chunked online-softmax attention (two-level scan: q chunks × kv chunks)
# ---------------------------------------------------------------------------
def _mask_bias(q_pos, k_pos, *, causal, window, kv_valid_len):
    """(sq, sk) additive bias from causal/window/valid-length masks."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    if kv_valid_len is not None:
        ok &= k_pos[None, :] < kv_valid_len
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention_core(q, k, v, *, causal=True, window=0, q_offset=0,
                   kv_valid_len=None, q_chunk=1024, kv_chunk=1024,
                   softmax_scale=None):
    """q: (b, sq, hq, dd); k, v: (b, skv, hkv, dd). Returns (b, sq, hq, dd).

    GQA via reshaping q heads into (hkv, group). Chunked over both q and kv
    with a running (m, l, acc) online softmax in fp32.
    """
    b, sq, hq, dd = q.shape
    _, skv, hkv, dv = v.shape
    g = hq // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qg = q.reshape(b, sq, hkv, g, dd)

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nk = sq // q_chunk, skv // kv_chunk
    if sq % q_chunk or skv % kv_chunk:
        # fall back to one chunk when sizes don't divide (small/smoke shapes)
        q_chunk, kv_chunk, nq, nk = sq, skv, 1, 1

    def q_step(_, qi):
        qc = jax.lax.dynamic_slice_in_dim(qg, qi * q_chunk, q_chunk, axis=1)
        qc = (qc * scale).astype(qg.dtype)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            m, l, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, axis=1)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            # scores: (b, hkv, g, qc, kc)
            s = jnp.einsum("bqhgd,bshd->bhgqs", qc, kc,
                           preferred_element_type=jnp.float32)
            s += _mask_bias(q_pos, k_pos, causal=causal, window=window,
                            kv_valid_len=kv_valid_len)[None, None, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqs,bshd->bhgqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (b, hkv, g, qc, dv) -> (b, qc, hq, dv)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, hq, dv)
        return None, out.astype(v.dtype)

    if nq == 1:
        _, out = q_step(None, 0)
        return out
    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: (nq, b, qc, hq, dv)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, dv)


# ---------------------------------------------------------------------------
# Standard attention module (ATTN / LOCAL_ATTN / CROSS_ATTN)
# ---------------------------------------------------------------------------
def attn_spec(cfg: ModelConfig, cross: bool = False):
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, hq, hd), ("embed", "heads", "head_dim"), init="fan_in"),
        "wk": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wv": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wo": ParamSpec((hq, hd, d), ("heads", "head_dim", "embed"), init="fan_in"),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((hq, hd), ("heads", "head_dim"), init="zeros")
        s["bk"] = ParamSpec((hkv, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bv"] = ParamSpec((hkv, hd), ("kv_heads", "head_dim"), init="zeros")
    if getattr(cfg, "qk_norm", False):
        s["q_norm"] = norm_spec(hd)
        s["k_norm"] = norm_spec(hd)
    if cross:
        s["gate_attn"] = ParamSpec((), (), init="zeros", dtype="float32")
        s["gate_ffn"] = ParamSpec((), (), init="zeros", dtype="float32")
        s["q_norm_x"] = norm_spec(hd)
        s["k_norm_x"] = norm_spec(hd)
    return s


def _qkv(cfg: ModelConfig, p, x, kv_src):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def roped_qkv(cfg: ModelConfig, p, x, positions):
    """Project + (optional) qk-norm + rope at (b, s) `positions` (none
    where the config has no position encoding) — the shared front half
    of every self-attention mode."""
    q, k_new, v_new = _qkv(cfg, p, x, x)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k_new = rms_norm(k_new, p["k_norm"])
    if not cfg.rope:
        return q, k_new, v_new
    return (apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k_new, positions, cfg.rope_theta), v_new)


def decode_qkv(cfg: ModelConfig, p, x, pos):
    """`roped_qkv` for the decode-step token(s) at absolute position
    `pos` — a scalar shared by the batch (lockstep decode), a (b,) array
    of per-sequence positions (continuous batching, where admitted
    requests sit at different depths), or a (b, s) array giving every
    token its own position (speculative multi-token verify: s consecutive
    draft positions per sequence). Shared by the dense cache path and
    the serve layer's paged decode: the fused serving step traces this
    inside a `lax.scan` over stacked layer params with traced `pos`, so
    it must stay free of host-side branching on values."""
    b, s, _ = x.shape
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        positions = jnp.full((b, s), pos, jnp.int32)
    elif pos.ndim == 1:
        positions = jnp.broadcast_to(pos[:, None], (b, s))
    else:
        positions = jnp.broadcast_to(pos, (b, s))
    return roped_qkv(cfg, p, x, positions)


def attn_apply(cfg: ModelConfig, p, x, *, mode: str, positions=None,
               cache=None, window: int = 0, cross_embeds=None):
    """Returns (y, new_cache).

    mode:  "train" (no cache) | "prefill" (emit cache) | "decode" (use+update).
    cache: {"k","v"}: (b, cap, hkv, hd); for cross layers {"xk","xv"}.
    positions: decode -> scalar cache length; else (b, s) absolute positions.
    """
    cross = cross_embeds is not None or (cache is not None and "xk" in cache)
    b, s, _ = x.shape

    if cross:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if "q_norm_x" in p:
            q = rms_norm(q, p["q_norm_x"])
        if mode == "decode":
            k, v = cache["xk"], cache["xv"]
            new_cache = cache
        else:
            k = jnp.einsum("bnd,dhk->bnhk", cross_embeds, p["wk"])
            v = jnp.einsum("bnd,dhk->bnhk", cross_embeds, p["wv"])
            if "k_norm_x" in p:
                k = rms_norm(k, p["k_norm_x"])
            new_cache = {"xk": k, "xv": v} if mode == "prefill" else None
        y = attention_core(q, k, v, causal=False)
    else:
        if mode == "decode":
            pos = positions  # scalar: current absolute position
            q, k_new, v_new = decode_qkv(cfg, p, x, pos)
            if window:
                # ring buffer of size window; slot = pos % window. RoPE is
                # absolute so slot order is irrelevant under masking.
                cap = cache["k"].shape[1]
                slot = jax.lax.rem(pos, cap)
                k = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
                v = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
                new_cache = {"k": k, "v": v}
                y = attention_core(q, k, v, causal=False,
                                   kv_valid_len=jnp.minimum(pos + 1, cap))
            else:
                k = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
                v = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
                new_cache = {"k": k, "v": v}
                y = attention_core(q, k, v, causal=False, q_offset=pos,
                                   kv_valid_len=pos + 1)
        else:
            q, k_new, v_new = roped_qkv(cfg, p, x, positions)
            y = attention_core(q, k_new, v_new, causal=True, window=window)
            new_cache = ({"k": k_new, "v": v_new} if mode == "prefill" else None)

    y = constrain(y, ("batch", "seq", "heads", "head_dim"))
    out = jnp.einsum("bshk,hkd->bsd", y.astype(x.dtype), p["wo"])
    if cross and "gate_attn" in p:
        out = jnp.tanh(p["gate_attn"]).astype(out.dtype) * out
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, MiniCPM3/DeepSeek-V2 style)
# ---------------------------------------------------------------------------
def mla_spec(cfg: ModelConfig):
    d, h = cfg.d_model, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wdq": ParamSpec((d, qr), ("embed", "q_lora"), init="fan_in"),
        "q_norm": norm_spec(qr),
        "wuq": ParamSpec((qr, h, nope + rope), ("q_lora", "heads", "head_dim"),
                         init="fan_in"),
        "wdkv": ParamSpec((d, kr + rope), ("embed", "kv_lora"), init="fan_in"),
        "kv_norm": norm_spec(kr),
        "wuk": ParamSpec((kr, h, nope), ("kv_lora", "heads", "head_dim"),
                         init="fan_in"),
        "wuv": ParamSpec((kr, h, vd), ("kv_lora", "heads", "head_dim"),
                         init="fan_in"),
        "wo": ParamSpec((h, vd, d), ("heads", "head_dim", "embed"), init="fan_in"),
    }


def mla_apply(cfg: ModelConfig, p, x, *, mode: str, positions=None, cache=None):
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    kr = cfg.kv_lora_rank
    scale = 1.0 / math.sqrt(nope + rope)

    cq = rms_norm(x @ p["wdq"], p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wuq"])
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    dkv = x @ p["wdkv"]
    ckv_new = rms_norm(dkv[..., :kr], p["kv_norm"])
    krope_new = dkv[..., kr:]

    if mode == "decode":
        pos = positions
        q_rope = apply_rope(q_rope, jnp.full((b, s), pos, jnp.int32),
                            cfg.rope_theta)
        krope_new = apply_rope(krope_new[:, :, None, :],
                               jnp.full((b, s), pos, jnp.int32),
                               cfg.rope_theta)[:, :, 0, :]
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos, axis=1)
        krope = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], krope_new.astype(cache["krope"].dtype), pos, axis=1)
        new_cache = {"ckv": ckv, "krope": krope}
        # absorbed attention: score in latent space
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"])      # (b,s,h,kr)
        s_lat = jnp.einsum("bshr,btr->bhst", q_lat, ckv,
                           preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bshk,btk->bhst", q_rope, krope,
                            preferred_element_type=jnp.float32)
        scores = (s_lat + s_rope) * scale
        k_pos = jnp.arange(ckv.shape[1])
        scores = jnp.where(k_pos[None, None, None, :] <= pos, scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        ctx_lat = jnp.einsum("bhst,btr->bshr", w.astype(ckv.dtype), ckv)
        y = jnp.einsum("bshr,rhk->bshk", ctx_lat, p["wuv"])
    else:
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        krope_r = apply_rope(krope_new[:, :, None, :], positions,
                             cfg.rope_theta)[:, :, 0, :]
        k_nope = constrain(jnp.einsum("btr,rhk->bthk", ckv_new, p["wuk"]),
                           ("batch", "seq", "heads", "head_dim"))
        v = constrain(jnp.einsum("btr,rhk->bthk", ckv_new, p["wuv"]),
                      ("batch", "seq", "heads", "head_dim"))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope_r[:, :, None, :],
                                      (*k_nope.shape[:3], rope))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        y = attention_core(qq, k, v, causal=True, softmax_scale=scale)
        new_cache = ({"ckv": ckv_new, "krope": krope_r}
                     if mode == "prefill" else None)

    out = jnp.einsum("bshk,hkd->bsd", y.astype(x.dtype), p["wo"])
    return out, new_cache
