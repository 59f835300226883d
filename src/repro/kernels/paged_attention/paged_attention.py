"""Pallas TPU kernel: paged decode attention (GQA), 1 or k query rows.

The KV cache lives in a page pool rather than per-sequence dense buffers:
``{k,v}_pages`` (float, "fast"/HBM tier) and ``{k,v}_quant`` + ``{k,v}_scale``
(int8 + per-row scale, "slow" tier) share one page-id space, and each
sequence names its pages through ``page_table``. Pages are gathered by the
BlockSpec index maps from the scalar-prefetched page table (the TPU paged-
attention idiom: the table is known before the kernel body runs, so each
grid step DMAs exactly the pages it needs — no dense gather in HBM).

Grid: (batch, kv-head blocks, page blocks); the page axis is innermost so
the (m, l, acc) online-softmax state lives in VMEM scratch across page
steps. ``pages_per_block`` pages are fetched per step (each as its own
block, indexed off the page table), ``head_block`` kv heads — and all
their ``g = hq // hkv`` query heads — are reduced together. Slow-tier
content dequantizes on load: fast pages store zeros in the quant pool and
vice versa, so ``k = k_pages + k_quant * k_scale`` is exact either way.

Layer-stacked pools: the serve layer keeps every layer's pages in one
device-resident pool with a leading layer axis, so the fused decode step
(one jitted graph over the whole layer stack) can scan over layers
without slicing out per-layer copies. Passing 5-D ``(L, P, T, hkv, d)``
pools plus a ``layer`` scalar selects the layer inside the BlockSpec
index maps — the layer index rides in as a third scalar-prefetch operand,
so it may be a traced value (e.g. the induction variable of an outer
``lax.scan`` over the layer stack) and the kernel still only DMAs the
named layer's pages.

Multi-query-row decode (speculative verify): ``q`` may be
``(b, k, hq, d)`` — k *consecutive* token positions per sequence, row j
at absolute KV length ``lengths[b] + j`` (``lengths`` names row 0's valid
length, the causal shift of the later rows is baked into the mask). The
k rows fold into the query-head axis (``k * g`` virtual query heads per
kv head), so the page streaming, online softmax and grid are exactly the
single-row kernel's — one KV pass scores all k rows, which is what makes
a speculative verify step cost one decode step of traffic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(*args, ppb: int, t: int, scale: float, stacked: bool,
                  g: int, kq: int):
    if stacked:
        _lyr_ref, pt_ref, len_ref, q_ref, *refs = args
    else:
        pt_ref, len_ref, q_ref, *refs = args
    ins = refs[:-4]
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    length = len_ref[bi]
    # stacked pool blocks carry a leading singleton layer axis
    page = (lambda r: r[0, 0]) if stacked else (lambda r: r[0])
    # query row j of the folded (k * g) head axis sees length + j positions
    # (consecutive causal rows); kq == 1 reduces to the plain decode mask
    kg = kq * g
    row = jax.lax.broadcasted_iota(jnp.int32, (1, kg, 1), 1) // g

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip page blocks entirely past the *longest* row of this sequence
    @pl.when(ki * ppb * t < length + kq - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # (hb, kg, d)
        for j in range(ppb):
            kf, kq_, ks, vf, vq, vs = ins[6 * j:6 * j + 6]
            k = (page(kf).astype(jnp.float32)               # (t, hb, d)
                 + page(kq_).astype(jnp.float32)
                 * page(ks).astype(jnp.float32)[..., None])
            v = (page(vf).astype(jnp.float32)
                 + page(vq).astype(jnp.float32)
                 * page(vs).astype(jnp.float32)[..., None])
            s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                                    preferred_element_type=jnp.float32)
            pos = (ki * ppb + j) * t + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, t), 2)
            s = jnp.where(pos < length + row, s, NEG_INF)   # (hb, kg, t)

            m_prev = m_ref[...]                             # (hb, kg, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
            m_ref[...] = m_new
            pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((0,), (1,))),
                                     preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, k_quant, v_quant, k_scale,
                           v_scale, page_table, lengths, layer=None, *,
                           pages_per_block: int = 4,
                           head_block: int | None = None,
                           softmax_scale=None, interpret: bool = False):
    """q: (b, hq, d) single decode token, or (b, k, hq, d) for k
    consecutive causal positions per sequence (row j valid up to
    ``lengths[b] + j`` KV positions — the speculative verify layout);
    {k,v}_pages / {k,v}_quant: (P, T, hkv, d) — or layer-stacked
    (L, P, T, hkv, d) with ``layer`` a scalar int32 (may be traced) naming
    the layer to attend; {k,v}_scale: (P, T, hkv) or (L, P, T, hkv);
    page_table: (b, slots) int32; lengths: (b,) int32 (>= 1 per
    sequence, row 0's length). ``head_block=None`` reduces all kv heads
    per grid step, the one block every TPU accepts (see
    `spec.head_block_ok`). Returns q's shape."""
    stacked = k_pages.ndim == 5
    if stacked and layer is None:
        raise ValueError("layer-stacked pools need a layer index")
    if not stacked and layer is not None:
        raise ValueError("layer index given but pools are not layer-stacked")
    multi = q.ndim == 4
    if multi:
        b, kq, hq, d = q.shape
    else:
        b, hq, d = q.shape
        kq = 1
    t, hkv = k_pages.shape[-3], k_pages.shape[-2]
    slots = page_table.shape[1]
    g = hq // hkv
    kg = kq * g
    ppb = min(pages_per_block, slots)
    hb = hkv if head_block is None else min(head_block, hkv)
    assert slots % ppb == 0 and hkv % hb == 0, (slots, ppb, hkv, hb)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)

    # fold the k query rows into the grouped-query axis: (b, hkv, k * g, d)
    if multi:
        qg = q.reshape(b, kq, hkv, g, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, hkv, kg, d)
    else:
        qg = q.reshape(b, hkv, g, d)
    grid = (b, hkv // hb, slots // ppb)

    if stacked:
        def q_map(bi, hi, ki, lyr, pt, ln):
            return (bi, hi, 0, 0)

        def pool_spec(j):
            return pl.BlockSpec(
                (1, 1, t, hb, d),
                lambda bi, hi, ki, lyr, pt, ln:
                    (lyr[0], pt[bi, ki * ppb + j], 0, hi, 0))

        def scale_spec(j):
            return pl.BlockSpec(
                (1, 1, t, hb),
                lambda bi, hi, ki, lyr, pt, ln:
                    (lyr[0], pt[bi, ki * ppb + j], 0, hi))

        scalars = (jnp.asarray(layer, jnp.int32).reshape(1),
                   page_table.astype(jnp.int32), lengths.astype(jnp.int32))
    else:
        def q_map(bi, hi, ki, pt, ln):
            return (bi, hi, 0, 0)

        def pool_spec(j):
            return pl.BlockSpec(
                (1, t, hb, d),
                lambda bi, hi, ki, pt, ln: (pt[bi, ki * ppb + j], 0, hi, 0))

        def scale_spec(j):
            return pl.BlockSpec(
                (1, t, hb),
                lambda bi, hi, ki, pt, ln: (pt[bi, ki * ppb + j], 0, hi))

        scalars = (page_table.astype(jnp.int32), lengths.astype(jnp.int32))

    in_specs = [pl.BlockSpec((1, hb, kg, d), q_map)]
    operands = [qg]
    for j in range(ppb):
        in_specs += [pool_spec(j), pool_spec(j), scale_spec(j),
                     pool_spec(j), pool_spec(j), scale_spec(j)]
        operands += [k_pages, k_quant, k_scale, v_pages, v_quant, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, kg, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hb, kg, 1), jnp.float32),
            pltpu.VMEM((hb, kg, 1), jnp.float32),
            pltpu.VMEM((hb, kg, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, ppb=ppb, t=t, scale=scale,
                               stacked=stacked, g=g, kq=kq)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, kg, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*scalars, qg, *operands[1:])
    if multi:
        return out.reshape(b, hkv, kq, g, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, kq, hq, d)
    return out.reshape(b, hq, d)
