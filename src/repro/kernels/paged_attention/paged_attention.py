"""Pallas TPU kernel: paged decode attention (GQA), 1 or k query rows.

The KV cache lives in a page pool rather than per-sequence dense buffers:
``{k,v}_pages`` (float, "fast"/HBM tier) and ``{k,v}_quant`` + ``{k,v}_scale``
(int8 + per-row scale, "slow" tier) share one page-id space, and each
sequence names its pages through ``page_table``. Slow-tier content
dequantizes on load: fast pages store zeros in the quant pool and vice
versa, so ``k = k_pages + k_quant * k_scale`` is exact either way.

The work follows each row's live pages, as in the TPU paged-attention
idiom: the six pool arrays stay in HBM (``memory_space=ANY``) and the grid
is ``(rows, kv-head blocks)``. A grid step reads its row's length from the
scalar-prefetched ``lengths`` and loops (``lax.fori_loop``) over
``ceil(live_pages / pages_per_block)`` page blocks, where `live_pages` are
the pages that hold a position some query row of the row sees. A block's
pages are copied with ``make_async_copy``, page ids read from the
scalar-prefetched table, into one of two VMEM buffers: block i+1's copies
are in flight while block i computes. Pages past a row's length are never
copied, so a row costs its own pages and not the table's width; the last
block is partial and masked, so the table width need not be a multiple of
``pages_per_block``. A block is one score tile: its ``pages_per_block x t
x head_block`` (token, head) key rows against the kv-head block's query
rows, with the pairs of different heads masked, then one online-softmax
update and one PV product. ``head_block`` kv heads, and all their
``g = hq // hkv`` query heads, are reduced together.

Layer-stacked pools: the serve layer keeps every layer's pages in one
device-resident pool with a leading layer axis, so the fused decode step
(one jitted graph over the whole layer stack) can scan over layers
without slicing out per-layer copies. Passing 5-D ``(L, P, T, hkv, d)``
pools plus a ``layer`` scalar selects the layer inside the copies: the
layer index rides in as a scalar-prefetch operand, so it may be a traced
value (e.g. the induction variable of an outer ``lax.scan`` over the
layer stack) and the kernel still only copies the named layer's pages.
Flat ``(P, T, hkv, d)`` pools are the one-layer case.

Multi-query-row decode (speculative verify, prompt chunks): ``q`` may be
``(b, k, hq, d)`` — k *consecutive* token positions per sequence, row j
at absolute KV length ``lengths[b] + j`` (``lengths`` names row 0's valid
length, the causal shift of the later rows is baked into the mask). The
k rows fold into the query-head axis (``k * g`` virtual query heads per
kv head), so one KV pass scores all k rows, which is what makes a
speculative verify step cost one decode step of traffic.

Scales: the TPU copies only lane-aligned slices of an HBM array, and a
page's ``(t, hkv)`` scales are not. Each call views the layer's scales as
one row of 128-lane multiples per slot (`_scale_rows`), and a page's row
is copied like its K/V.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotune import LANE

NEG_INF = -1e30


def live_pages(lengths, k: int, page_tokens: int, slots: int):
    """Table entries a row's call copies: the pages holding a position
    that one of its k query rows sees (row j sees ``lengths + j``), at
    most the table's ``slots``. Works on numpy arrays and traced
    scalars alike, so the kernel's trip count and the host's count of
    streamed pages (`PagedKVState.begin_step`) are one formula."""
    return ((lengths + k + page_tokens - 2) // page_tokens).clip(max=slots)


def _paged_kernel(lyr_ref, pt_ref, len_ref, q_ref, *refs, ppb: int, t: int,
                  hkv: int, hb: int, scale: float, g: int, kq: int):
    srcs, o_ref, bufs = refs[:6], refs[6], refs[7:13]
    sem, m_ref, l_ref, acc_ref = refs[13:]
    bi, hi = pl.program_id(0), pl.program_id(1)
    lyr = lyr_ref[0]
    length = len_ref[bi]
    n_pages = live_pages(length, kq, t, pt_ref.shape[1])
    n_blocks = (n_pages + ppb - 1) // ppb
    rows = ppb * t * hb                   # a block's (token, head) key rows
    w = srcs[2].shape[-1]                 # lanes of a page's scale row
    qr, d = q_ref.shape[1:]               # (head, query row, group) rows
    kg = qr // hb

    def page_of(src, pid):
        if src.ndim == 3:                         # (C, 1, w) scale rows
            return src.at[pid]
        if hb == hkv:                             # (L, C, t, hkv, d) pool
            return src.at[lyr, pid]
        return src.at[lyr, pid, :, pl.ds(pl.multiple_of(hi * hb, hb), hb)]

    def each_page(blk, slot, act):
        """Start (or wait for) the copies of block ``blk``'s live pages
        into buffer ``slot``: six per page, one per pool array."""
        for j in range(ppb):
            @pl.when(blk * ppb + j < n_pages)
            def _():
                pid = pt_ref[bi, blk * ppb + j]
                for src, buf in zip(srcs, bufs):
                    getattr(pltpu.make_async_copy(
                        page_of(src, pid), buf.at[slot, j], sem.at[slot]),
                        act)()

    # A page's scale row holds (token, head) at lane token * hkv + head:
    # one exact matmul with ones spreads each key row's scale over d lanes.
    r = jax.lax.broadcasted_iota(jnp.int32, (1, t * hb, w), 1)
    own = jax.lax.broadcasted_iota(jnp.int32, (1, t * hb, w), 2) \
        == (r // hb) * hkv + hi * hb + r % hb
    ones = jnp.ones((w, d), jnp.float32)

    def load(slot, f, qn, sc):
        """Dequantize a block on load, as (token, head) rows of d."""
        s = jnp.dot(jnp.where(own, sc[slot], 0.0).reshape(rows, w), ones,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        return (f[slot].astype(jnp.float32).reshape(rows, d)
                + qn[slot].astype(jnp.float32).reshape(rows, d) * s)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    each_page(0, 0, "start")
    q = q_ref[0].astype(jnp.float32) * scale                # (qr, d)
    # Every query row is scored against every key row of the block and the
    # pairs whose heads differ are masked: hb x the MXU work, but no data
    # moves between a block's token and head axes. Query row j of a head's
    # k * g rows sees length + j positions (consecutive causal rows).
    qrow = jax.lax.broadcasted_iota(jnp.int32, (qr, 1), 0)
    seen = length + (qrow % kg) // g
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    same = col % hb == qrow // kg

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            each_page(i + 1, 1 - slot, "start")

        each_page(i, slot, "wait")
        k = load(slot, *bufs[:3])
        v = load(slot, *bufs[3:])
        base = i * ppb * t
        # rows no query row sees (the partial block's uncopied pages, the
        # last page's tail) hold stale VMEM: zero them so p = 0 there
        # cannot meet a NaN
        vtok = base + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // hb
        v = jnp.where(vtok < length + kq - 1, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(same & (base + col // hb < seen), s, NEG_INF)

        m_prev = m_ref[...]                                 # (qr, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _scale_rows(scales, layer):
    """One layer's scales, (C, t, hkv) of (L, C, t, hkv), as (C, 1, w)
    float32 lane rows, w = t * hkv rounded up to 128 lanes: the TPU copies
    a page's scales only as whole lane-aligned rows."""
    if scales.ndim == 4:
        scales = jax.lax.dynamic_index_in_dim(scales, layer, 0,
                                              keepdims=False)
    c, t, hkv = scales.shape
    w = -(-(t * hkv) // LANE) * LANE
    rows = scales.reshape(c, 1, t * hkv).astype(jnp.float32)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, w - t * hkv)))


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
    sequence, row 0's length). Only a row's `live_pages` table entries
    are read. ``head_block=None`` reduces all kv heads per grid step, the
    one block every TPU accepts (see `spec.head_block_ok`). Returns q's
    shape."""
    stacked = k_pages.ndim == 5
    if stacked and layer is None:
        raise ValueError("layer-stacked pools need a layer index")
    if not stacked and layer is not None:
        raise ValueError("layer index given but pools are not layer-stacked")
    if not stacked:                 # the one-layer case of the stacked form
        k_pages, v_pages, k_quant, v_quant = (
            a[None] for a in (k_pages, v_pages, k_quant, v_quant))
        layer = 0
    layer = jnp.asarray(layer, jnp.int32)
    srcs = (k_pages, k_quant, _scale_rows(k_scale, layer),
            v_pages, v_quant, _scale_rows(v_scale, layer))
    multi = q.ndim == 4
    if multi:
        b, kq, hq, d = q.shape
    else:
        b, hq, d = q.shape
        kq = 1
    t, hkv = k_pages.shape[-3], k_pages.shape[-2]
    g = hq // hkv
    kg = kq * g
    ppb = min(pages_per_block, page_table.shape[1])
    hb = hkv if head_block is None else min(head_block, hkv)
    assert hkv % hb == 0, (hkv, hb)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)

    # fold the k query rows into the grouped-query axis, heads major:
    # (b, hkv * k * g, d)
    if multi:
        qg = q.reshape(b, kq, hkv, g, d).transpose(0, 2, 1, 3, 4)
    else:
        qg = q
    qg = qg.reshape(b, hkv * kg, d)
    scalars = (layer.reshape(1), page_table.astype(jnp.int32),
               lengths.astype(jnp.int32))

    def q_map(bi, hi, *_):
        return (bi, hi, 0)

    def buffers(a):
        """Two buffers of ppb pages of one source array."""
        page = (1,) + a.shape[2:] if a.ndim == 3 else (t, hb, d)
        return pltpu.VMEM((2, ppb) + page, a.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, hkv // hb),
        in_specs=[pl.BlockSpec((1, hb * kg, d), q_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 6,
        out_specs=pl.BlockSpec((1, hb * kg, d), q_map),
        scratch_shapes=[
            *(buffers(a) for a in srcs),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((hb * kg, 1), jnp.float32),
            pltpu.VMEM((hb * kg, 1), jnp.float32),
            pltpu.VMEM((hb * kg, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, ppb=ppb, t=t, hkv=hkv, hb=hb,
                               scale=scale, g=g, kq=kq)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv * kg, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*scalars, qg, *srcs)
    if multi:
        return out.reshape(b, hkv, kq, g, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, kq, hq, d)
    return out.reshape(b, hq, d)
