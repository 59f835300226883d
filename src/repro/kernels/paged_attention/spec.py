"""KernelSpec for paged decode attention (serving hot path).

The tune space is (pages_per_block, head_block): more pages per loop trip
and more kv heads per grid step cut fixed costs at the price of VMEM for
the double-buffered page blocks — the same window-vs-resource trade NERO
searches (thesis §3.3.1), here on the serving side. ``example_inputs``
builds a mixed-tier pool (odd page ids are "slow": int8 + per-row scale,
zeros in the float pool) so every consumer — conformance tests,
precision sweeps, bench_nero — exercises the dequant-on-load path by
default.
"""
from __future__ import annotations

import numpy as np

from repro.core.autotune import (GRID_STEP_OVERHEAD_S, HBM_BW, LANE,
                                 PEAK_FLOPS)
from repro.kernels import registry
from repro.kernels.api import KernelCase, KernelSpec
from repro.kernels.paged_attention import ref
from repro.kernels.paged_attention.paged_attention import paged_attention_pallas
from repro.kernels.paged_attention.quant import quantize_page

DEFAULT_SHAPE = {"b": 2, "pages": 16, "page_tokens": 16, "slots": 4,
                 "hq": 4, "hkv": 2, "d": 32, "k": 1}
BENCH_SHAPE = {"b": 16, "pages": 512, "page_tokens": 64, "slots": 32,
               "hq": 32, "hkv": 8, "d": 128, "k": 1}


HEAD_BLOCKS = (1, 2, 4, 8, 16, 32, 64, 128)


def head_block_ok(hkv: int, hb: int) -> bool:
    """Mosaic's block rule: the last two dims of every block are multiples
    of (8, 128) or equal the array's. The pool blocks end in (hb, d) and
    the scale blocks in (t, hb), so a head block must span all ``hkv``
    heads or be a multiple of 128 (which also meets the wider second-minor
    tiling of bf16 and int8 pools)."""
    return hkv % hb == 0 and (hb == hkv or hb % LANE == 0)


# fixed costs of the kernel's page loop, fitted to its times on a TPU v5e
# at the served shapes: issuing and waiting for one page's six copies, and
# one trip of the loop (dequantizing, scoring and the online-softmax update
# of a block)
PAGE_COPY_S = 0.15e-6
BLOCK_S = 1.0e-6


def paged_cost(grid_shape, tile: dict, dtype_bytes: int) -> tuple | None:
    """tile = {"pages_per_block": ppb, "head_block": hb}; None for a head
    block the TPU refuses (`head_block_ok`). A grid step (one row, one
    head block) streams the row's live pages, at most the table's
    ``slots``, in blocks of ppb: the model charges the bytes of a full
    table (float + int8 pages and each page's scale row, read whatever
    tier holds the page) at HBM bandwidth, or its MXU FLOPs, plus a fixed
    cost per grid step, per page copied and per block. A block scores all
    its (token, head) key rows against all the head block's query rows,
    hb x the useful FLOPs, and its last, partial block is computed in
    full. VMEM holds two buffers of ppb pages of all six arrays, the
    block's dequantized K and V and its scores, and q/out. The k query
    rows (speculative verify, prompt chunks) ride along the folded head
    axis: q/out traffic, FLOPs and the q/out/softmax VMEM scale by k while
    the dominant KV stream does not — the cost-model face of "more
    compute per byte moved"."""
    b, pages, t, slots, hq, hkv, d, k = grid_shape
    ppb, hb = min(tile["pages_per_block"], slots), tile["head_block"]
    if not head_block_ok(hkv, hb):
        return None
    steps = b * (hkv // hb)
    blocks = -(-slots // ppb)
    rows = ppb * t * hb                             # key rows of a block
    qr = hb * k * (hq // hkv)                       # query rows
    w = -(-(t * hkv) // LANE) * LANE                # a page's scale row
    page = t * hb * d * (dtype_bytes + 1) + w * 4   # one array pair + scales
    vmem = (2 * 2 * ppb * page + 2 * rows * d * 4 + 2 * qr * rows * 4
            + 2 * 2 * qr * d * dtype_bytes + qr * (d + 2) * 4)
    traffic = 2 * b * k * hq * d * dtype_bytes + 2 * steps * slots * page
    flops = 4 * steps * blocks * qr * rows * d
    align = 1.0 if d % LANE == 0 else 1.0 + (LANE - d % LANE) / LANE
    time = max(traffic * align / HBM_BW, flops / PEAK_FLOPS) \
        + steps * (GRID_STEP_OVERHEAD_S + slots * PAGE_COPY_S
                   + blocks * BLOCK_S)
    return vmem, time


def example_inputs(shape=None, dtype=np.float32, seed: int = 0) -> dict:
    """Mixed-tier pool: odd page ids live in the slow (int8) tier, even in
    the fast (float) tier; each sequence gets distinct pages and a random
    valid length (>= 1), so partial-page masking is always exercised.
    ``k > 1`` emits a (b, k, hq, d) multi-query-row q (speculative verify:
    row j valid to lengths + j) with lengths drawn so the last row still
    fits the table."""
    s = {**DEFAULT_SHAPE, **(shape or {})}
    b, pages, t, slots = s["b"], s["pages"], s["page_tokens"], s["slots"]
    hq, hkv, d, k = s["hq"], s["hkv"], s["d"], s.get("k", 1)
    assert b * slots <= pages, "each sequence needs distinct pages"
    assert k >= 1 and slots * t - (k - 1) >= 1, (k, slots, t)
    rng = np.random.default_rng(seed)

    def pool(raw):
        slow = (np.arange(pages) % 2 == 1)[:, None, None, None]
        quant, qscale = quantize_page(raw)     # the serve tier's format
        fast = np.where(slow, 0.0, raw).astype(dtype)
        qq = np.where(slow, quant, 0).astype(np.int8)
        sc = np.where(slow, qscale, 0.0)[..., 0].astype(dtype)
        return fast, qq, sc

    kf, kq, ks = pool(rng.normal(size=(pages, t, hkv, d)))
    vf, vq, vs = pool(rng.normal(size=(pages, t, hkv, d)))
    table = rng.permutation(pages)[:b * slots].reshape(b, slots)
    q_shape = (b, hq, d) if k == 1 else (b, k, hq, d)
    return {
        "q": rng.normal(size=q_shape).astype(dtype),
        "k_pages": kf, "v_pages": vf,
        "k_quant": kq, "v_quant": vq,
        "k_scale": ks, "v_scale": vs,
        "page_table": table.astype(np.int32),
        "lengths": rng.integers(1, slots * t - (k - 1) + 1, b)
        .astype(np.int32),
    }


def head_sharded_specs(k: int = 1, *, data_axis: str = "data",
                       model_axis: str = "model",
                       layer_stacked: bool = True) -> dict:
    """The kernel's `shard_map` calling convention for mesh-sharded
    serving: PartitionSpec per argument (plus ``"out"``) such that every
    shard's kernel call is fully LOCAL — no cross-device page gather.

    Page capacity shards over the data axis (each decode row's pages live
    on the shard that decodes it, so the page table indexes only local
    slots) and kv heads shard over the model axis. Query heads shard over
    the model axis too, which is legal because query head ``h`` attends
    kv head ``h // (hq // hkv)``: when the model axis divides both ``hq``
    and ``hkv``, shard ``s``'s contiguous q-head block is exactly the
    ``g = hq // hkv`` query heads of each of its kv heads, so the kernel's
    GQA head folding is preserved per shard. Pools are the serve layer's
    layer-stacked ``(L, C, t, hkv, hd)`` arrays (``layer_stacked=False``
    drops the leading layer dim for the flat kernel-level layout);
    ``k > 1`` is the multi-query-row verify shape ``(b, k, hq, d)``."""
    from jax.sharding import PartitionSpec as P

    d, m = data_axis, model_axis
    ll = (None,) if layer_stacked else ()
    pool = P(*ll, d, None, m, None)
    scale = P(*ll, d, None, m)
    q = P(d, m, None) if k == 1 else P(d, None, m, None)
    return {
        "q": q,
        "k_pages": pool, "v_pages": pool,
        "k_quant": pool, "v_quant": pool,
        "k_scale": scale, "v_scale": scale,
        "page_table": P(d, None), "lengths": P(d),
        "layer": P(),
        "out": q,
    }


def _grid_of(q, k_pages, v_pages, k_quant, v_quant, k_scale, v_scale,
             page_table, lengths, *layer):
    """Handles both the flat (P, T, hkv, d) pools and the serve layer's
    layer-stacked (L, P, T, hkv, d) pools with a trailing layer operand,
    and both the single-row (b, hq, d) and multi-query-row (b, k, hq, d)
    q: per-layer capacity is the grid's page count either way."""
    k = q.shape[1] if q.ndim == 4 else 1
    b, hq, d = q.shape[0], q.shape[-2], q.shape[-1]
    pages, t, hkv = k_pages.shape[-4], k_pages.shape[-3], k_pages.shape[-2]
    return b, pages, t, page_table.shape[1], hq, hkv, d, k


SPEC = registry.register(KernelSpec(
    name="paged_attention",
    pallas_fn=paged_attention_pallas,
    ref_fn=ref.paged_attention,
    arg_names=("q", "k_pages", "v_pages", "k_quant", "v_quant",
               "k_scale", "v_scale", "page_table", "lengths"),
    shape_keys=("b", "pages", "page_tokens", "slots", "hq", "hkv", "d", "k"),
    tune_space={"pages_per_block": (1, 2, 4, 8),
                "head_block": HEAD_BLOCKS},
    cost_fn=paged_cost,
    example_inputs=example_inputs,
    # 2 matmuls x 2 flops over every (q row, q head, kv position) pair
    flops=lambda g: 4.0 * g[0] * g[7] * g[4] * g[3] * g[2] * g[6],
    grid_of=_grid_of,
    default_shape=DEFAULT_SHAPE,
    bench_shape=BENCH_SHAPE,
    vjp_mode="jit",
    dtypes=("float32", "bfloat16"),
    tol={"float32": 5e-5, "bfloat16": 0.04},
    cases=(
        KernelCase({"b": 2, "pages": 16, "page_tokens": 16, "slots": 4,
                    "hq": 4, "hkv": 2, "d": 32},
                   {"pages_per_block": 2, "head_block": 1}),
        KernelCase({"b": 1, "pages": 32, "page_tokens": 8, "slots": 8,
                    "hq": 8, "hkv": 4, "d": 64},
                   {"pages_per_block": 4, "head_block": 2}),
        KernelCase({"b": 2, "pages": 12, "page_tokens": 16, "slots": 2,
                    "hq": 4, "hkv": 4, "d": 16},
                   {"pages_per_block": 1, "head_block": 4}),
        KernelCase({"b": 2, "pages": 16, "page_tokens": 16, "slots": 4,
                    "hq": 4, "hkv": 2, "d": 32},
                   {"pages_per_block": 2, "head_block": 2},
                   dtype="bfloat16"),
        # multi-query-row (speculative verify): k consecutive causal rows
        KernelCase({"b": 2, "pages": 16, "page_tokens": 16, "slots": 4,
                    "hq": 4, "hkv": 2, "d": 32, "k": 4},
                   {"pages_per_block": 2, "head_block": 1}),
        KernelCase({"b": 1, "pages": 32, "page_tokens": 8, "slots": 8,
                    "hq": 8, "hkv": 4, "d": 64, "k": 3},
                   {"pages_per_block": 4, "head_block": 2}),
        KernelCase({"b": 2, "pages": 16, "page_tokens": 16, "slots": 4,
                    "hq": 4, "hkv": 2, "d": 32, "k": 2},
                   {"pages_per_block": 2, "head_block": 2},
                   dtype="bfloat16"),
    ),
))
