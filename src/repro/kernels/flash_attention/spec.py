"""KernelSpec for blocked flash attention (custom-vjp: Pallas fwd, XLA bwd
via the reference formulation — recompute, no residuals)."""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

from repro.core.autotune import (GRID_STEP_OVERHEAD_S, HBM_BW, LANE,
                                 PEAK_FLOPS)
from repro.kernels import registry
from repro.kernels.api import KernelCase, KernelSpec, default_interpret
from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas

DEFAULT_SHAPE = {"b": 2, "sq": 128, "skv": 128, "hq": 4, "hkv": 2, "d": 64}
BENCH_SHAPE = {"b": 8, "sq": 2048, "skv": 2048, "hq": 32, "hkv": 8, "d": 128}


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=0, block_q=128, block_k=128,
                    interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


def _fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out = flash_attention(q, k, v, causal, window, block_q, block_k, interpret)
    return out, (q, k, v)


def _bwd(causal, window, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: ref.attention(q, k, v, causal=causal,
                                                   window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)


def _pallas_entry(q, k, v, *, causal=True, window=0, block_q=128,
                  block_k=128, interpret=None):
    """Keyword-style wrapper so the registry dispatch (api.run) reaches the
    differentiable custom-vjp entry with tile params as kwargs."""
    return flash_attention(q, k, v, causal, window, block_q, block_k,
                           interpret)


def flash_cost(grid_shape, tile: dict, dtype_bytes: int,
               causal: bool = True) -> tuple | None:
    """tile = {"block_q": bq, "block_k": bk}. Q/O stream once; K/V blocks
    re-stream once per q-block row (the kv-innermost flash schedule), so a
    larger bq cuts HBM traffic at the price of VMEM and softmax state."""
    b, sq, skv, hq, hkv, d = grid_shape
    bq, bk = tile["block_q"], tile["block_k"]
    if sq % bq or skv % bk:
        return None
    # q + out blocks, k + v blocks (double buffered) + fp32 (m, l, acc)
    vmem = (2 * bq * d + 2 * bk * d) * dtype_bytes * 2 + bq * (d + 2) * 4
    frac = 0.5 if causal else 1.0       # fully-masked kv blocks are skipped
    traffic = (2 * b * hq * sq * d
               + 2 * b * hkv * skv * d * (sq // bq) * frac) * dtype_bytes
    flops = 4 * b * hq * sq * skv * d * frac
    steps = b * hq * (sq // bq) * max(int((skv // bk) * frac), 1)
    align = 1.0 if d % LANE == 0 else 1.0 + (LANE - d % LANE) / LANE
    time = max(traffic * align / HBM_BW, flops / PEAK_FLOPS) \
        + steps * GRID_STEP_OVERHEAD_S
    return vmem, time


def example_inputs(shape=None, dtype=np.float32, seed: int = 0) -> dict:
    s = {**DEFAULT_SHAPE, **(shape or {})}
    rng = np.random.default_rng(seed)
    return {
        "q": rng.normal(size=(s["b"], s["sq"], s["hq"], s["d"])).astype(dtype),
        "k": rng.normal(size=(s["b"], s["skv"], s["hkv"],
                              s["d"])).astype(dtype),
        "v": rng.normal(size=(s["b"], s["skv"], s["hkv"],
                              s["d"])).astype(dtype),
    }


def _grid_of(q, k, *rest):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    return b, sq, skv, hq, hkv, d


SPEC = registry.register(KernelSpec(
    name="flash_attention",
    pallas_fn=_pallas_entry,
    ref_fn=ref.attention,
    arg_names=("q", "k", "v"),
    shape_keys=("b", "sq", "skv", "hq", "hkv", "d"),
    tune_space={"block_q": (32, 64, 128, 256),
                "block_k": (32, 64, 128, 256)},
    cost_fn=flash_cost,
    example_inputs=example_inputs,
    # 2 matmuls x 2 flops, causal default halves the score tile work
    flops=lambda g: 2.0 * g[0] * g[3] * g[1] * g[2] * g[5],
    grid_of=_grid_of,
    default_shape=DEFAULT_SHAPE,
    bench_shape=BENCH_SHAPE,
    vjp_mode="custom_vjp",
    dtypes=("float32", "bfloat16"),
    tol={"float32": 5e-5, "bfloat16": 0.03},
    cases=(
        KernelCase({"b": 2, "sq": 128, "skv": 128, "hq": 4, "hkv": 2,
                    "d": 64}, {"block_q": 64, "block_k": 64}),
        KernelCase({"b": 1, "sq": 256, "skv": 256, "hq": 8, "hkv": 1,
                    "d": 32}, {"block_q": 64, "block_k": 64}),
        KernelCase({"b": 2, "sq": 128, "skv": 128, "hq": 4, "hkv": 4,
                    "d": 64}, {"block_q": 64, "block_k": 64},
                   kwargs={"causal": False}),
        KernelCase({"b": 1, "sq": 256, "skv": 256, "hq": 2, "hkv": 2,
                    "d": 64}, {"block_q": 64, "block_k": 64},
                   kwargs={"window": 64}),
        KernelCase({"b": 1, "sq": 128, "skv": 128, "hq": 2, "hkv": 2,
                    "d": 128}, {"block_q": 64, "block_k": 64},
                   dtype="bfloat16"),
    ),
))
