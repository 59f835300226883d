"""int8 error-feedback gradient compression (distributed-optimization trick).

Two pieces:
 1. ``make_error_feedback_compressor`` — a grad_transform hook for
    train_step: quantize each gradient leaf to int8 (per-leaf symmetric
    scale), carry the quantization residual to the next step (error
    feedback keeps SGD unbiased in the long run).
 2. ``compressed_psum`` — shard_map demonstration of the wire-level win:
    all-gather int8 + fp32 scale instead of fp32 tensors (≈4x DP-reduce
    bandwidth), summing after dequantization.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def quantize_int8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def make_error_feedback_compressor():
    """grad_transform(grads, state) -> (compressed grads, new state)."""

    def transform(grads, state):
        if state is None:
            state = jax.tree.map(
                lambda g: jnp.zeros(g.shape, jnp.float32), grads)

        def leaf(g, resid):
            total = g.astype(jnp.float32) + resid
            q, scale = quantize_int8(total)
            deq = dequantize_int8(q, scale)
            return deq.astype(g.dtype), total - deq

        flat_g, treedef = jax.tree.flatten(grads)
        flat_r = treedef.flatten_up_to(state)
        out = [leaf(g, r) for g, r in zip(flat_g, flat_r)]
        new_g = treedef.unflatten([o[0] for o in out])
        new_state = treedef.unflatten([o[1] for o in out])
        return new_g, new_state

    return transform


def compressed_psum(x, axis_name: str):
    """Inside shard_map: int8 all-gather + local dequant-sum (bandwidth
    ~x.size bytes instead of 4*x.size for an fp32 ring all-reduce)."""
    q, scale = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)           # int8 on the wire
    ss = jax.lax.all_gather(scale, axis_name)
    return jnp.tensordot(ss, qs.astype(jnp.float32), axes=((0,), (0,)))


def data_parallel_mean_compressed(grads, mesh, axis: str = "data"):
    """Compressed DP-mean over one mesh axis via shard_map (demo/benchmark
    path; the production train_step lets XLA emit the fused reduce)."""
    from jax.sharding import PartitionSpec as P

    def f(g):
        return jax.tree.map(
            lambda t: compressed_psum(t, axis) / mesh.shape[axis], g)

    spec = jax.tree.map(lambda _: P(), grads)
    return jax.shard_map(f, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(grads)
