"""Parameter-spec machinery: one source of truth for shape/logical-axes/init.

A module describes its parameters as a pytree of ``ParamSpec`` leaves; the
same tree materializes real params, abstract (ShapeDtypeStruct) params, and
PartitionSpecs — so init, dry-run, and sharding can never drift apart.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    logical: tuple                 # logical axis name per dim (None = replicated)
    init: str = "normal"           # normal | zeros | ones | fan_in | custom:<name>
    scale: float = 0.02
    dtype: Optional[str] = None    # override model param dtype (e.g. fp32 norms)

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def stack_specs(tree, n: int):
    """Prepend a scan ("layers") dim of size n to every spec in the tree."""
    def f(ps: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + tuple(ps.shape), ("layers",) + tuple(ps.logical),
                         ps.init, ps.scale, ps.dtype)
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def _draw(ps: ParamSpec, shape, key):
    """float32 sample of ``ps``'s distribution in ``shape`` (all of
    ``ps.shape``, or one layer of a stacked leaf), drawn as a matrix: the
    TPU compiler takes longer over a draw of more dimensions."""
    if len(shape) > 2:
        return _draw(ps, (int(np.prod(shape[:-1])), shape[-1]),
                     key).reshape(shape)
    if ps.init == "fan_in":
        fan_in = ps.shape[0] if len(ps.shape) == 1 else int(np.prod(ps.shape[:-1]))
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return std * jax.random.normal(key, shape, jnp.float32)
    if ps.init == "alog":  # mamba2 A_log init: log(uniform[1,16])
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if ps.init == "lambda":  # RG-LRU Lambda: a = sigmoid(L) in [0.9, 0.999]
        u = jax.random.uniform(key, shape, jnp.float32, 0.9, 0.999)
        return jnp.log(u / (1 - u))
    return ps.scale * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_leaf(ps: ParamSpec, key, default_dtype):
    """One leaf, compiled so the float32 draw fuses into the cast: the
    device holds the leaf in its own dtype and no full-leaf float32
    temporary (a bf16 7B model's stacked FFN leaf would need 10.9 GB).
    Stacked leaves draw one layer per loop step: the TPU compiler takes
    about five times as long over a whole 16-layer FFN leaf."""
    dtype = jnp.dtype(ps.dtype or default_dtype)
    if ps.init == "zeros":
        return jnp.zeros(ps.shape, dtype)
    if ps.init == "ones":
        return jnp.ones(ps.shape, dtype)
    if ps.logical[:1] == ("layers",):
        return jax.lax.map(lambda k: _draw(ps, ps.shape[1:], k).astype(dtype),
                           jax.random.split(key, ps.shape[0]))
    return _draw(ps, ps.shape, key).astype(dtype)


def materialize(spec_tree, key, default_dtype):
    leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(ps, k, default_dtype) for ps, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract(spec_tree, default_dtype):
    def f(ps: ParamSpec):
        return jax.ShapeDtypeStruct(ps.shape, jnp.dtype(ps.dtype or default_dtype))
    return jax.tree.map(f, spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def logical_tree(spec_tree):
    return jax.tree.map(lambda ps: tuple(ps.logical), spec_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))
