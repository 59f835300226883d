"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """`jax.make_mesh` with Auto axis types on every axis."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_abstract_mesh(shape, axes):
    """Deviceless `AbstractMesh` of these axis sizes and names."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests/examples): 1D data mesh."""
    return make_mesh_compat((len(jax.devices()),), ("data",))


def make_serve_mesh(data: int = 1, model: int = 1):
    """2-D serving mesh over the first ``data * model`` local devices:
    decode rows shard over "data", attention/MLP heads over "model" (the
    layout `serve.sharding.ServePlan` consumes). Built over an explicit
    device slice — not `jax.make_mesh`, which may use every device — so
    one 8-device host can carry 1x1, 2x2 and 2x4 meshes side by side."""
    import numpy as np
    from jax.sharding import Mesh

    n = data * model
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(
            f"serve mesh {data}x{model} needs {n} devices, have "
            f"{len(devs)} (forced host devices: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})")
    return Mesh(np.asarray(devs[:n]).reshape(data, model),
                ("data", "model"))
