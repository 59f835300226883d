"""DEPRECATED shim — use ``repro.kernels.api.run("hdiff", ...)``.

Kept so existing imports keep working; the flags map 1:1 onto the
registry dispatch (`use_kernel` -> backend, `block_z` -> tile).
"""
from __future__ import annotations

from repro.kernels import api


def hdiff(src, *, use_kernel: bool = True, block_z: int = 1,
          interpret: bool | None = None):
    """Horizontal diffusion over a (nz, ny, nx) grid."""
    if not use_kernel:
        return api.run("hdiff", src, backend="ref")
    return api.run("hdiff", src, backend="pallas",
                   tile={"block_z": block_z}, interpret=interpret)
