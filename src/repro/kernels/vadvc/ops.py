"""DEPRECATED shim — use ``repro.kernels.api.run("vadvc", ...)``."""
from __future__ import annotations

from repro.kernels import api


def vadvc(ustage, upos, utens, utens_stage, wcon, *, use_kernel: bool = True,
          tile_y: int = 4, interpret: bool | None = None):
    args = (ustage, upos, utens, utens_stage, wcon)
    if not use_kernel:
        return api.run("vadvc", *args, backend="ref")
    return api.run("vadvc", *args, backend="pallas",
                   tile={"tile_y": tile_y}, interpret=interpret)
