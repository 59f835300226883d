"""DEPRECATED shim — use ``repro.kernels.api.run("rglru_scan", ...)``."""
from __future__ import annotations

from repro.kernels import api


def lru_scan(a, b, *, use_kernel: bool = True, chunk: int = 256,
             interpret: bool | None = None):
    if not use_kernel:
        return api.run("rglru_scan", a, b, backend="ref")
    return api.run("rglru_scan", a, b, backend="pallas",
                   tile={"chunk": chunk}, interpret=interpret)
