"""DEPRECATED shim — use ``repro.kernels.api.run("ssd_scan", ...)``."""
from __future__ import annotations

from repro.kernels import api


def ssd_scan(x, b_mat, c_mat, dt, a, *, use_kernel: bool = True,
             chunk: int = 128, interpret: bool | None = None):
    args = (x, b_mat, c_mat, dt, a)
    if not use_kernel:
        return api.run("ssd_scan", *args, backend="ref")
    return api.run("ssd_scan", *args, backend="pallas",
                   tile={"chunk": chunk}, interpret=interpret)
