"""Data-centric kernel API: one `KernelSpec` per Pallas kernel, one
`run()` dispatch over all of them.

The thesis' through-line is that data movement should drive design
decisions: window/tile selection (NERO, §3.3.1), number formats (Ch. 4)
and performance prediction (NAPEL, Ch. 5) are all *per-kernel
data-movement models*. A `KernelSpec` packages exactly that knowledge —
the Pallas entry point, the jnp oracle, the tunable tile space, the
analytic VMEM/traffic cost model, and an input generator — so every
data-driven subsystem (autotune, precision search, benchmarks, tests)
consumes a single interface instead of five bespoke `ops.py` wrappers.

    from repro.kernels import api
    y = api.run("hdiff", x)                        # Pallas, default tile
    y = api.run("hdiff", x, backend="ref")         # jnp oracle
    y = api.run("hdiff", x, backend="auto")        # knee-point tile from
                                                   # the spec's cost model
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One validation case: a shape dict, a tile dict, a dtype and any
    extra (non-tile) keyword arguments both backends accept."""
    shape: Mapping[str, int]
    tile: Mapping[str, int]
    dtype: str = "float32"
    kwargs: Mapping[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Everything the data-driven layers need to know about a kernel.

    cost_fn follows the `autotune` contract:
    ``cost_fn(grid_shape, tile, dtype_bytes) -> (vmem_bytes, est_time_s)``
    or ``None`` when the tile does not divide the grid. ``grid_shape`` is
    ``tuple(shape[k] for k in shape_keys)`` — a per-kernel convention
    shared by ``grid_of`` (which recovers it from live arrays).
    """
    name: str
    pallas_fn: Callable          # (*args, **tile, interpret=...) -> out
    ref_fn: Callable             # (*args, **kwargs) -> out (jnp oracle)
    arg_names: tuple             # positional argument names, in order
    shape_keys: tuple            # logical dims defining the grid shape
    tune_space: Mapping[str, tuple]   # tile param -> candidate values
    cost_fn: Callable            # analytic VMEM/traffic model (see above)
    example_inputs: Callable     # (shape=None, dtype=..., seed=0) -> dict
    flops: Callable              # (grid_shape) -> useful flop count
    grid_of: Callable            # (*args) -> grid_shape tuple
    default_shape: Mapping[str, int]      # smoke size (tests, sweeps)
    bench_shape: Mapping[str, int]        # production size (benchmarks)
    vjp_mode: str = "jit"        # "custom_vjp" | "jit" (XLA autodiff)
    dtypes: tuple = ("float32",)
    tol: Mapping[str, float] = dataclasses.field(
        default_factory=lambda: {"float32": 1e-5})
    cases: tuple = ()            # KernelCase sweep for tests

    def grid_from_shape(self, shape: Mapping[str, int] | None = None):
        s = {**self.default_shape, **(shape or {})}
        return tuple(s[k] for k in self.shape_keys)


def as_spec(kernel) -> KernelSpec:
    """Accept a spec or a registered name everywhere."""
    if isinstance(kernel, KernelSpec):
        return kernel
    from repro.kernels import registry
    return registry.get(kernel)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------
BACKENDS = ("pallas", "ref", "auto")


@functools.lru_cache(maxsize=None)
def _jitted(name: str, which: str, frozen_kwargs: tuple):
    spec = as_spec(name)
    fn = spec.ref_fn if which == "ref" else spec.pallas_fn
    return jax.jit(functools.partial(fn, **dict(frozen_kwargs)))


def _freeze(kw: dict) -> tuple:
    return tuple(sorted(kw.items()))


def default_interpret() -> bool:
    """Pallas kernels compile on a TPU and are interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def run(name: str, *args, backend: str = "pallas", tile=None,
        interpret: bool | None = None, **kwargs):
    """Single entry point over every registered kernel.

    backend="pallas" runs the Pallas kernel; "ref" runs the jnp oracle;
    "auto" runs Pallas with tile=None resolved to the knee point of the
    spec's cost model over its tune_space (repro.core.autotune).
    interpret=None follows the platform: compiled by Mosaic on a TPU,
    interpreted (the kernel body executed op by op) everywhere else. A
    kernel that does not compile on the TPU raises; nothing falls back.
    tile=/interpret= are Pallas-only: passing either with backend="ref"
    raises, so a typoed benchmark call can't silently measure the oracle.
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    spec = as_spec(name)
    if backend == "ref":
        if tile is not None or interpret is not None:
            raise ValueError(
                f"{spec.name}: tile={tile!r} / interpret={interpret!r} "
                f"have no effect with backend='ref' — the jnp oracle takes "
                f"no tile parameters; drop them or use backend='pallas'")
        return _jitted(spec.name, "ref", _freeze(kwargs))(*args)
    if interpret is None:
        interpret = default_interpret()
    if tile is None:
        tile = resolve_tile(spec, args) if backend == "auto" else {}
    tile = dict(tile)
    unknown = set(tile) - set(spec.tune_space)
    if unknown:
        raise ValueError(f"{spec.name}: unknown tile params {sorted(unknown)}"
                         f" (tunable: {sorted(spec.tune_space)})")
    kw = {**tile, "interpret": interpret, **kwargs}
    return _jitted(spec.name, "pallas", _freeze(kw))(*args)


# ---------------------------------------------------------------------------
# Tile resolution (NERO knee point) — cached per (kernel, grid, dtype)
# ---------------------------------------------------------------------------
def resolve_tile(kernel, args, vmem_budget: int | None = None) -> dict:
    """Knee-point tile for these arguments, from the spec's cost model."""
    spec = as_spec(kernel)
    grid = tuple(spec.grid_of(*args))
    dtype = str(np.result_type(args[0]) if not hasattr(args[0], "dtype")
                else args[0].dtype)
    return dict(_resolve_cached(spec.name, grid, dtype, vmem_budget))


# Resolved knees live in a plain dict (not an lru_cache) so they can be
# persisted next to checkpoints and reloaded at engine construction — a
# serving restart then skips re-tuning every (kernel, grid, dtype) it
# already saw (ROADMAP: knee persistence for serving restarts).
_KNEES: dict[tuple, tuple] = {}     # (name, grid, dtype, vmem) -> frozen tile
_knees_dirty = False


def _resolve_cached(name, grid, dtype, vmem_budget):
    global _knees_dirty
    key = (name, tuple(grid), dtype, vmem_budget)
    tile = _KNEES.get(key)
    if tile is None:
        from repro.core.autotune import VMEM_BYTES, autotune_kernel
        result = autotune_kernel(as_spec(name), grid, dtype=dtype,
                                 vmem_budget=vmem_budget or VMEM_BYTES)
        tile = _freeze(result["knee"].params)
        _KNEES[key] = tile
        _knees_dirty = True
    return tile


def knee_cache_path(checkpoint_dir) -> "Path":
    """Canonical knee-cache location next to a checkpoint directory."""
    from pathlib import Path
    return Path(checkpoint_dir) / "knee_cache.json"


def save_knee_cache(path) -> int:
    """Write every knee resolved so far to `path` (JSON), MERGED with any
    entries already in the file (in-memory knees win) — so a process that
    only resolved a subset (or whose in-memory store was cleared by
    `invalidate_caches`) never truncates knees persisted by earlier runs.
    Returns the entry count. Cheap enough to call after each
    serve/generate; skipping a no-op rewrite is the caller's choice via
    `knees_dirty()`."""
    global _knees_dirty
    import json
    from pathlib import Path
    p = Path(path)
    merged: dict[tuple, dict] = {}
    if p.exists():
        for e in json.loads(p.read_text()):
            key = (e["kernel"], tuple(e["grid"]), e["dtype"],
                   e["vmem_budget"])
            merged[key] = dict(e["tile"])
    merged.update({k: dict(t) for k, t in _KNEES.items()})
    entries = [{"kernel": k[0], "grid": list(k[1]), "dtype": k[2],
                "vmem_budget": k[3], "tile": t}
               for k, t in sorted(merged.items(),
                                  key=lambda kv: (kv[0][0], kv[0][1],
                                                  kv[0][2], str(kv[0][3])))]
    p.parent.mkdir(parents=True, exist_ok=True)
    # atomic replace: a crash (or concurrent saver) mid-write must never
    # leave a truncated file that breaks the next engine construction
    import os
    tmp = p.with_name(f".{p.name}.{os.getpid()}.tmp")
    tmp.write_text(json.dumps(entries, indent=1))
    os.replace(tmp, p)
    _knees_dirty = False
    return len(entries)


def load_knee_cache(path) -> int:
    """Load previously persisted knees (missing file -> 0). Loaded
    entries pre-populate the resolver, so ``backend="auto"`` dispatches
    skip the tuning sweep for shapes a previous run already resolved.
    A malformed cache is a warning + re-tune, never a startup failure."""
    import json
    import warnings
    from pathlib import Path
    p = Path(path)
    if not p.exists():
        return 0
    try:
        entries = json.loads(p.read_text())
        n = 0
        for e in entries:
            key = (e["kernel"], tuple(e["grid"]), e["dtype"],
                   e["vmem_budget"])
            _KNEES.setdefault(key, _freeze(e["tile"]))
            n += 1
        return n
    except (ValueError, KeyError, TypeError) as err:
        warnings.warn(f"ignoring malformed knee cache {p}: {err} "
                      f"(knees will be re-tuned and the file rewritten)")
        return 0


def knees_dirty() -> bool:
    """True when a knee was resolved since the last save_knee_cache."""
    return _knees_dirty


def invalidate_caches():
    """Drop cached jitted dispatches and resolved tiles; the registry calls
    this on (re-)registration so a reloaded spec takes effect."""
    _jitted.cache_clear()
    _KNEES.clear()


# ---------------------------------------------------------------------------
# Numpy adapter for the precision layers (Ch. 4 sweeps take numpy fns)
# ---------------------------------------------------------------------------
def ref_numpy_fn(kernel, **fixed) -> Callable:
    """fn(**inputs) running the jnp oracle on numpy inputs (fp32 compute,
    numpy out) — the shape `precision_sweep` / `search_fixed_point` expect.
    Integer inputs (page tables, lengths, int8 pools) keep their dtype;
    only inexact inputs are cast to fp32."""
    spec = as_spec(kernel)

    def fn(**inputs):
        import jax.numpy as jnp

        def cast(v):
            v = np.asarray(v)
            return v if np.issubdtype(v.dtype, np.integer) \
                else np.asarray(v, np.float32)

        args = [jnp.asarray(cast(inputs[n])) for n in spec.arg_names]
        return np.asarray(run(spec.name, *args, backend="ref", **fixed))

    return fn
