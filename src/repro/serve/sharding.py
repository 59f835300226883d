"""Mesh-aware serving plan: how a decode batch, page pool and fused
graph map onto a jax mesh.

One `ServePlan` is derived from a mesh (`launch.mesh.make_serve_mesh` or
the default `make_host_mesh`) and threaded from launcher to kernel:

- decode rows (and therefore each row's KV pages) shard over the
  ``data`` axis — shard ``s`` of ``dp`` owns rows
  ``[s * b/dp, (s+1) * b/dp)`` and ALL pages of the sequences decoding
  in those rows, so per-shard paged attention never gathers a remote
  page (the dissertation's thesis applied across devices: the pages
  live where the attention compute runs);
- attention / MLP heads shard over the ``model`` axis via
  `sharding.partition.SERVE_RULES` (embeddings / lm_head / norms
  replicate — no per-token all-gather), with the two tensor-parallel
  reduction seams (attention wo-proj, MLP down-proj) psum'd inside the
  fused step body;
- the page-pool arrays carry the `kernels.paged_attention.spec
  .head_sharded_specs` layout: capacity over ``data``, kv heads over
  ``model``.

A 1-device mesh (today's default) collapses to ``plan = None`` — the
exact unsharded code path.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (ATTN, CROSS_ATTN, LOCAL_ATTN, MLA,
                                MLP_DENSE, RGLRU, SSD)
from repro.kernels.paged_attention.spec import head_sharded_specs
from repro.sharding.partition import SERVE_RULES, spec_for

POOL_ARGS = ("k_pages", "v_pages", "k_quant", "v_quant",
             "k_scale", "v_scale")

_is_logical = lambda x: isinstance(x, tuple) and all(  # noqa: E731
    isinstance(e, (str, type(None))) for e in x)


def mesh_axis_sizes(mesh) -> dict:
    try:  # AbstractMesh (deviceless) and Mesh both expose axis_sizes
        return dict(zip(mesh.axis_names, mesh.axis_sizes))
    except (AttributeError, ValueError):
        return dict(zip(mesh.axis_names, mesh.devices.shape))


class ServePlan:
    """dp (rows over "data") x tp (heads over "model") serving layout for
    one mesh; see module docstring. Construct through `from_mesh`, which
    returns None for the trivial 1-device mesh."""

    def __init__(self, mesh: Mesh):
        sizes = mesh_axis_sizes(mesh)
        self.mesh = mesh
        self.dp = int(sizes.get("data", 1))
        self.tp = int(sizes.get("model", 1))

    @staticmethod
    def from_mesh(mesh: Optional[Mesh]) -> Optional["ServePlan"]:
        """None (or a mesh of one device) -> None: the single-device
        serving stack runs the exact pre-mesh code path."""
        if mesh is None:
            return None
        plan = ServePlan(mesh)
        return plan if plan.dp * plan.tp > 1 else None

    def __repr__(self):
        return f"ServePlan(dp={self.dp}, tp={self.tp})"

    # -- validation ---------------------------------------------------------
    def check_config(self, cfg):
        """Fail at engine construction (not deep inside a trace) when the
        model's head/ffn dims cannot split over the model axis."""
        if self.tp == 1:
            return
        mixers = {m for m, _ in cfg.layer_kinds()}
        mlps = {ml for _, ml in cfg.layer_kinds()}
        checks = []
        if mixers & {ATTN, LOCAL_ATTN, MLA, CROSS_ATTN}:
            checks.append(("num_heads", cfg.num_heads))
            # kv heads that the model axis cannot divide (e.g. MQA) are
            # fine as long as each shard's q-head block still maps onto
            # whole kv heads — the pool then replicates the head axis
            if cfg.num_kv_heads % self.tp and \
                    (cfg.num_heads // max(self.tp, 1)) % cfg.num_kv_heads:
                checks.append(("num_kv_heads", cfg.num_kv_heads))
        if MLP_DENSE in mlps:
            checks.append(("d_ff", cfg.d_ff))
        if SSD in mixers:
            nh = (cfg.ssm_expand * cfg.d_model) // cfg.ssm_head_dim
            checks.append(("ssm_heads", nh))
            # the gate norm is taken per group: whole groups on a shard,
            # or one group over all of them (one psum)
            if cfg.ssm_ngroups > 1:
                checks.append(("ssm_ngroups", cfg.ssm_ngroups))
        if RGLRU in mixers:
            checks.append(("lru_width", cfg.lru_width))
        bad = [f"{name}={n}" for name, n in checks if n % self.tp]
        if bad:
            raise ValueError(
                f"{cfg.name}: {', '.join(bad)} not divisible by the "
                f"model-axis size {self.tp} — pick a mesh whose model "
                f"axis divides the head and ffn dims")

    # -- decode rows over the data axis -------------------------------------
    def pad_rows(self, n: int) -> int:
        """Rows the decode batch must carry so every data shard gets an
        equal block (extra rows are seq -1 padding)."""
        return -(-n // self.dp) * self.dp

    def shard_of_row(self, row: int, n_rows: int) -> int:
        """Data shard owning row `row` of an `n_rows`-row batch (equal
        contiguous blocks; `n_rows` must be a multiple of dp)."""
        return row // (n_rows // self.dp)

    # -- page pool ----------------------------------------------------------
    def pool_specs(self, replicate_heads: bool = False) -> tuple:
        """PartitionSpecs of the six layer-stacked pool arrays, in
        `DevicePagePool.arrays` order. `replicate_heads` strips the
        "model" entry (used when kv heads don't divide the model axis —
        e.g. MQA — so every model shard holds the full kv heads)."""
        specs = head_sharded_specs(layer_stacked=True)
        out = tuple(specs[a] for a in POOL_ARGS)
        # degrade to replication on any axis the mesh does not carry
        # (a data-only host mesh has no "model" axis at all), mirroring
        # partition.spec_for's graceful fallback
        sizes = mesh_axis_sizes(self.mesh)
        drop = {"model"} if replicate_heads else set()
        out = tuple(
            P(*(None if ax in drop or (ax is not None and ax not in sizes)
                else ax for ax in s))
            for s in out)
        return out

    def pool_shardings(self, replicate_heads: bool = False) -> tuple:
        return tuple(NamedSharding(self.mesh, s)
                     for s in self.pool_specs(replicate_heads))

    def control_sharding(self) -> NamedSharding:
        """The per-step int32 control block: rows over data."""
        return NamedSharding(self.mesh, P("data", None))

    def token_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P("data"))

    # -- params -------------------------------------------------------------
    def _param_spec(self, shape, logical) -> P:
        logical = tuple(logical)
        if "experts" in logical:
            # MoE subtrees replicate wholesale: per-token top-k routing is
            # local and must score every expert, and the grouped-matmul
            # bucket layout does not survive an ffn split
            return P()
        return spec_for(shape, logical, self.mesh, SERVE_RULES)

    def param_specs(self, model):
        """PartitionSpec tree matching the model params (shard_map
        in_specs)."""
        return jax.tree.map(
            lambda a, lg: self._param_spec(a.shape, lg),
            model.abstract_params(), model.logical(), is_leaf=_is_logical)

    def param_shardings(self, model):
        return jax.tree.map(
            lambda a, lg: NamedSharding(self.mesh,
                                        self._param_spec(a.shape, lg)),
            model.abstract_params(), model.logical(), is_leaf=_is_logical)

    def shard_params(self, model, params):
        """Commit a params tree onto the mesh with the serve layout (head
        and ffn dims split over "model", everything else replicated)."""
        return jax.tree.map(jax.device_put, params,
                            self.param_shardings(model))
