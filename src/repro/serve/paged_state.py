"""Per-layer paged-state protocol: one serving substrate for three state kinds.

The thesis' argument — design the memory system around what the data
actually *is* (arXiv:2208.08886) — applied to our own serving stack: a
dense-attention KV cache, a recurrent SSM/LRU state and a sliding-window
ring each have a different natural layout, and forcing all of them
through O(len) KV pages wastes the hierarchy. This module keys the
layout off `ModelConfig.pattern` per layer:

``kv``    `ATTN` (and `MLA`) layers: page-pool KV exactly as before —
          O(len/page_tokens) pages per sequence, tiered fast/slow/host,
          prefix-shareable by content hash. (MLA's compressed cache is
          protocol-compatible but the fused graph has no MLA paged
          attention yet — `supports_paged` still declines it.)

``rec``   `SSD` / `RGLRU` layers: ONE fixed-size state block per
          sequence per layer (the SSD (H, P, N) state + conv taps, or
          the RG-LRU (W,) state + conv taps), held in a
          `RecurrentStore` sharing the device pool's slot discipline
          (per-shard free lists, trash slot for dead rows, host parking
          for preemption). O(1) per sequence regardless of length; the
          fused step updates it in place: one token through the
          single-token forms of `ssd_decode_core` / `rglru_decode_core`,
          a prompt chunk through `ssd_chunk_core` (SSD: the state is read
          and written once a chunk) or an RG-LRU scan.

``none``  layers with no token mixer (an MLP alone): no state.

``ring``  `LOCAL_ATTN` layers: a window-sized circular page set. Pages
          fill exactly like KV pages, but once ``pos >= window`` the
          oldest page no longer intersects any future query's window and
          its pool page + device slot are recycled — pool need is
          O(window), not O(len). Ring pages carry no content hash (a
          dropped-prefix page can never be prefix-shared).

`StateLayout` is the static map from a config's layer stack to this
substrate (per-kind layer indices for the scan graph, control-block
column layout, per-request page charge for the scheduler's admission
math). `RecurrentStore` owns the recurrent device arrays. The
``*_fused_*`` functions are the jit-traceable step forms the fused
decode graph (`serve.paged_decode.build_fused_step`) scans over.

A row's state starts from zero on the device: the control block's
``fresh`` column tells the fused step to read zeros instead of the slot
at the row's first step, so admission uploads nothing.

Speculative verify over recurrent layers checkpoints by construction:
the pre-step state is *read* (never overwritten in-scan), the k
candidate post-token states come out of the scan as stacked outputs,
and after the accept rule picks ``keep`` tokens per row, ONE scatter
per store writes the state checkpoint at index ``keep - 1``. Rollback
is selecting an earlier checkpoint — O(1) per token, never a replay of
the sequence (the `RecurrentStore` read/write counters let tests assert
exactly that).
"""
from __future__ import annotations

import functools
import math
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ATTN, CROSS_ATTN, LOCAL_ATTN, MIXER_NONE, MLA,
                                MLP_DENSE, MLP_MOE, MLP_NONE, RGLRU, SSD)
from repro.models.rglru import rglru_decode_core
from repro.models.ssm import ssd_chunk_core, ssd_decode_core, ssm_dims

KV, REC, RING, STATELESS = "kv", "rec", "ring", "none"

RGLRU_CONV_TAPS = 4          # Griffin's fixed temporal conv width
TRASH_SLOT = 0               # each data shard's first recurrent slot: dead
                             # rows' trash, whose content nothing reads


def state_kind(mixer: str):
    """Which paged-state substrate a mixer's layer state lives on, or
    None for mixers the protocol does not cover (cross-attention)."""
    if mixer == MIXER_NONE:
        return STATELESS
    if mixer in (ATTN, MLA):
        return KV
    if mixer == LOCAL_ATTN:
        return RING
    if mixer in (SSD, RGLRU):
        return REC
    return None


# ---------------------------------------------------------------------------
# Static layout: layer stack -> substrate map + control columns + page math
# ---------------------------------------------------------------------------
class ControlCols:
    """Column offsets into the per-step int32 control block for one
    (slots, k) shape. Pure-ATTN stacks keep the exact legacy layout; a
    stack with recurrent or ring layers appends columns at the end:

    ``rec``        this row's shard-local recurrent slot (has_rec)
    ``fresh``      1 at a row's first step: its recurrent state starts
                   from zero, not from the slot (has_rec)
    ``base``       dropped-ring-page count: table position n holds the
                   logical page ``base + n`` (has_ring)
    ``keep_fixed`` k > 1 only: fixed token-keep count for chunked
                   prefill rows (-1 for verify rows, whose keep comes
                   from the in-graph accept rule)
    ``keep_cap``   k > 1 only: cap on accepted drafts (the row's real
                   proposal count; pad drafts must not advance state)
    """

    def __init__(self, layout: "StateLayout", slots: int, k: int):
        s = slots
        if k == 1:
            self.tail, self.row, self.pos, self.len = s, s + 1, s + 2, s + 3
            w = s + 4
        else:
            self.tail, self.spill = s, s + 1
            self.row, self.pos, self.len = s + 2, s + 3, s + 4
            self.tok = s + 5
            w = s + 5 + k
        if layout.has_rec:
            self.rec, self.fresh = w, w + 1
            w += 2
        if layout.has_ring:
            self.base = w
            w += 1
        if layout.has_rec and k > 1:
            self.keep_fixed, self.keep_cap = w, w + 1
            w += 2
        self.width = w


class StateLayout:
    """Static description of how one model's layer stack maps onto the
    paged-state substrate. Deterministic in (cfg, page_tokens) — the
    host bookkeeping (`PagedKVState`) and the fused graph builder
    construct identical layouts independently."""

    def __init__(self, cfg, page_tokens: int):
        self.cfg = cfg
        self.page_tokens = page_tokens
        self.kinds = cfg.layer_kinds()
        mixers = [m for m, _ in self.kinds]
        self.roles = [state_kind(m) for m in mixers]
        # KV-bearing layers own the pool's layer axis (0..n_kv-1);
        # recurrent layers own their store's layer axis the same way
        self.kv_of: dict[int, int] = {}
        self.ssd_of: dict[int, int] = {}
        self.rg_of: dict[int, int] = {}
        for l, m in enumerate(mixers):
            if m in (ATTN, MLA, LOCAL_ATTN):
                self.kv_of[l] = len(self.kv_of)
            elif m == SSD:
                self.ssd_of[l] = len(self.ssd_of)
            elif m == RGLRU:
                self.rg_of[l] = len(self.rg_of)
        self.n_kv = len(self.kv_of)
        self.n_ssd = len(self.ssd_of)
        self.n_rg = len(self.rg_of)
        self.has_rec = (self.n_ssd + self.n_rg) > 0
        self.has_ring = any(m == LOCAL_ATTN for m in mixers)
        self.window = cfg.window if self.has_ring else 0
        # scan-group structure: counts + within-group ranks so a traced
        # group index g resolves each layer's substrate row as
        # g * per_group + rank (and tail layers index past every group)
        gs = cfg.group_size()
        self.gs = gs
        self.n_groups = cfg.num_layers // gs
        group_mixers = mixers[:gs]

        def ranks(pred):
            out, c = [], 0
            for m in group_mixers:
                out.append(c if pred(m) else None)
                c += 1 if pred(m) else 0
            return out, c

        self.kv_rank, self.kv_per_group = ranks(
            lambda m: m in (ATTN, MLA, LOCAL_ATTN))
        self.ssd_rank, self.ssd_per_group = ranks(lambda m: m == SSD)
        self.rg_rank, self.rg_per_group = ranks(lambda m: m == RGLRU)
        # tail layers: substrate rows continue after the scanned groups
        self.tail_kv, self.tail_ssd, self.tail_rg = [], [], []
        kv0 = self.n_groups * self.kv_per_group
        s0 = self.n_groups * self.ssd_per_group
        r0 = self.n_groups * self.rg_per_group
        for m in mixers[self.n_groups * gs:]:
            self.tail_kv.append(kv0 if m in (ATTN, MLA, LOCAL_ATTN) else None)
            self.tail_ssd.append(s0 if m == SSD else None)
            self.tail_rg.append(r0 if m == RGLRU else None)
            kv0 += m in (ATTN, MLA, LOCAL_ATTN)
            s0 += m == SSD
            r0 += m == RGLRU

    # -- control block -------------------------------------------------------
    def cols(self, slots: int, k: int = 1) -> ControlCols:
        return ControlCols(self, slots, k)

    # -- ring math -----------------------------------------------------------
    def ring_pages(self) -> int:
        """Full pages a ring layer can need at once: the window plus one
        partially-out-of-window page — O(window / page_tokens)."""
        return -(-self.window // self.page_tokens) + 1

    def ring_base(self, pos: int) -> int:
        """Logical index of the oldest page any query at absolute
        position >= ``pos`` can still see (the oldest in-window column
        is ``pos - window + 1``). Pages below it are recyclable."""
        oldest = pos - self.window + 1
        return max(0, oldest // self.page_tokens) if oldest > 0 else 0

    # -- admission math ------------------------------------------------------
    def pages_needed(self, cap_tokens: int, tail_slots: int = 1) -> int:
        """True pool-page charge for a request growing to ``cap_tokens``:
        KV layers pay O(len) pages, ring layers O(window), recurrent
        layers zero (their state lives in the RecurrentStore, charged in
        rows, not pages). One charge per KV-bearing layer."""
        t = self.page_tokens
        full = -(-cap_tokens // t)
        if self.has_ring:
            full = min(full, self.ring_pages())
        return self.n_kv * (full + tail_slots)

    def rec_state_bytes(self) -> int:
        """Host-visible recurrent state footprint per sequence (all
        recurrent layers) — the O(1)-per-request quantity `bench_traffic`
        reports against the O(len) dense-cache alternative."""
        cfg = self.cfg
        total = 0
        if self.n_ssd:
            din, nh, conv_dim = ssm_dims(cfg)
            k = cfg.ssm_conv_width
            per = (nh * cfg.ssm_head_dim * cfg.ssm_state * 4
                   + (k - 1) * conv_dim * 4)
            total += self.n_ssd * per
        if self.n_rg:
            w = cfg.lru_width
            total += self.n_rg * (w * 4 + (RGLRU_CONV_TAPS - 1) * w * 4)
        return total


def supports_paged_layout(cfg) -> bool:
    """Whether the paged-state protocol covers every layer of `cfg`:
    ATTN / LOCAL_ATTN / SSD / RGLRU / no mixers with dense/MoE/none MLPs.
    ATTN and LOCAL_ATTN cannot mix in one stack (the pool's page groups
    are layer-uniform, and ring recycling drops whole groups — a global
    layer would lose pages it still needs). MLA and cross-attention
    stay on the dense decode path."""
    mixers = {m for m, _ in cfg.layer_kinds()}
    if any(mlp not in (MLP_DENSE, MLP_MOE, MLP_NONE)
           for _, mlp in cfg.layer_kinds()):
        return False
    if mixers & {MLA, CROSS_ATTN}:
        return False
    if not mixers <= {ATTN, LOCAL_ATTN, SSD, RGLRU, MIXER_NONE}:
        return False
    if ATTN in mixers and LOCAL_ATTN in mixers:
        return False
    return True


# ---------------------------------------------------------------------------
# Device-resident recurrent slot store
# ---------------------------------------------------------------------------
def rec_array_names(layout: StateLayout) -> tuple:
    """Names (and order) of the recurrent store arrays a layout needs —
    the fused graph and the `RecurrentStore` derive the same tuple
    independently so the donated-array protocol cannot drift."""
    names = []
    if layout.n_ssd:
        names += ["ssd_state", "ssd_conv"]
    if layout.n_rg:
        names += ["rg_h", "rg_conv"]
    return tuple(names)


# logical axes per store array, aligned with rec_array_names order
_REC_LOGICAL = {
    "ssd_state": (None, "data", "model", None, None),
    "ssd_conv": (None, "data", None, None),
    "rg_h": (None, "data", "model"),
    "rg_conv": (None, "data", None, "model"),
}


def rec_array_specs(layout: StateLayout, plan=None) -> tuple:
    """shard_map PartitionSpecs aligned with `rec_array_names(layout)`.
    Axes the plan's mesh does not carry degrade to replication (a
    data-only host mesh has no "model" axis at all)."""
    if plan is None:
        return tuple(P() for _ in rec_array_names(layout))
    from repro.serve.sharding import mesh_axis_sizes
    sizes = mesh_axis_sizes(plan.mesh)
    return tuple(
        P(*(ax if ax is None or ax in sizes else None
            for ax in _REC_LOGICAL[n]))
        for n in rec_array_names(layout))


def _flat1(a):
    return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])


@functools.lru_cache(maxsize=None)
def _jit_rec_scatter():
    return jax.jit(lambda f, idx, v: f.at[idx].set(v), donate_argnums=(0,))


def rec_read(arr, idx, slots, fresh):
    """(b, ...) state blocks at rows ``[idx, slots]`` of an (L, R, ...)
    store array (`idx` may be traced: the scan group index), with zeros
    for the rows whose state starts fresh (``fresh`` (b,) nonzero): a new
    row's slot is never cleared by an upload, the step reads zeros in
    its place."""
    vals = _flat1(arr)[idx * arr.shape[1] + slots]
    keep = (fresh == 0).reshape((-1,) + (1,) * (vals.ndim - 1))
    return jnp.where(keep, vals, jnp.zeros((), vals.dtype))


def rec_scatter(arr, idx, slots, vals):
    """In-place (donated) write of per-row state blocks at [idx, slots]."""
    flat = _flat1(arr)
    return flat.at[idx * arr.shape[1] + slots].set(
        vals.astype(arr.dtype)).reshape(arr.shape)


class RecurrentStore:
    """Slot-addressed device arrays for every recurrent layer's per-
    sequence state, sharing the `DevicePagePool` slot discipline: global
    slot ids split into per-data-shard contiguous ranges, shard-local
    ids inside the fused graph, a per-shard trash slot for dead rows,
    free-list recycling, and host parking for preemption.

    ``arrays`` (in `names` order, subset of (ssd_state, ssd_conv, rg_h,
    rg_conv)) ride the fused step's donated array tuple right behind the
    six KV pool arrays. Under a mesh plan the slot axis shards over
    "data" and the state width over "model" (SSD heads / LRU width, like
    attention heads); conv taps replicate where the channel layout mixes
    head-local and group-shared channels.

    The store is sized once, for ``batch_hint`` rows and a trash slot per
    data shard, and never grows: a growth would copy every array (GBs at
    published widths) and retrace the step.
    """

    _instances: "weakref.WeakSet[RecurrentStore]" = weakref.WeakSet()

    def __init__(self, layout: StateLayout, batch_hint: int = 1, plan=None,
                 compute_dtype=jnp.float32):
        cfg = layout.cfg
        self.layout = layout
        self.plan = plan
        self.shards = plan.dp if plan is not None else 1
        tp = plan.tp if plan is not None else 1
        rows = -(-max(1, batch_hint) // self.shards)
        self.slots_local = rows + 1           # + the shard's trash slot
        self.slots = self.shards * self.slots_local
        self.names = list(rec_array_names(layout))
        shapes = {}
        if layout.n_ssd:
            din, nh, conv_dim = ssm_dims(cfg)
            if tp > 1 and nh % tp:
                raise ValueError(
                    f"{cfg.name}: ssm heads {nh} not divisible by the "
                    f"model-axis size {tp}")
            k = cfg.ssm_conv_width
            shapes["ssd_state"] = (layout.n_ssd, self.slots, nh,
                                   cfg.ssm_head_dim, cfg.ssm_state)
            shapes["ssd_conv"] = (layout.n_ssd, self.slots, k - 1, conv_dim)
        if layout.n_rg:
            w = cfg.lru_width
            if tp > 1 and w % tp:
                raise ValueError(
                    f"{cfg.name}: lru_width {w} not divisible by the "
                    f"model-axis size {tp}")
            shapes["rg_h"] = (layout.n_rg, self.slots, w)
            shapes["rg_conv"] = (layout.n_rg, self.slots,
                                 RGLRU_CONV_TAPS - 1, w)
        dtypes = {"ssd_state": jnp.float32, "ssd_conv": compute_dtype,
                  "rg_h": jnp.float32, "rg_conv": jnp.float32}
        self._specs = rec_array_specs(layout, plan)
        self._shardings = None
        self.arrays = tuple(jnp.zeros(shapes[n], dtypes[n])
                            for n in self.names)
        if plan is not None:
            self._shardings = tuple(NamedSharding(plan.mesh, s)
                                    for s in self._specs)
            self.arrays = tuple(jax.device_put(a, s) for a, s in
                                zip(self.arrays, self._shardings))
        lc = self.slots_local
        self._free = [list(range((s + 1) * lc - 1, s * lc - 1, -1))
                      for s in range(self.shards)]
        self._used: set[int] = set()
        self.trash = [self.alloc(s) for s in range(self.shards)]
        assert all(self.local_slot(t) == TRASH_SLOT for t in self.trash)
        self.writes = 0      # host->device scatter calls
        self.reads = 0       # device->host slot pulls
        RecurrentStore._instances.add(self)

    def specs(self) -> tuple:
        """PartitionSpecs aligned with `arrays` (shard_map in_specs)."""
        return self._specs

    def local_slot(self, slot: int) -> int:
        return slot % self.slots_local

    def shard_of_slot(self, slot: int) -> int:
        return slot // self.slots_local

    # -- slots ---------------------------------------------------------------
    def alloc(self, shard: int = 0) -> int:
        if not self._free[shard]:
            raise RuntimeError(
                f"data shard {shard} exhausted its {self.slots_local} "
                f"recurrent slots — size batch_hint to the per-shard "
                f"worst case (the store never grows)")
        slot = self._free[shard].pop()
        self._used.add(slot)
        return slot

    def release_slot(self, slot: int):
        self._used.discard(slot)
        self._free[self.shard_of_slot(slot)].append(slot)

    # -- content -------------------------------------------------------------
    def _scatter_one(self, i: int, slot: int, blocks):
        """blocks: (L, ...) per-layer values for one slot of array i."""
        a = self.arrays[i]
        idx = np.arange(a.shape[0], dtype=np.int64) * self.slots + slot
        out = _jit_rec_scatter()(_flat1(a), jnp.asarray(idx),
                                 jnp.asarray(blocks, a.dtype))
        arrs = list(self.arrays)
        arrs[i] = out.reshape(a.shape)
        self.arrays = tuple(arrs)
        self.writes += 1

    def write_slot(self, slot: int, blocks: dict):
        """Host -> device: install per-layer state blocks at one slot.
        ``blocks`` maps every one of `names` to (L_kind, ...) arrays —
        prefill installation and swap-in both land here."""
        if set(blocks) != set(self.names):
            raise ValueError(f"a slot's state is written whole: got "
                             f"{sorted(blocks)}, the store holds "
                             f"{self.names}")
        for name, val in blocks.items():
            self._scatter_one(self.names.index(name), slot, val)

    def read_slot(self, slot: int) -> dict:
        """Device -> host: every store's per-layer blocks at one slot
        (swap-out parking, tests). Counts one read per store array."""
        out = {}
        for name, a in zip(self.names, self.arrays):
            out[name] = np.asarray(a[:, slot])
            self.reads += 1
        return out

    def check_invariants(self) -> None:
        for shard, free in enumerate(self._free):
            uniq = set(free)
            assert len(uniq) == len(free), \
                f"shard {shard} recurrent free list holds duplicates"
            for slot in uniq:
                assert self.shard_of_slot(slot) == shard, \
                    f"recurrent slot {slot} on wrong shard free list"
                assert slot not in self._used, \
                    f"recurrent slot {slot} both free and in use"


# ---------------------------------------------------------------------------
# Fused step forms (traced inside the jitted decode graph)
# ---------------------------------------------------------------------------
class StoreRows:
    """The batch rows of one layer of a recurrent store array, as the
    ``map_rows`` of the SSD cores: each row's block (zeros for a fresh
    row) is read, advanced and written back in place, one row at a time,
    so no batch of blocks is ever formed beside the store. A dead row
    (the trash slot) is skipped: its output is zeros and no block
    moves."""

    def __init__(self, idx, slots, fresh):
        self.slots, self.fresh = slots, fresh
        self.idx = jnp.asarray(idx, jnp.int32)

    def map(self, arr, step, inputs):
        """(outs, new store array) of ``step(block, inputs_i) -> (out_i,
        block')`` over the rows of ``arr``."""

        def body(arr, xs):
            slot, fresh, inp = xs
            at = (self.idx, slot.astype(jnp.int32)) + (0,) * (arr.ndim - 2)

            def advance(arr):
                blk = jax.lax.dynamic_slice(arr, at,
                                            (1, 1) + arr.shape[2:])[0, 0]
                blk = jnp.where(fresh != 0, jnp.zeros((), blk.dtype), blk)
                out, blk = step(blk, inp)
                return jax.lax.dynamic_update_slice(
                    arr, blk.astype(arr.dtype)[None, None], at), out

            def skip(arr):
                out = jax.eval_shape(lambda a: advance(a)[1], arr)
                return arr, jax.tree.map(
                    lambda o: jnp.zeros(o.shape, o.dtype), out)

            return jax.lax.cond(slot == TRASH_SLOT, skip, advance, arr)

        arr, outs = jax.lax.scan(body, arr, (self.slots, self.fresh, inputs))
        return outs, arr


def rec_advance(cfg, kind_mixer, p, x, arrays, idx, slots, fresh, n,
                tp: int = 1):
    """Advance one recurrent layer's rows over x: (b, k, d), row i by its
    first ``n[i]`` tokens (the rest are padding), in the store arrays
    ``arrays`` ((conv, state) for SSD, (h, conv) for RG-LRU) at layer row
    ``idx``. Returns ``(y (b, k, d), new arrays)``. One token is the
    single-token decode core; a wider step is SSD's chunk form or an
    RG-LRU scan. The SSD state passes through `StoreRows` (read and
    written once, in place); the small conv taps and RG-LRU state are
    gathered and scattered."""
    k = x.shape[1]
    if kind_mixer == SSD:
        conv0 = rec_read(arrays[0], idx, slots, fresh)
        rows = StoreRows(idx, slots, fresh).map
        if k == 1:
            y, conv1, st = ssd_decode_core(cfg, p, x, conv0, arrays[1],
                                           tp=tp, map_rows=rows)
        else:
            y, conv1, st = ssd_chunk_core(cfg, p, x, conv0, arrays[1], n,
                                          tp=tp, map_rows=rows)
        return y, (rec_scatter(arrays[0], idx, slots, conv1), st)
    state0 = tuple(rec_read(a, idx, slots, fresh) for a in arrays)
    if k == 1:
        y, h1, conv1 = rglru_decode_core(cfg, p, x, *state0, tp=tp)
        state1 = (h1, conv1)
    else:
        y, states = rec_scan_tokens(cfg, kind_mixer, p, x, state0, tp=tp)
        state1 = tuple(select_checkpoint(s, n) for s in states)
    return y, tuple(rec_scatter(a, idx, slots, v)
                    for a, v in zip(arrays, state1))


def rec_scan_tokens(cfg, kind_mixer, p, x, state0, tp: int = 1):
    """Run k single-token recurrent steps over x: (b, k, d) from the
    checkpoint ``state0`` (tuple of state leaves), emitting every
    intermediate state as a stacked output — the substrate of recurrent
    speculative verify: nothing is overwritten, so 'rollback' is
    selecting checkpoint ``keep - 1``. Returns
    ``(y (b, k, d), states)`` where each states leaf is (k, b, ...)."""
    core = ssd_decode_core if kind_mixer == SSD else rglru_decode_core

    def body(carry, xj):
        if kind_mixer == SSD:
            conv, st = carry
            yj, conv, st = core(cfg, p, xj[:, None, :], conv, st, tp=tp)
            return (conv, st), (yj[:, 0], conv, st)
        h, conv = carry
        yj, h, conv = core(cfg, p, xj[:, None, :], h, conv, tp=tp)
        return (h, conv), (yj[:, 0], h, conv)

    _, (ys, sa, sb) = jax.lax.scan(body, state0, x.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2), (sa, sb)


def select_checkpoint(stacked, keep):
    """Per-row checkpoint pick: stacked (k, b, ...) candidate states,
    keep (b,) in [1, k] -> (b, ...) the state after `keep` tokens."""
    sel = jnp.clip(keep - 1, 0, stacked.shape[0] - 1)
    idx = sel[None, :].reshape((1, -1) + (1,) * (stacked.ndim - 2))
    return jnp.take_along_axis(stacked, idx, axis=0)[0]


def ring_attend(q, k_all, v_all, *, lengths, base, positions, window: int,
                page_tokens: int):
    """Sliding-window attention over ring-gathered pages, mirroring
    `attention_core`'s single-chunk online-softmax numerics.

    q: (b, kq, hq, hd) already roped; k_all/v_all: (b, S, hkv, hd) the
    ring gather (S = table_slots * page_tokens, table position n holding
    logical page ``base + n``); lengths: (b,) valid rows for query row
    0; base: (b,) dropped-page counts; positions: (b, kq) absolute query
    positions. Column j's absolute position is ``base * page_tokens +
    j``; query row jq masks to ``j < lengths + jq`` and the window."""
    b, kq, hq, hd = q.shape
    hkv = k_all.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)
    qg = (q.reshape(b, kq, hkv, g, hd) * scale).astype(q.dtype)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_all,
                   preferred_element_type=jnp.float32)
    j = jnp.arange(k_all.shape[1], dtype=jnp.int32)
    offs = jnp.arange(kq, dtype=jnp.int32)
    ok = j[None, None, :] < (lengths[:, None, None] + offs[None, :, None])
    abs_col = base[:, None] * page_tokens + j[None, :]          # (b, S)
    ok &= abs_col[:, None, :] > (positions[:, :, None] - window)
    bias = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
    s = s + bias[:, None, None]                                 # (b,h,g,q,s)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    pv = jnp.einsum("bhgqs,bshd->bhgqd", p.astype(v_all.dtype), v_all,
                    preferred_element_type=jnp.float32)
    out = pv / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, kq, hq, hd) \
        .astype(v_all.dtype)


def gather_ring_kv(arrays, pool_layer, table):
    """Gather one layer's ring pages for the batch from the stacked pool
    arrays, dequantizing slow cells exactly like the paged kernel
    (``k = k_pages + k_quant * k_scale``). table: (b, s) shard-local
    slots -> (k_all, v_all): (b, s * t, hkv, hd)."""
    kf, vf, kq, vq, ks, vs = arrays
    c, t = kf.shape[1], kf.shape[2]
    rows = pool_layer * c + table                              # (b, s)

    def flat(a):
        return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])

    def merge(f, q, sc):
        out = flat(f)[rows] + flat(q)[rows] * flat(sc)[rows][..., None]
        b, s = table.shape
        return out.reshape(b, s * t, out.shape[-2], out.shape[-1])

    return merge(kf, kq, ks), merge(vf, vq, vs)
