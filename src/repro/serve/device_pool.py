"""Device-resident page-pool arrays for the paged-attention gather.

The host `PagedKVPool` owns page *lifecycle* (placement, ref counts, LRU
demotion, byte stats); this mirror keeps page *contents* resident in
preallocated jax arrays so the decode-step gather is an index update +
jitted kernel dispatch instead of re-stacking the whole pool in host
numpy every step (the thesis' data-movement argument applied to our own
serving hot path: keep the computation next to the resident data).

All layers share ONE pool with a leading layer axis on its six arrays:
``(num_layers, capacity, page_tokens, hkv, hd)``. A *slot* is
layer-uniform — the same KV token range lives at slot ``s`` of every
layer — because the paged structure is identical across layers (each
decode token appends one row to every layer's tail, prefill writes the
same page count per layer, and prefix sharing is layer-consistent). One
page *group* (the per-layer pool pids of one logical page, keyed by its
layer-0 pid) therefore occupies one slot, and a single page table per
decode step serves the whole layer stack — the layout the fused jitted
decode step scans over.

Both tier representations share one slot-id space, exactly the layout the
paged-attention kernel consumes: a fast (layer, slot) cell holds float
K/V and zeros in the int8 + scale arrays, a slow cell the reverse, so
``k = k_pages + k_quant * k_scale`` is exact either way. A cell is
written in full on (re)assignment — a recycled slot can never leak a
previous occupant's other-tier content into the sum. Tier is per
(layer, page): one group may mix fast and slow cells across layers.

Sync is incremental and versioned: a page is written when its group is
new to the mirror, and rewritten in place when its `Page.version`
changes (LRU demotion, swap). The pool reports those pages
(`PagedKVPool.watch`), so a sync visits the groups it is handed and the
pages in ``stale``, never the whole mirror. Write batches are padded to
the next power of two (duplicate trailing indices — last write wins on
identical data) so jit caches a bounded set of scatter shapes as the
pool grows.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np


# The pool arrays are donated on every update: XLA reuses the input
# buffers, so a write is an in-place index update (O(rows written)), not a
# full-pool copy (O(capacity)). Callers must always adopt the returned
# arrays — `DevicePagePool` reassigns `self.arrays` from every call and
# never touches the donated objects again. All scatters flatten the
# leading (layer, slot[, row]) axes to one index so XLA performs them
# in place on the donated buffer (the multi-axis `.at[l, s]` form lowers
# to a copying gather-scatter).
def _flat2(a):
    return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])


# The factories take the pool's NamedSharding tuple (hashable; None for
# the unsharded pool) so a mesh-sharded pool's writes pin their outputs
# to the same layout the donated inputs carry — the scatter stays an
# in-place per-shard update rather than a resharding copy.
@functools.lru_cache(maxsize=None)
def _jit_write_fast(shardings=None):
    def f(kf, vf, kq, vq, ks, vs, idx, k, v):
        return (_flat2(kf).at[idx].set(k).reshape(kf.shape),
                _flat2(vf).at[idx].set(v).reshape(vf.shape),
                _flat2(kq).at[idx].set(0).reshape(kq.shape),
                _flat2(vq).at[idx].set(0).reshape(vq.shape),
                _flat2(ks).at[idx].set(0.0).reshape(ks.shape),
                _flat2(vs).at[idx].set(0.0).reshape(vs.shape))
    return jax.jit(f, donate_argnums=(0, 1, 2, 3, 4, 5),
                   out_shardings=shardings)


@functools.lru_cache(maxsize=None)
def _jit_write_slow(shardings=None):
    def f(kf, vf, kq, vq, ks, vs, idx, kq_new, ks_new, vq_new, vs_new):
        return (_flat2(kf).at[idx].set(0.0).reshape(kf.shape),
                _flat2(vf).at[idx].set(0.0).reshape(vf.shape),
                _flat2(kq).at[idx].set(kq_new).reshape(kq.shape),
                _flat2(vq).at[idx].set(vq_new).reshape(vq.shape),
                _flat2(ks).at[idx].set(ks_new).reshape(ks.shape),
                _flat2(vs).at[idx].set(vs_new).reshape(vs.shape))
    return jax.jit(f, donate_argnums=(0, 1, 2, 3, 4, 5),
                   out_shardings=shardings)


@functools.lru_cache(maxsize=None)
def _jit_write_rows(shardings=None):
    # single-axis scatter on a flattened (layer, slot, row) index; `layer`
    # is an operand so one compiled scatter serves the whole layer stack
    def f(kf, vf, layer, slots, rows, k_rows, v_rows):
        c, t = kf.shape[1], kf.shape[2]
        idx = (layer * c + slots) * t + rows
        flat = (kf.shape[0] * c * t,) + kf.shape[3:]

        def upd(a, x):
            return a.reshape(flat).at[idx].set(x).reshape(a.shape)

        return upd(kf, k_rows), upd(vf, v_rows)
    return jax.jit(f, donate_argnums=(0, 1), out_shardings=shardings)


def _pad_pow2(idx: np.ndarray, *stacks):
    """Pad a write batch to the next power of two by repeating the last
    entry — duplicate scatter indices with identical payloads are benign
    and keep the jitted scatter shapes bounded as the pool grows."""
    n = len(idx)
    m = 1
    while m < n:
        m *= 2
    if m == n:
        return (idx, *stacks)
    reps = m - n
    idx = np.concatenate([idx, np.repeat(idx[-1:], reps)])
    return (idx, *(np.concatenate([s, np.repeat(s[-1:], reps, axis=0)])
                   for s in stacks))


class DevicePagePool:
    """Layer-stacked, slot-addressed device arrays mirroring a
    `PagedKVPool` across the whole layer stack.

    ``arrays`` is the kernel's stacked pool-argument tuple ``(k_pages,
    v_pages, k_quant, v_quant, k_scale, v_scale)`` with a leading layer
    axis; `sync` keeps it current for a set of page *groups* (the
    per-layer pids of one logical page), `write_rows` streams decode-token
    rows into one layer of a tail slot, and released slots are recycled
    through a free list.
    """

    # every live mirror, for test-teardown invariant sweeps (conftest)
    _instances: "weakref.WeakSet[DevicePagePool]" = weakref.WeakSet()

    def __init__(self, num_layers: int, page_tokens: int, hkv: int, hd: int,
                 init_slots: int = 8, dtype=jnp.float32, plan=None):
        self.num_layers = num_layers
        self.t, self.hkv, self.hd = page_tokens, hkv, hd
        self.dtype = dtype
        # mesh-aware slot space (`serve.sharding.ServePlan`): the global
        # capacity axis splits into `dp` contiguous per-shard ranges —
        # shard s owns global slots [s * lc, (s+1) * lc) — and the kv-head
        # axis splits over the mesh's model axis. `init_slots` is the
        # PER-SHARD requirement (== total for the 1-shard pool).
        self.plan = plan
        self.shards = plan.dp if plan is not None else 1
        # a kv-head count the model axis cannot divide (e.g. hkv=1 MQA on
        # tp=2) replicates the head axis instead — each model shard holds
        # the full kv heads and attends them against its local q heads
        rep_heads = plan is not None and hkv > 0 and hkv % plan.tp != 0
        self.capacity_local = 1
        while self.capacity_local < max(8, init_slots):
            self.capacity_local *= 2
        self.capacity = self.shards * self.capacity_local
        ll, c, t = num_layers, self.capacity, page_tokens
        self._shardings = plan.pool_shardings(replicate_heads=rep_heads) \
            if plan is not None else None
        self.arrays = (
            jnp.zeros((ll, c, t, hkv, hd), dtype),      # k_pages (fast float)
            jnp.zeros((ll, c, t, hkv, hd), dtype),      # v_pages
            jnp.zeros((ll, c, t, hkv, hd), jnp.int8),   # k_quant (slow int8)
            jnp.zeros((ll, c, t, hkv, hd), jnp.int8),   # v_quant
            jnp.zeros((ll, c, t, hkv), dtype),          # k_scale
            jnp.zeros((ll, c, t, hkv), dtype),          # v_scale
        )
        if self._shardings is not None:
            self.arrays = tuple(jax.device_put(a, s) for a, s in
                                zip(self.arrays, self._shardings))
        # per-shard free lists of GLOBAL slot ids; pop() -> lowest first
        lc = self.capacity_local
        self._free = [list(range((s + 1) * lc - 1, s * lc - 1, -1))
                      for s in range(self.shards)]
        # group key pid -> slot; a prefix-shared page can occupy one slot
        # PER data shard (each shard's rows attend their own copy), so a
        # multi-shard pool keys by (shard, pid) while the 1-shard pool
        # keeps the plain pid keys its tests and callers know
        self.slot_of: dict = {}
        # same keying, per page of every layer -> (version, slot) written
        self._synced: dict = {}
        self.stale: set[int] = set()    # pids whose version changed (pool)
        self._dirty: set[int] = set()               # slots ever written
        self.writes = 0     # device scatter calls (bench/test instrumentation)
        self.reads = 0      # device->host pulls (fill readbacks)
        DevicePagePool._instances.add(self)

    def _key(self, pid: int, shard: int):
        return pid if self.shards == 1 else (shard, pid)

    def slot(self, pid: int, shard: int = 0) -> int:
        """Global slot id of page-group `pid` on `shard`."""
        return self.slot_of[self._key(pid, shard)]

    def local_slot(self, slot: int) -> int:
        """Shard-local slot id — what page tables carry under shard_map,
        where each shard sees only its own capacity_local slot rows."""
        return slot % self.capacity_local

    def shard_of_slot(self, slot: int) -> int:
        return slot // self.capacity_local

    # -- slots ---------------------------------------------------------------
    def _grow(self):
        old = self.capacity
        self.capacity *= 2
        self.capacity_local = self.capacity
        pad = [(0, 0), (0, old)] + [(0, 0)] * 3
        self.arrays = tuple(jnp.pad(a, pad[:a.ndim]) for a in self.arrays)
        if self._shardings is not None:     # tp-only plan: re-pin the layout
            self.arrays = tuple(jax.device_put(a, s) for a, s in
                                zip(self.arrays, self._shardings))
        self._free[0].extend(range(self.capacity - 1, old - 1, -1))

    def alloc(self, shard: int = 0) -> int:
        if not self._free[shard]:
            if self.shards > 1:
                # growth would re-partition the global slot axis and strand
                # every shard's existing slot ids — sharded pools are sized
                # up front (PagedKVState passes the per-shard worst case)
                raise RuntimeError(
                    f"data shard {shard} exhausted its {self.capacity_local}"
                    f" device slots — size init_slots to the per-shard "
                    f"worst case (sharded pools cannot grow)")
            self._grow()
        return self._free[shard].pop()

    def release_slot(self, slot: int):
        self._free[self.shard_of_slot(slot)].append(slot)

    def release_pid(self, pid: int):
        """Forget a destroyed pool page. Only the group-key (layer-0) pid
        owns the slot; other layers' pids just drop their sync record."""
        for shard in range(self.shards):
            key = self._key(pid, shard)
            self._synced.pop(key, None)
            slot = self.slot_of.pop(key, None)
            if slot is not None:
                self._free[self.shard_of_slot(slot)].append(slot)

    def adopt(self, group, slot: int, pool, shard: int = 0):
        """Hand an already-written tail slot to a page group that just
        filled. Per layer: a fast placement's device cell already holds
        the full float rows, so it is marked synced; a slow placement
        stays dirty and the next sync rewrites the cell in place (int8 +
        zeroed float). A group already mapped (the fill's hashed `put`
        deduped onto an existing page — chunked prefill rebuilding a
        cached prompt page) keeps its synced slot and the incoming tail
        slot is recycled instead of leaking."""
        pool.watch(self)
        key = self._key(group[0], shard)
        prev = self.slot_of.get(key)
        if prev is not None and prev != slot:
            self.release_slot(slot)
            return
        self.slot_of[key] = slot
        for pid in group:
            page = pool.pages[pid]
            if page.tier == "fast":
                self._synced[self._key(pid, shard)] = (page.version, slot)

    # -- content writes ------------------------------------------------------
    def zero_slot(self, slot: int):
        """Full clear of a slot across every layer before streaming tail
        rows into it (stale other-tier content from a previous occupant
        would otherwise alias into the dequant sum). Slots never written
        since allocation are already zero — skipped."""
        if slot not in self._dirty:
            return
        ll = self.num_layers
        idx = np.arange(ll, dtype=np.int32) * self.capacity + slot
        z = np.zeros((ll, self.t, self.hkv, self.hd), np.float32)
        self.arrays = _jit_write_fast(self._shardings)(*self.arrays,
                                                       idx, z, z)
        self._dirty.discard(slot)
        self.writes += 1

    def write_rows(self, layer: int, slots: np.ndarray, rows: np.ndarray,
                   k_rows, v_rows):
        """Batched decode-token append at one layer: one scatter for the
        whole active batch (fixed shapes — dead rows target a trash slot
        so the compiled scatter never changes shape). Used by the eager
        reference path and prefill-tail writes; the fused step performs
        the same scatter inside its own jitted graph."""
        sh = None if self._shardings is None else self._shardings[:2]
        kf, vf = _jit_write_rows(sh)(self.arrays[0], self.arrays[1],
                                     jnp.int32(layer),
                                     jnp.asarray(slots), jnp.asarray(rows),
                                     jnp.asarray(k_rows, self.arrays[0].dtype),
                                     jnp.asarray(v_rows, self.arrays[0].dtype))
        self.arrays = (kf, vf) + self.arrays[2:]
        self._dirty.update(int(s) for s in slots)
        self.writes += 1

    def read_slot(self, slot: int):
        """Pull one slot's float rows for every layer back to the host —
        (num_layers, t, hkv, hd) each for K and V. Used once per *filled*
        page (not per step) by the fused path to hand the page contents to
        the host pool; 2 device->host transfers."""
        self.reads += 2
        return (np.asarray(self.arrays[0][:, slot]),
                np.asarray(self.arrays[1][:, slot]))

    def check_invariants(self) -> None:
        """Structural self-check (satellite: every serve-suite teardown):
        free lists hold unique in-range slots from their own shard's range
        and are disjoint from every mapped slot; no two group keys share a
        slot. Raises AssertionError on the first breach."""
        used: dict[int, object] = {}
        for key, slot in self.slot_of.items():
            assert 0 <= slot < self.capacity, \
                f"slot_of[{key}] = {slot} outside capacity {self.capacity}"
            assert slot not in used, \
                f"slot {slot} mapped by both {used[slot]} and {key}"
            used[slot] = key
        for shard, free in enumerate(self._free):
            uniq = set(free)
            assert len(uniq) == len(free), \
                f"shard {shard} free list holds duplicate slots"
            for slot in uniq:
                assert 0 <= slot < self.capacity, \
                    f"shard {shard} freed out-of-range slot {slot}"
                assert self.shard_of_slot(slot) == shard, \
                    f"slot {slot} on shard {shard}'s free list belongs to " \
                    f"shard {self.shard_of_slot(slot)}"
                assert slot not in used, \
                    f"slot {slot} is both free and mapped by {used[slot]}"

    # -- sync ----------------------------------------------------------------
    def sync(self, pool, groups, shards=None):
        """Bring the mirror current: allocate a slot for each page group
        (a tuple of per-layer pids) new to the mirror and write its cells,
        and rewrite the cells of every mapped page whose version changed
        since it was written (the pool's ``stale`` reports: demotions,
        swaps). Batched into at most one fast + one slow scatter.
        `shards` (aligned with `groups`, default all 0) pins each group to
        the data shard whose rows attend it — the slot comes from that
        shard's range and the sync record is keyed per shard."""
        pool.watch(self)
        groups = list(groups)
        if shards is None:
            shards = [0] * len(groups)
        # allocate every slot FIRST: alloc() may _grow() (capacity doubles),
        # and the flattened (layer * capacity + slot) scatter indices must
        # be computed against the final capacity or every layer > 0 write
        # would land in the wrong cell of the grown arrays
        fresh = []
        seen = set()
        for group, shard in zip(groups, shards):
            key = self._key(group[0], shard)
            if key in seen:
                continue
            seen.add(key)
            fresh.append((group, shard))
            if key not in self.slot_of:
                self.slot_of[key] = self.alloc(shard)
        cells = [(layer, pid, shard, self.slot_of[self._key(group[0], shard)])
                 for group, shard in fresh
                 for layer, pid in enumerate(group)]
        for pid in self.stale:
            page = pool.pages.get(pid)
            if page is None or page.tier == "host":
                continue    # destroyed, or parked: swap-in bumps it again
            for shard in range(self.shards):
                rec = self._synced.get(self._key(pid, shard))
                if rec is not None:
                    cells.append((page.layer, pid, shard, rec[1]))
        self.stale.clear()
        if not cells:
            return
        fast_w, slow_w = [], []
        c = self.capacity
        for layer, pid, shard, slot in cells:
            page = pool.pages[pid]
            key = self._key(pid, shard)
            if self._synced.get(key) == (page.version, slot):
                continue
            if page.tier == "host":
                raise RuntimeError(
                    f"sync asked to mirror parked (host-tier) page {pid}"
                    " — swap the sequence in before scheduling it")
            idx = layer * c + slot
            if page.tier == "fast":
                k, v = page.data
                fast_w.append((idx, k, v))
            else:
                (kq, ks), (vq, vs) = page.data
                slow_w.append((idx, kq, ks[..., 0], vq, vs[..., 0]))
            self._synced[key] = (page.version, slot)
        if fast_w:
            idx = np.array([w[0] for w in fast_w], np.int32)
            k = np.stack([w[1] for w in fast_w]).astype(np.float32)
            v = np.stack([w[2] for w in fast_w]).astype(np.float32)
            idx, k, v = _pad_pow2(idx, k, v)
            self.arrays = _jit_write_fast(self._shardings)(*self.arrays,
                                                           idx, k, v)
            self._dirty.update(int(i) % c for i in idx)
            self.writes += 1
        if slow_w:
            idx = np.array([w[0] for w in slow_w], np.int32)
            stacks = [np.stack([w[i] for w in slow_w]) for i in range(1, 5)]
            idx, kq, ks, vq, vs = _pad_pow2(idx, *stacks)
            self.arrays = _jit_write_slow(self._shardings)(
                *self.arrays, idx, kq, ks.astype(np.float32), vq,
                vs.astype(np.float32))
            self._dirty.update(int(i) % c for i in idx)
            self.writes += 1
