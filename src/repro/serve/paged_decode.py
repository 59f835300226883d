"""Paged decode: page-pool KV state + the fused jitted decode step.

This is where the thesis' two threads meet in the serving hot path: the
KV cache lives in a tiered `PagedKVPool` (Sibyl's substrate — placement
policy decides fast float vs. slow int8 per page), and the attention over
it runs through ``api.run("paged_attention", ..., backend="auto")``, i.e.
the NERO knee-point autotuner picks the page/head blocking from the
kernel spec's cost model.

Three decode modes over one `PagedKVState`:

``fused``  (default) The whole per-token step — embed -> layer stack
           (lax.scan over stacked group params, paged-attention kernel
           inside, the step's K/V rows appended by donated in-place
           scatters) -> final norm -> lm_head -> sample — is ONE jitted
           graph over the layer-stacked device pool
           (`serve.device_pool.DevicePagePool`). The host's job per token
           shrinks to pure bookkeeping: build the page table + tail
           indices before the step, bump tail counters (and hand filled
           pages to the pool) after. Steady state crosses the
           host/device boundary twice per token — one int32 control
           upload, one sampled-token download — independent of
           num_layers.

``eager``  The pre-fusion reference: a python loop over layers, each
           pulling its K/V rows to host numpy, scattering them back, and
           dispatching the kernel per layer (~2 transfers per layer per
           token). Same stacked device pool, same kernel — the fused path
           is tested token-for-token against this one.

``numpy``  No device pool: pool-shaped arrays are assembled in host
           numpy each step (padded to stable shapes so the jitted kernel
           recompiles only when the pool grows). Portability fallback and
           the data-movement baseline in `bench_serve`.

Page lifecycle (see serve/README.md):
  prefill  -> full pages ``put`` per (sequence, layer), remainder rows
              streamed into a layer-uniform tail slot
  decode   -> each step appends the token's K/V rows (one per layer) to
              the tail slot; a filled tail becomes a pool ``put`` per
              layer (tier decided there), the slot adopted in place
  attend   -> one page table per step serves every layer (slots are
              layer-uniform); the kernel selects the layer from the
              stacked pool via a scalar-prefetched index
  retire   -> ``free_seq`` releases the request's pool pages (ref-
              counted; prefix-shared pages survive) and device slots
"""
from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN, LOCAL_ATTN, MIXER_NONE, MLP_DENSE,
                                MLP_MOE, MLP_NONE, RGLRU, SSD)
from repro.kernels import api
from repro.kernels.paged_attention.paged_attention import live_pages
from repro.models.attention import decode_qkv
from repro.models.layers import lm_head_apply, rms_norm
from repro.models.transformer import mlp_tail
from repro.serve import tracing
from repro.serve.device_pool import DevicePagePool
from repro.serve.kvcache import PagedKVPool
from repro.serve.paged_state import (RecurrentStore, StateLayout,
                                     gather_ring_kv, rec_array_names,
                                     rec_advance, rec_array_specs, rec_read,
                                     rec_scan_tokens,
                                     ring_attend, select_checkpoint,
                                     supports_paged_layout)

MODES = ("fused", "eager", "numpy")


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class _TableRow:
    """One sequence's page-table row, kept current by the events that
    change its pages (prefill, fills, ring drops) rather than rebuilt
    from the pool every step. ``pids[:n]`` are its layer-uniform page
    groups in page order (row-major: the flat pid order a step touches);
    ``slots[:synced]`` the shard-local device slot of each group the
    mirror has synced — groups past ``synced`` get theirs at the next
    `begin_step`'s sync."""

    __slots__ = ("pids", "slots", "n", "synced")

    def __init__(self, groups: np.ndarray, capacity: int):
        # room for the whole page table: a step's overflow check leaves
        # at least one tail slot free, and a step fills at most one page
        n, layers = groups.shape
        self.pids = np.zeros((max(n, capacity), layers), np.int64)
        self.pids[:n] = groups
        self.slots = np.zeros(len(self.pids), np.int32)
        self.n = n
        self.synced = 0

    def append(self, group) -> None:
        self.pids[self.n] = group
        self.n += 1

    def drop_front(self, d: int) -> None:
        n = self.n
        self.pids[:n - d] = self.pids[d:n]
        self.slots[:n - d] = self.slots[d:n]
        self.n -= d
        self.synced = max(0, self.synced - d)


class PagedKVState:
    """Pool-backed KV state for a decode batch.

    The pool holds full pages; a per-sequence *tail slot* in the
    layer-stacked device pool holds the < page_tokens newest rows of every
    layer until they fill a page (``numpy`` mode buffers the rows on the
    host instead). Tail fill level is layer-uniform — every decode token
    appends exactly one row at every layer — so one counter per sequence
    and one page table per step describe the whole stack.

    Batch rows may carry ``seq_id = -1`` (continuous batching pads retired
    rows): they write to a scratch slot and attend a zero page.

    ``h2d`` / ``d2h`` count the explicit host->device / device->host
    tensor transfers this state (and its device pool) performs on the
    decode path — the quantity the fused step minimizes and
    `bench_serve` / the transfer-count tests report.

    Each live sequence's page-table row (`_TableRow`) is built from the
    pool once — at its first step, after a prefill write or a prefix
    adoption, and after a swap-in — and from then on changed in place
    by the page events: a filled tail appends a group, a ring drop
    removes front groups. A step copies the rows into its control block.
    """

    # every live state, for test-teardown invariant sweeps (conftest)
    _instances: "weakref.WeakSet[PagedKVState]" = weakref.WeakSet()

    def __init__(self, pool: PagedKVPool, capacity: int, num_layers: int,
                 hkv: int, hd: int, mode: str = "fused",
                 batch_hint: int = 1, tail_slots: int = 1, plan=None,
                 layout: StateLayout | None = None):
        if mode not in MODES:
            raise ValueError(f"mode {mode!r} not in {MODES}")
        if tail_slots not in (1, 2):
            raise ValueError(f"tail_slots must be 1 or 2, got {tail_slots}")
        if plan is not None and mode != "fused":
            raise ValueError(f"mesh-sharded serving requires the fused "
                             f"decode mode, got {mode!r}")
        # heterogeneous stacks (recurrent / ring layers) route through the
        # paged-state layout: the pool's layer axis holds only KV-bearing
        # layers, recurrent state lives in a RecurrentStore, ring layers
        # bound their page-table need at O(window)
        self.layout = layout
        if layout is not None:
            num_layers = layout.n_kv
            if (layout.has_rec or layout.has_ring) and mode != "fused":
                raise NotImplementedError(
                    f"recurrent/ring paged state is fused-only, got "
                    f"mode {mode!r}")
        self.pool = pool
        self.num_layers = num_layers
        self.hkv, self.hd = hkv, hd
        t = pool.page_tokens
        slots = -(-capacity // t)          # ceil: pages covering capacity
        if layout is not None and layout.has_ring:
            slots = min(slots, layout.ring_pages())
        # + tail page(s) (2 for speculative steps, whose k rows may cross
        # one page boundary into a spill slot), rounded to a mult. of 8
        self.slots = -(-(slots + tail_slots) // 8) * 8
        self.mode = mode
        self.plan = plan
        self.batch_hint = max(1, batch_hint)   # expected live sequences
        self.tail_len: dict[int, int] = {}     # seq -> tail rows (all layers)
        self.tail_data: dict[tuple, list] = {}  # (seq, layer) -> rows (numpy)
        # chunked prefill: content hashes awaiting the seq's next page
        # fills, so prompt pages built by chunk scatters dedup/share and
        # are insertable into the radix prefix tree
        self._pending_hashes: dict[int, list] = {}
        self._tail_slot: dict[int, int] = {}   # seq -> GLOBAL device slot
        self._spill_slot: dict[int, int] = {}  # k>1: boundary-crossing rows
        self._shard_of: dict[int, int] = {}    # seq -> data shard
        # preempted sequences: seq -> host copy of its partial tail rows
        # (num_layers, tail_len, hkv, hd) K/V, or None when the tail was
        # empty at swap-out (numpy mode keeps tails host-side already)
        self._parked_tail: dict[int, object] = {}
        self._rec: RecurrentStore | None = None
        self._rec_slot: dict[int, int] = {}    # seq -> GLOBAL rec slot
        self._parked_rec: dict[int, dict] = {}  # seq -> parked state blocks
        self._ring_base: dict[int, int] = {}   # seq -> dropped ring pages
        self._table: dict[int, _TableRow] = {}  # seq -> page-table row
        self._device: DevicePagePool | None = None
        self._trash = 0
        if mode != "numpy":
            shards = plan.dp if plan is not None else 1
            # init_slots is the PER-SHARD worst case: each shard carries
            # its block of decode rows (batch_hint / dp of them)
            rows_per_shard = -(-self.batch_hint // shards)
            self._device = DevicePagePool(
                num_layers, t, hkv, hd,
                init_slots=self.slots * rows_per_shard, plan=plan)
            self._trash = [self._device.alloc(s) for s in range(shards)]
            if layout is not None and layout.has_rec:
                self._rec = RecurrentStore(
                    layout, batch_hint=self.batch_hint, plan=plan,
                    compute_dtype=jnp.dtype(layout.cfg.compute_dtype))
        self._step = None         # per-step view (begin_step .. end_step)
        self.gather_s = 0.0       # host-side bookkeeping time (Sibyl reward)
        self.h2d = 0              # control/token uploads owned by the state
        self.d2h = 0
        PagedKVState._instances.add(self)

    # -- data-shard binding --------------------------------------------------
    def bind_seq(self, seq: int, shard: int):
        """Pin a sequence to a data shard BEFORE its prefill pages are
        written: all of its device slots (pages, tail, spill) come from
        that shard's slot range, so its decode row attends purely local
        pages. A no-op binding conflict is an error."""
        prev = self._shard_of.setdefault(seq, shard)
        if prev != shard:
            raise RuntimeError(f"sequence {seq} already bound to data "
                               f"shard {prev}, cannot rebind to {shard}")

    def shard_of(self, seq: int) -> int:
        shard = self._shard_of.get(seq, 0)
        if (self._device is not None and self._device.shards > 1
                and seq not in self._shard_of):
            raise RuntimeError(f"sequence {seq} not bound to a data shard "
                               f"— call bind_seq before prefill writes")
        return shard

    @property
    def device_arrays(self):
        """The fused step's donated array tuple: the six layer-stacked KV
        pool arrays, then the recurrent store arrays (if any)."""
        kv = self._device.arrays
        return kv + self._rec.arrays if self._rec is not None else kv

    def adopt_device_arrays(self, arrays):
        """Take ownership of the pool arrays returned by a fused step (the
        previous ones were donated into the jit and must not be reused)."""
        arrays = tuple(arrays)
        self._device.arrays = arrays[:6]
        if self._rec is not None:
            self._rec.arrays = arrays[6:]

    def transfer_counts(self) -> tuple[int, int]:
        """(host->device, device->host) explicit transfers so far,
        including the device pool's scatter payload uploads and fill
        readbacks."""
        dev = self._device
        h2d = self.h2d + (dev.writes if dev is not None else 0)
        d2h = self.d2h + (dev.reads if dev is not None else 0)
        if self._rec is not None:
            h2d += self._rec.writes
            d2h += self._rec.reads
        return h2d, d2h

    # -- writes -------------------------------------------------------------
    def write_prefill(self, layer: int, seq: int, k: np.ndarray,
                      v: np.ndarray, page_hashes=None, skip_pages: int = 0):
        """k, v: (prefill_len, hkv, hd) — full pages into the pool, the
        remainder rows into the sequence's tail slot. `page_hashes[p]`
        (cumulative token-prefix digests) enables ref-counted page sharing
        across requests with identical prompt prefixes. ``skip_pages``
        full pages at the front are assumed already present (adopted from
        the radix prefix index) and are not re-put; the tail-row math is
        unchanged."""
        self._table.pop(seq, None)      # rebuilt from the pool at its step
        t = self.pool.page_tokens
        n_full = k.shape[0] // t
        for p in range(n_full):
            if p < skip_pages:
                continue
            h = page_hashes[p] if page_hashes is not None else None
            self.pool.put(seq, k[p * t:(p + 1) * t], v[p * t:(p + 1) * t],
                          layer=layer, content_hash=h)
        n_rest = k.shape[0] - n_full * t
        prev = self.tail_len.setdefault(seq, n_rest)
        if prev != n_rest:
            raise ValueError(
                f"sequence {seq}: layer {layer} prefilled {n_rest} tail "
                f"rows where earlier layers prefilled {prev} — the paged "
                f"layout requires layer-uniform prefill lengths")
        if not n_rest:
            return
        rest_k, rest_v = k[n_full * t:], v[n_full * t:]
        if self._device is not None:
            slot = self._ensure_tail_slot(seq)
            slots = np.full(n_rest, slot, np.int32)
            rows = np.arange(n_rest, dtype=np.int32)
            self._device.write_rows(layer, slots, rows, rest_k, rest_v)
        else:
            self.tail_data[(seq, layer)] = \
                [(rest_k[r], rest_v[r]) for r in range(n_rest)]

    def adopt_prefix(self, seq: int, groups, pending_hashes=()):
        """Start a sequence from cached pages instead of a prefill:
        each group (per-layer pool pids of one prompt page, from the
        radix prefix index) is adopted by reference — the pool stores
        nothing new, the device mirror already holds (or will sync) the
        slots — and ``pending_hashes`` (the cumulative digests of the
        prompt pages the suffix chunks will fill) are queued so
        `end_step`'s fills store them hash-shared. Must run BEFORE any
        suffix write; the tail starts empty."""
        prev = self.tail_len.setdefault(seq, 0)
        if prev != 0 or self.pool.seq_pages(seq, 0):
            raise RuntimeError(f"sequence {seq}: adopt_prefix must run "
                               f"before any prefill write")
        self._table.pop(seq, None)
        for group in groups:
            for layer, pid in enumerate(group):
                self.pool.adopt_page(seq, pid, layer)
        if pending_hashes:
            self._pending_hashes[seq] = list(pending_hashes)

    def _ensure_tail_slot(self, seq: int) -> int:
        slot = self._tail_slot.get(seq)
        if slot is None:
            slot = self._device.alloc(self.shard_of(seq))
            self._device.zero_slot(slot)
            self._tail_slot[seq] = slot
        return slot

    def _ensure_spill_slot(self, seq: int) -> int:
        """Second tail slot for speculative (k > 1) steps: rows past the
        page boundary scatter here; it is promoted to the tail slot when
        the accepted tokens actually fill the page."""
        slot = self._spill_slot.get(seq)
        if slot is None:
            slot = self._device.alloc(self.shard_of(seq))
            self._device.zero_slot(slot)
            self._spill_slot[seq] = slot
        return slot

    def write_prefill_rec(self, seq: int, blocks: dict):
        """Install post-prefill recurrent state for `seq`: ``blocks`` maps
        every store array name to (n_layers_of_kind, ...) host blocks
        (a dense prefill's state, or a swap-in's, bit-identical)."""
        slot = self._rec_slot.get(seq)
        if slot is None:
            slot = self._rec_slot[seq] = self._rec.alloc(self.shard_of(seq))
        self._rec.write_slot(slot, blocks)

    def _release_rec_slot(self, seq: int):
        slot = self._rec_slot.pop(seq, None)
        if slot is not None:
            with tracing.span("serve.rec_store"):
                self._rec.release_slot(slot)

    # -- per-step protocol ---------------------------------------------------
    def _build_row(self, seq: int) -> _TableRow:
        """`seq`'s page-table row built from the pool: the per-layer pool
        pids of each logical page, zipped into layer-uniform groups."""
        per_layer = [self.pool.seq_pages(seq, l)
                     for l in range(self.num_layers)]
        n = len(per_layer[0])
        if any(len(p) != n for p in per_layer):
            raise RuntimeError(
                f"sequence {seq}: ragged page counts across layers "
                f"({[len(p) for p in per_layer]}) — paged decode requires "
                f"layer-uniform page structure")
        groups = np.array(per_layer, np.int64).T.reshape(n,
                                                         self.num_layers)
        return _TableRow(groups, self.slots)

    def check_invariants(self) -> None:
        """Structural self-check (debug mode and every test teardown):
        each cached page-table row equals the one built from the pool,
        and each synced group's slot is the one the mirror maps."""
        dev = self._device
        for seq, row in self._table.items():
            want = self._build_row(seq)
            assert row.n == want.n and np.array_equal(
                row.pids[:row.n], want.pids[:want.n]), \
                (f"sequence {seq}: cached page groups "
                 f"{row.pids[:row.n].tolist()} != the pool's "
                 f"{want.pids[:want.n].tolist()}")
            if dev is None:
                continue
            shard = self._shard_of.get(seq, 0)
            for j in range(row.synced):
                slot = dev.local_slot(dev.slot(int(row.pids[j, 0]), shard))
                assert row.slots[j] == slot, \
                    (f"sequence {seq}: cached slot {row.slots[j]} of page "
                     f"{j} != the mirror's {slot}")

    def begin_step(self, seq_ids, positions, k: int = 1,
                   tokens=None, keep_fixed=None, keep_cap=None) -> np.ndarray:
        """Host bookkeeping before one decode step: touch each live page
        once (one pool-clock tick for the whole step), sync the device
        mirror (page groups new since the row's last step, demotion
        rewrites), and copy each row's cached page-table row into the
        layer-uniform control block the fused step consumes. The span
        ``serve.begin_step`` counts the live ``rows`` and those
        ``rebuilt`` from the pool this step (admission, swap-in), the
        pages one attention layer's kernel call copies (``streamed``,
        `live_pages` summed over the batch rows, dead rows included; 0
        for a stack with no KV pages) and the batch's table slots
        (``table``: rows x slots). A stack
        with recurrent layers gives each row its state slot inside the
        child span ``serve.rec_store``, whose count ``fresh`` is the rows
        whose slot was allocated this step: the step reads zeros for them
        (the control block's ``fresh`` column), so nothing is uploaded.

        ``k == 1`` (plain decode): ``(b, slots + 4)`` int32 rows
        ``[page table | tail slot | tail row | position | kv length]``,
        where the length already counts the token this step appends.

        ``k > 1`` (speculative verify): ``(b, slots + 5 + k)`` rows
        ``[page table | tail slot | spill slot | tail row | position |
        kv length | k input tokens]`` — the spill slot receives scattered
        rows that cross the page boundary, ``position``/``length`` are row
        0's (later rows shift by +1 each inside the graph), and the input
        tokens (last accepted + k-1 drafts, from ``tokens``) ride in the
        control block so the whole verify step still costs ONE upload.

        Dead rows (seq -1) get the scratch slot and length 1."""
        with tracing.span("serve.begin_step") as sp:
            t = self.pool.page_tokens
            b = len(seq_ids)
            if k > 1 and self._device is None:
                raise RuntimeError("speculative (k > 1) steps scatter "
                                   "inside the fused graph — they need a "
                                   "device pool")
            if k > t:
                raise ValueError(
                    f"k={k} tokens per step exceed page_tokens={t}: one step "
                    f"may spill across at most one page boundary")
            positions = np.broadcast_to(np.asarray(positions, np.int32), (b,))
            s = self.slots
            lay = self.layout
            if lay is not None:
                cc = lay.cols(s, k)
                width = cc.width
                c_tail, c_row, c_pos, c_len = cc.tail, cc.row, cc.pos, cc.len
            else:
                cc = None
                width = s + 4 if k == 1 else s + 5 + k
                # column offsets past the page table (k=1 keeps the
                # plain-decode layout)
                c_tail, c_row, c_pos, c_len = (s, s + 1, s + 2, s + 3) \
                    if k == 1 else (s, s + 2, s + 3, s + 4)
            dev = self._device
            shards = dev.shards if dev is not None else 1
            if shards > 1 and b % shards:
                raise ValueError(f"decode batch of {b} rows does not split "
                                 f"over {shards} data shards — pad with -1 "
                                 f"rows (ServePlan.pad_rows)")
            # under shard_map every control value is shard-LOCAL: shard s sees
            # only its block of rows and its capacity_local slot rows
            row_shard = [i * shards // b for i in range(b)] if b else []
            control = np.zeros((b, width), np.int32)
            if dev is not None:
                trash = np.array([dev.local_slot(self._trash[sh])
                                  for sh in row_shard], np.int32)
                control[:, c_tail] = trash
            control[:, c_len] = 1
            if self._rec is not None:
                # dead rows read/write the recurrent trash slot, and keep
                # exactly 1 phantom token (keep_fixed 1) so their garbage
                # never escapes the trash row
                control[:, cc.rec] = [self._rec.local_slot(self._rec.trash[sh])
                                      for sh in row_shard]
                if k > 1:
                    control[:, cc.keep_fixed] = 1
                    control[:, cc.keep_cap] = 0
            if k > 1:
                control[:, s + 1] = control[:, c_tail]            # spill slot
                if tokens is not None:
                    control[:, s + 5:s + 5 + k] = np.asarray(tokens, np.int32)
            tail_slots = 1 if k == 1 else 2
            live, rebuilt = [], 0       # (batch row, seq, table row)
            for i, seq in enumerate(seq_ids):
                if seq < 0:
                    continue
                if shards > 1:
                    self.bind_seq(seq, row_shard[i])
                row = None
                if self.num_layers:     # pure-recurrent stacks: no pages
                    row = self._table.get(seq)
                    if row is None:
                        row = self._table[seq] = self._build_row(seq)
                        rebuilt += 1
                    if row.n + tail_slots > self.slots:
                        raise ValueError(
                            f"sequence {seq}: {row.n} pages + {tail_slots} "
                            f"tail page(s) exceed the page-table capacity "
                            f"of {self.slots} slots ({self.slots * t} "
                            f"tokens); size the PagedKVState capacity to "
                            f"the longest request")
                live.append((i, seq, row))
            rows = [row for _, _, row in live if row is not None]
            self.pool.touch_many(np.concatenate(
                [row.pids[:row.n].ravel() for row in rows]) if rows else ())
            if dev is not None:
                # only groups new since the row's last step (and pages the
                # pool reports changed) reach the mirror
                new = [(i, row) for i, _, row in live
                       if row is not None and row.synced < row.n]
                dev.sync(self.pool,
                         [tuple(g) for _, row in new
                          for g in row.pids[row.synced:row.n].tolist()],
                         [row_shard[i] for i, row in new
                          for _ in range(row.synced, row.n)])
                for i, row in new:
                    for j in range(row.synced, row.n):
                        row.slots[j] = dev.local_slot(
                            dev.slot(int(row.pids[j, 0]), row_shard[i]))
                    row.synced = row.n
            if self._rec is not None:
                # a row's first step: its slot is allocated here and the
                # step reads zeros for it (``fresh``) — nothing is uploaded
                with tracing.span("serve.rec_store") as rs:
                    fresh = 0
                    for i, seq, _row in live:
                        slot = self._rec_slot.get(seq)
                        if slot is None:
                            slot = self._rec_slot[seq] = \
                                self._rec.alloc(self.shard_of(seq))
                            control[i, cc.fresh] = 1
                            fresh += 1
                        control[i, cc.rec] = self._rec.local_slot(slot)
                    rs.set(fresh=fresh)
            for i, seq, row in live:
                n = row.n if row is not None else 0
                tail = self.tail_len.get(seq, 0)
                if dev is not None and self.num_layers:
                    control[i, :n] = row.slots[:n]
                    control[i, c_tail] = \
                        dev.local_slot(self._ensure_tail_slot(seq))
                    control[i, n] = control[i, c_tail]
                    if k > 1:
                        control[i, s + 1] = \
                            dev.local_slot(self._ensure_spill_slot(seq))
                        control[i, n + 1] = control[i, s + 1]
                if self._rec is not None and k > 1:
                    control[i, cc.keep_fixed] = \
                        -1 if keep_fixed is None else int(keep_fixed[i])
                    control[i, cc.keep_cap] = \
                        k - 1 if keep_cap is None else int(keep_cap[i])
                if cc is not None and lay.has_ring:
                    control[i, cc.base] = self._ring_base.get(seq, 0)
                control[i, c_row] = tail
                control[i, c_pos] = positions[i]
                control[i, c_len] = n * t + tail + 1
            # what one attention layer's kernel call copies (a stack with
            # no KV pages calls none), against the batch's table slots
            streamed = live_pages(control[:, c_len], k, t, s).sum() \
                if self.num_layers else 0
            sp.set(rows=len(live), rebuilt=rebuilt, streamed=int(streamed),
                   table=b * s)
            self._step = {"seq_ids": list(seq_ids), "control": control,
                          "table": None, "lengths": None}
        self.gather_s += sp.elapsed
        return control

    def _step_view(self):
        if self._step is None:
            raise RuntimeError("decode step used outside "
                               "begin_step()/end_step()")
        return self._step

    def run_fused(self, step_fn, params, tokens, seq_ids, positions, key):
        """Drive one fused step (`build_fused_step`) with the exact
        steady-state transfer protocol — THE single place that owns the
        fused step's host/device accounting: begin_step bookkeeping, one
        control upload, donated pool arrays through the jit, one
        sampled-token download, end_step bookkeeping. `tokens` may be the
        previous step's device array (no upload — the steady state) or
        host values (one extra upload: the first step, or a continuous
        admission). Returns ``(host_tokens, device_tokens)``."""
        control = self.begin_step(seq_ids, positions)
        with tracing.span("serve.dispatch"):
            # one logical upload either way; a mesh plan pins the layout so
            # the jit ingests each shard's rows without a gather-and-reshard
            cdev = self._upload_control(control)
            if not isinstance(tokens, jax.Array):
                tokens = np.asarray(tokens, np.int32)
                tokens = jnp.asarray(tokens) if self.plan is None else \
                    jax.device_put(tokens, self.plan.token_sharding())
                self.h2d += 1
            tok_dev, arrays = step_fn(params, self.device_arrays, tokens,
                                      cdev, key)
            self.adopt_device_arrays(arrays)
        with tracing.span("serve.device_wait"):
            tok_host = np.asarray(tok_dev)
        self.d2h += 1
        self.end_step(seq_ids)
        return tok_host, tok_dev

    def run_spec(self, step_fn, params, tokens_k, seq_ids, positions, key,
                 keep_fixed=None, keep_cap=None):
        """Drive one speculative verify step (`build_fused_step(k=...)`)
        with the steady-state transfer protocol: begin_step bookkeeping,
        ONE control upload (page table + tail/spill slots + the k input
        tokens), donated pool arrays through the jit, ONE download of the
        ``(b, k + 1)`` verdict block ``[k sampled tokens | accepted draft
        count]`` — 2 host<->device crossings per *accepted run* of up to k
        tokens. ``tokens_k`` is the (b, k) host matrix [last accepted |
        k-1 drafts]. The step is left OPEN: the caller decides how many
        tokens each row keeps (eos / max_new / per-request k clamping) and
        must call ``end_step(seq_ids, advanced)`` with those counts.

        ``keep_fixed`` / ``keep_cap`` (per-row, recurrent stacks only)
        drive the in-graph state-checkpoint pick: a row with
        ``keep_fixed[i] >= 0`` commits exactly that many tokens of
        recurrent state (chunked prefill rows); ``-1`` rows commit
        ``min(accepted, keep_cap) + 1`` (the verify accept rule)."""
        control = self.begin_step(seq_ids, positions,
                                  k=int(np.asarray(tokens_k).shape[1]),
                                  tokens=tokens_k, keep_fixed=keep_fixed,
                                  keep_cap=keep_cap)
        with tracing.span("serve.dispatch"):
            cdev = self._upload_control(control)
            out_dev, arrays = step_fn(params, self.device_arrays, cdev, key)
            self.adopt_device_arrays(arrays)
        with tracing.span("serve.device_wait"):
            out = np.asarray(out_dev)
        self.d2h += 1
        return out

    def _upload_control(self, control: np.ndarray):
        """The step's one control upload."""
        self.h2d += 1
        if self.plan is not None:
            return jax.device_put(control, self.plan.control_sharding())
        return jnp.asarray(control)

    def append_step_rows(self, layer: int, k_rows: np.ndarray,
                         v_rows: np.ndarray):
        """Eager/numpy modes: append this step's (b, hkv, hd) K/V rows at
        one layer. The fused step performs the equivalent scatter inside
        its own jitted graph instead."""
        st = self._step_view()
        c = st["control"]
        if self._device is not None:
            self._device.write_rows(layer, c[:, self.slots],
                                    c[:, self.slots + 1], k_rows, v_rows)
        else:
            for i, seq in enumerate(st["seq_ids"]):
                if seq >= 0:
                    self.tail_data.setdefault((seq, layer), []) \
                        .append((k_rows[i], v_rows[i]))

    def attend(self, q, layer: int, backend: str = "auto"):
        """q: (b, hq, hd) for the decode token at one layer -> (b, hq, hd)
        over every pooled page + tail row (eager/numpy modes; the fused
        step dispatches the kernel inside its jit)."""
        st = self._step_view()
        if self._device is not None:
            if st["table"] is None:
                c = st["control"]
                st["table"] = jnp.asarray(c[:, :self.slots])
                st["lengths"] = jnp.asarray(c[:, self.slots + 3])
                self.h2d += 2
            return api.run("paged_attention", q, *self._device.arrays,
                           st["table"], st["lengths"],
                           jnp.int32(layer), backend=backend)
        with tracing.span("serve.gather") as sp:
            view = self._gather_numpy(layer, st["seq_ids"])
        self.gather_s += sp.elapsed     # the restack IS the
        self.h2d += len(view)           # Sibyl-visible latency
        return api.run("paged_attention", q,
                       *[jnp.asarray(a) for a in view], backend=backend)

    def end_step(self, seq_ids, advanced=None):
        """Host bookkeeping after one decode step: bump tail counters and
        turn filled tails into pool pages — per layer, tier decided by the
        pool; the device tail slot is adopted in place (its float rows are
        already current; slow placements are rewritten by the next sync).
        The fused path reads a filled page back once (2 transfers per
        page_tokens tokens, amortized); it never touches row data on the
        per-token path.

        ``advanced`` (speculative steps) is the per-sequence count of
        tokens actually KEPT this step — the accepted draft prefix plus
        the bonus token, after the caller's eos/max_new clamping. Rows the
        verify step scattered beyond the kept count are *phantom*: the
        tail counter does not advance over them, the per-row length
        masking keeps them invisible, and the next step's scatters
        overwrite them — so the pool never holds (and never ``put``s)
        phantom tokens. That bookkeeping IS the rollback. When the kept
        tokens cross the page boundary, the spill slot (which already
        holds their scattered rows) is promoted to be the new tail slot.
        Default: 1 token per live row (the plain decode path)."""
        with tracing.span("serve.end_step") as sp:
            t = self.pool.page_tokens
            if advanced is None:
                advanced = [1] * len(seq_ids)
            for seq, adv in zip(seq_ids, advanced):
                if seq < 0 or adv == 0:
                    continue
                if not 0 < adv <= t:
                    raise ValueError(
                        f"sequence {seq}: advanced {adv} tokens in one step "
                        f"(valid: 1..page_tokens={t})")
                if self.num_layers == 0:
                    continue    # pure-recurrent stack: no pages to fill
                n = self.tail_len.get(seq, 0) + adv
                if n < t:
                    self.tail_len[seq] = n
                    if self.layout is not None and self.layout.has_ring:
                        self._drop_ring(seq)
                    continue
                self.tail_len[seq] = n - t
                if self._device is not None:
                    slot = self._tail_slot.pop(seq)
                    k_all, v_all = self._device.read_slot(slot)
                    # a chunked prefill queued this page's cumulative prompt
                    # hash: store it shared (identical content dedups onto a
                    # live/pinned page; `adopt` then recycles the tail slot)
                    pending = self._pending_hashes.get(seq)
                    h = pending.pop(0) if pending else None
                    group = tuple(
                        self.pool.put(seq, k_all[l], v_all[l], layer=l,
                                      content_hash=h)
                        for l in range(self.num_layers))
                    self._device.adopt(group, slot, self.pool,
                                       self._device.shard_of_slot(slot))
                    self._append_group(seq, group)
                    spill = self._spill_slot.pop(seq, None)
                    if spill is not None:
                        # rows past the boundary were scattered here already
                        self._tail_slot[seq] = spill
                    elif n > t:
                        raise RuntimeError(
                            f"sequence {seq}: {n - t} tokens crossed the page "
                            f"boundary without a spill slot — multi-token "
                            f"steps must begin_step with k > 1")
                else:
                    if adv != 1:
                        raise RuntimeError("multi-token steps need the device "
                                           "pool (decode_mode='fused')")
                    group = []
                    for l in range(self.num_layers):
                        rows = self.tail_data.pop((seq, l))
                        group.append(self.pool.put(
                            seq, np.stack([r[0] for r in rows]),
                            np.stack([r[1] for r in rows]), layer=l))
                    self._append_group(seq, group)
                if self.layout is not None and self.layout.has_ring:
                    self._drop_ring(seq)
            self._step = None
        self.gather_s += sp.elapsed

    def _append_group(self, seq: int, group) -> None:
        """A filled tail became page group ``group``: append it to the
        sequence's table row (its slot comes with the next sync)."""
        row = self._table.get(seq)
        if row is not None:
            row.append(group)

    def _drop_ring(self, seq: int):
        """Ring recycling: retire front pages every query position can no
        longer see (`StateLayout.ring_base`), releasing their pool pages
        and device slots in place — the sequence's resident page set stays
        O(window) no matter how long it runs. `_ring_base[seq]` counts the
        drops so page-table position n keeps meaning logical page
        ``base + n``."""
        lay = self.layout
        t = self.pool.page_tokens
        base = self._ring_base.get(seq, 0)
        n_pages = len(self.pool.seq_pages(seq, 0))
        last_pos = (base + n_pages) * t + self.tail_len.get(seq, 0) - 1
        target = lay.ring_base(last_pos)
        dropped = 0
        while base < target and n_pages > 0:
            for l in range(self.num_layers):
                for pid, _layer in self.pool.drop_front(seq, l):
                    if self._device is not None:
                        self._device.release_pid(pid)
            base += 1
            n_pages -= 1
            dropped += 1
        self._ring_base[seq] = base
        row = self._table.get(seq)
        if dropped and row is not None:
            row.drop_front(dropped)

    def release_page(self, pid: int):
        """Recycle a destroyed pool page's device slot — the radix
        prefix tree hooks this (``on_release``) so an evicted/cleared
        pin frees its device slot exactly like `free_seq` does for a
        retired sequence's pages. A destroyed page has no holder left,
        so no table row names it."""
        if self._device is not None:
            self._device.release_pid(pid)

    # -- preemption: whole-sequence swap out / in ---------------------------
    def is_parked(self, seq: int) -> bool:
        return seq in self._parked_tail

    def swap_out(self, seq: int) -> int:
        """Park a live sequence between steps: its partial tail rows are
        read back to the host, its tail/spill device slots are recycled,
        its exclusively-held pool pages move to the host tier
        (`PagedKVPool.swap_out_seq` — shared/pinned pages stay resident),
        and their device slots free. All decode bookkeeping (`tail_len`,
        shard binding, pending chunk hashes) survives, so `swap_in`
        followed by the next `begin_step` resumes mid-decode with
        bit-identical KV. Returns the tail bytes moved to host (page bytes
        are counted in the pool's ``swap_out_bytes`` stat)."""
        if seq in self._parked_tail:
            raise RuntimeError(f"sequence {seq} is already swapped out")
        self._table.pop(seq, None)      # its slots go: rebuilt at swap-in
        tail_bytes = 0
        if self._device is not None:
            n = self.tail_len.get(seq, 0)
            slot = self._tail_slot.pop(seq, None)
            if n > 0:
                if slot is None:
                    raise RuntimeError(
                        f"sequence {seq}: {n} tail rows but no tail slot")
                k_all, v_all = self._device.read_slot(slot)
                kt = np.ascontiguousarray(k_all[:, :n])
                vt = np.ascontiguousarray(v_all[:, :n])
                self._parked_tail[seq] = (kt, vt)
                tail_bytes = kt.nbytes + vt.nbytes
                self.pool.stats["swap_out_bytes"] += tail_bytes
            else:
                self._parked_tail[seq] = None
            if slot is not None:
                self._device.release_slot(slot)
            # the spill slot only ever holds phantom (not-yet-kept) rows
            # between steps — nothing to preserve
            spill = self._spill_slot.pop(seq, None)
            if spill is not None:
                self._device.release_slot(spill)
        else:
            self._parked_tail[seq] = None   # numpy tails already host-side
        if self._rec is not None:
            slot = self._rec_slot.get(seq)
            if slot is not None:
                blocks = self._rec.read_slot(slot)
                self._parked_rec[seq] = blocks
                self._release_rec_slot(seq)
                rec_bytes = sum(v.nbytes for v in blocks.values())
                self.pool.stats["swap_out_bytes"] += rec_bytes
                tail_bytes += rec_bytes
        for pid, _layer in self.pool.swap_out_seq(seq):
            if self._device is not None:
                self._device.release_pid(pid)
        return tail_bytes

    def swap_in(self, seq: int) -> int:
        """Un-park a sequence: pool pages return to their pre-swap device
        tier (the next `begin_step`'s `sync` re-uploads them to freshly
        allocated slots on the sequence's bound shard) and the saved tail
        rows scatter into a new tail slot. Returns tail bytes restored."""
        data = self._parked_tail.pop(seq)   # KeyError == caller bug
        self.pool.swap_in_seq(seq)
        tail_bytes = 0
        n = self.tail_len.get(seq, 0)
        if self._device is not None and n > 0:
            kt, vt = data
            slot = self._ensure_tail_slot(seq)
            slots = np.full(n, slot, np.int32)
            rows = np.arange(n, dtype=np.int32)
            for layer in range(self.num_layers):
                self._device.write_rows(layer, slots, rows,
                                        kt[layer], vt[layer])
            tail_bytes = kt.nbytes + vt.nbytes
            self.pool.stats["swap_in_bytes"] += tail_bytes
        blocks = self._parked_rec.pop(seq, None)
        if blocks is not None:
            self.write_prefill_rec(seq, blocks)    # full set: bit-identical
            rec_bytes = sum(v.nbytes for v in blocks.values())
            self.pool.stats["swap_in_bytes"] += rec_bytes
            tail_bytes += rec_bytes
        return tail_bytes

    # -- retire -------------------------------------------------------------
    def free_seq(self, seq: int) -> list[int]:
        """Retire a request: drop its pool page refs (destroying pages
        whose last holder it was) and recycle its device slots. Returns
        the destroyed pool (page id, layer) pairs."""
        destroyed = self.pool.free(seq)
        if self._device is not None:
            for pid, _layer in destroyed:
                self._device.release_pid(pid)
        self.tail_len.pop(seq, None)
        self._shard_of.pop(seq, None)
        self._pending_hashes.pop(seq, None)
        self._parked_tail.pop(seq, None)
        self._parked_rec.pop(seq, None)
        self._ring_base.pop(seq, None)
        self._table.pop(seq, None)
        if self._rec is not None:
            self._release_rec_slot(seq)
        for key in [k for k in self.tail_data if k[0] == seq]:
            self.tail_data.pop(key)
        for slot in (self._tail_slot.pop(seq, None),
                     self._spill_slot.pop(seq, None)):
            if slot is not None and self._device is not None:
                self._device.release_slot(slot)
        return destroyed

    # -- numpy fallback gather ----------------------------------------------
    def gather(self, layer: int, seq_ids) -> tuple:
        """numpy mode: build (k_pages, v_pages, k_quant, v_quant, k_scale,
        v_scale, page_table, lengths) for the batch at this layer, in the
        kernel's argument order (device modes keep the pool resident — use
        the begin_step/attend protocol instead)."""
        if self.mode != "numpy":
            raise RuntimeError("gather() assembles host arrays — device-"
                               "resident modes use begin_step()/attend()")
        with tracing.span("serve.gather") as sp:
            view = self._gather_numpy(layer, list(seq_ids))
        self.gather_s += sp.elapsed
        return view

    def _seq_view_numpy(self, seq, layer):
        pids = self.pool.seq_pages(seq, layer)
        tail = self.tail_data.get((seq, layer), ())
        if len(pids) + bool(tail) > self.slots:
            raise ValueError(
                f"sequence {seq}: {len(pids)} pages + "
                f"{'a partial' if tail else 'no'} tail page exceed the "
                f"page-table capacity of {self.slots} slots "
                f"({self.slots * self.pool.page_tokens} tokens) at layer "
                f"{layer}; size the PagedKVState capacity to the longest "
                f"request")
        return pids, tail

    def _gather_numpy(self, layer: int, seq_ids) -> tuple:
        pool, t = self.pool, self.pool.page_tokens
        b = len(seq_ids)
        entries: list = []
        table = np.zeros((b, self.slots), np.int32)
        lengths = np.ones(b, np.int32)
        for i, seq in enumerate(seq_ids):
            if seq < 0:
                continue
            pids, tail = self._seq_view_numpy(seq, layer)
            for n, pid in enumerate(pids):
                table[i, n] = len(entries)
                entries.append(pool.pages[pid])
            if tail:
                table[i, len(pids)] = len(entries)
                entries.append(tuple(tail))
            lengths[i] = max(1, len(pids) * t + len(tail))

        hkv, hd = self.hkv, self.hd
        n = max(8, _next_pow2(len(entries)))
        kf = np.zeros((n, t, hkv, hd), np.float32)
        vf = np.zeros_like(kf)
        kq = np.zeros((n, t, hkv, hd), np.int8)
        vq = np.zeros_like(kq)
        ks = np.zeros((n, t, hkv), np.float32)
        vs = np.zeros_like(ks)
        for e, entry in enumerate(entries):
            if isinstance(entry, tuple):               # tail: partial page
                kf[e, :len(entry)] = np.stack([r[0] for r in entry])
                vf[e, :len(entry)] = np.stack([r[1] for r in entry])
            elif entry.tier == "fast":
                kf[e], vf[e] = entry.data
            else:                                      # slow: stays int8
                (pkq, pks), (pvq, pvs) = entry.data
                kq[e], ks[e] = pkq, pks[..., 0]
                vq[e], vs[e] = pvq, pvs[..., 0]
        return kf, vf, kq, vq, ks, vs, table, lengths


# ---------------------------------------------------------------------------
# Full decode step over the layer stack, attention via the paged kernel
# ---------------------------------------------------------------------------
def supports_paged(cfg) -> bool:
    """The paged path covers every stack the paged-state protocol maps:
    ATTN / LOCAL_ATTN / SSD / RGLRU mixers (KV pages, ring pages, O(1)
    recurrent slots) with dense/MoE/none MLPs. MLA and cross-attention
    stacks keep their dense decode caches."""
    return supports_paged_layout(cfg)


def _iter_layers(model, params):
    """Yield (global layer index, kind, per-layer params), unstacking the
    scan groups the same order the dense stack applies them."""
    gs = len(model.group_kinds)
    for g in range(model.n_groups):
        for i, kind in enumerate(model.group_kinds):
            yield (g * gs + i, kind,
                   jax.tree.map(lambda a: a[g], params["groups"][f"l{i}"]))
    for i, kind in enumerate(model.tail_kinds):
        yield model.n_groups * gs + i, kind, params["tail"][f"t{i}"]


def extract_prefill_pages(model, caches, state: PagedKVState, seq_ids,
                          page_hashes=None, valid_len=None, skip_pages=None):
    """Write the prefill caches into the paged-state substrate — KV/ring
    layers as pool pages, recurrent layers as O(1) state blocks.
    `page_hashes[bi]` is that request's cumulative token-prefix digest
    list (prefix caching); `valid_len` drops right-padding rows emitted
    by a bucketed prefill (continuous admission pads prompts to a
    power-of-two length); `skip_pages[bi]` front pages were adopted from
    the prefix cache and are not re-put. Ring (LOCAL_ATTN) layers keep
    only the pages the window can still see — the drop count seeds the
    sequence's ring base. Recurrent layers require an unpadded-right
    prefill (their state is position-final, not sliceable)."""
    gs = len(model.group_kinds)
    lay = state.layout
    t = state.pool.page_tokens
    sl = slice(None, valid_len)
    if lay is not None and lay.has_rec and valid_len is not None:
        raise NotImplementedError(
            "bucketed (right-padded) prefill cannot extract recurrent "
            "state — hybrid stacks admit through chunked prefill")

    def hashes(bi):
        return page_hashes[bi] if page_hashes is not None else None

    def skips(bi):
        return skip_pages[bi] if skip_pages is not None else 0

    # per batch row: store-array name -> per-layer state blocks, appended
    # in global layer order == each kind's substrate row order
    rec_parts: list[dict] = [{} for _ in seq_ids]

    def emit(glayer, mixer, c, cut=None):
        if mixer == MIXER_NONE:
            return
        if mixer == SSD:
            names = (("ssd_conv", "conv"), ("ssd_state", "state"))
        elif mixer == RGLRU:
            names = (("rg_h", "h"), ("rg_conv", "conv"))
        else:
            names = None
        if names is not None:
            for bi in range(len(seq_ids)):
                for store_name, key in names:
                    val = c[key][cut] if cut is not None else c[key]
                    rec_parts[bi].setdefault(store_name, []) \
                        .append(np.asarray(val[bi]))
            return
        kvrow = lay.kv_of[glayer] if lay is not None else glayer
        k = np.asarray(c["k"][cut] if cut is not None else c["k"])
        v = np.asarray(c["v"][cut] if cut is not None else c["v"])
        for bi, seq in enumerate(seq_ids):
            if mixer == LOCAL_ATTN:
                # dense prefill emits the full natural-order cache; keep
                # only pages the window still sees and seed the ring base
                plen = k.shape[1] if valid_len is None else valid_len
                base = lay.ring_base(plen - 1)
                state.write_prefill(kvrow, seq, k[bi, base * t:plen],
                                    v[bi, base * t:plen])
                state._ring_base[seq] = base
            else:
                state.write_prefill(kvrow, seq, k[bi][sl], v[bi][sl],
                                    page_hashes=hashes(bi),
                                    skip_pages=skips(bi))

    for g in range(model.n_groups):
        for i, (mixer, _mlp) in enumerate(model.group_kinds):
            emit(g * gs + i, mixer, caches["groups"][f"l{i}"], cut=g)
    for i, (mixer, _mlp) in enumerate(model.tail_kinds):
        emit(model.n_groups * gs + i, mixer, caches["tail"][f"t{i}"])

    for bi, seq in enumerate(seq_ids):
        if rec_parts[bi]:
            state.write_prefill_rec(
                seq, {n: np.stack(v) for n, v in rec_parts[bi].items()})


def paged_decode_step(model, params, tokens, state: PagedKVState, seq_ids,
                      pos, backend: str = "auto"):
    """One decode step with every attention layer served from the page
    pool — the per-layer *eager* reference path (and the numpy fallback):
    each layer pulls its new K/V rows to the host and dispatches the
    paged kernel separately, ~2 host/device crossings per layer. The
    fused path (`build_fused_step`) must match it token-for-token.

    tokens: (b,) int32; `pos` is a scalar shared by the batch (static
    lockstep) or a (b,) int32 array of per-sequence absolute positions
    (continuous batching); `seq_ids` may carry -1 for padded (retired)
    rows, whose logits are garbage and must be ignored. Returns logits
    (b, V)."""
    cfg = model.cfg
    if not all(mixer == ATTN for mixer, _ in cfg.layer_kinds()) \
            or not supports_paged(cfg):
        raise NotImplementedError(
            f"eager paged decode needs a pure global-attention stack "
            f"(recurrent/ring layers are fused-only), got "
            f"{cfg.layer_kinds()}")
    seq_ids = list(seq_ids)
    state.begin_step(seq_ids, pos)
    x = model._embed_in(params, {"tokens": jnp.asarray(tokens)[:, None]})
    pos_in = jnp.asarray(pos, jnp.int32)

    for layer, kind, p in _iter_layers(model, params):
        h = rms_norm(x, p["norm1"])
        ap = p["attn"]
        q, k_new, v_new = decode_qkv(cfg, ap, h, pos_in)
        kn = np.asarray(k_new[:, 0], np.float32)       # (b, hkv, hd)
        vn = np.asarray(v_new[:, 0], np.float32)
        state.d2h += 2
        state.append_step_rows(layer, kn, vn)
        y = state.attend(q[:, 0], layer, backend=backend)
        y = jnp.einsum("bhk,hkd->bd", y.astype(x.dtype), ap["wo"])[:, None]
        x = x + y
        x, _ = mlp_tail(cfg, kind, p, x)

    x = rms_norm(x, params["final_norm"])
    logits = lm_head_apply(cfg, params["embed"], x)[:, 0]
    state.end_step(seq_ids)
    return logits


# ---------------------------------------------------------------------------
# Fused decode step: the whole token in one jitted, device-resident graph
# ---------------------------------------------------------------------------
def _mlp_tail_tp(cfg, kind, p, x, tp):
    """`mlp_tail` with the tensor-parallel reduction seam: a dense MLP's
    up/down projections are ffn-sharded over the mesh's model axis, so the
    down-proj emits a partial sum that one psum completes. MoE subtrees
    replicate (routing is local, every shard runs the full expert stack)
    and MLP_NONE layers pass through — both fall back to plain mlp_tail."""
    from repro.models.layers import mlp_apply
    _mixer, mlp = kind
    if tp <= 1 or mlp != MLP_DENSE:
        x, _ = mlp_tail(cfg, kind, p, x)
        return x
    h = rms_norm(x, p["norm2"])
    y = jax.lax.psum(mlp_apply(cfg, p["mlp"], h), "model")
    return x + y


def _mixer_scope(mixer) -> str:
    """`jax.named_scope` of a layer's token mixer in the fused step; the
    step's other scopes are "mlp" and "logits" (final norm, lm head,
    sampling and, k > 1, the accept rule)."""
    return "attention" if mixer in (ATTN, LOCAL_ATTN) else "recurrence"


def _wrap_step(step, model, plan, *, k, control_spec, out_spec,
               layout=None):
    """jit the step as ``fused_step_k<k>`` (its XLA module is
    ``jit_fused_step_k<k>``, so a profile tells the widths apart); under
    a mesh plan, shard_map it first: params by the serve partition rules,
    pool + recurrent-store arrays by the kernel's head-sharded calling
    convention, decode rows over "data".
    check_vma=False because the body's donated scatters + psum seams are
    not replication-safe to infer; correctness is asserted by the
    sharded-vs-single-device equivalence tests."""
    step.__name__ = step.__qualname__ = f"fused_step_k{k}"
    if plan is None:
        return jax.jit(step, donate_argnums=(1,))
    from jax.sharding import PartitionSpec as P
    cfg = model.cfg
    rep_heads = cfg.num_kv_heads > 0 and cfg.num_kv_heads % plan.tp != 0
    arr_specs = plan.pool_specs(replicate_heads=rep_heads)
    if layout is not None:
        arr_specs = arr_specs + rec_array_specs(layout, plan)
    mapped = jax.shard_map(
        step, mesh=plan.mesh,
        in_specs=(plan.param_specs(model), arr_specs) + control_spec
        + (P(),),
        out_specs=(out_spec, arr_specs), check_vma=False)
    return jax.jit(mapped, donate_argnums=(1,))


def build_fused_step(model, num_slots: int, *, k: int = 1,
                     backend: str = "auto", greedy: bool = True,
                     temperature: float = 1.0, plan=None, layout=None,
                     drafts: bool = True):
    """Build the jitted fused decode step.

    ``k == 1`` — the plain PR-4 step. Returned callable:
    ``step(params, arrays, tokens, control, key) -> (sampled_tokens (b,)
    int32, new_arrays)`` where ``arrays`` is the layer-stacked device pool
    tuple (DONATED — callers must adopt the returned tuple) and
    ``control`` the int32 block from `PagedKVState.begin_step`.
    Everything the step touches is already device-resident: the K/V rows
    of each layer are appended by in-place scatters on the donated pool
    inside the graph, the paged-attention kernel reads the layer's pages
    via a scalar-prefetched layer index resolved at trace time through
    ``api.run(..., backend=...)``, and only the sampled tokens come back —
    the host sees no tensor data.

    ``k > 1`` — the speculative VERIFY step over the same graph, widened
    to k token rows per sequence. Returned callable:
    ``step(params, arrays, control, key) -> (verdict (b, k + 1) int32,
    new_arrays)``. The k input tokens (last accepted + k-1 draft tokens)
    ride inside the control block (`begin_step(k=..., tokens=...)`), every
    layer scatters k K/V rows (spilling across at most one page boundary
    into the control block's spill slot) and attends all k rows through
    the kernel's multi-query-row path in ONE KV pass, and the graph
    finishes with the accept rule itself: position j's sampled token is
    the model's answer after consuming drafts 0..j-1, draft j is accepted
    while it equals the sampled token at position j-1, and the verdict
    block packs ``[k sampled tokens | accepted draft count]`` so the host
    learns an entire accepted run (plus the standard bonus token) from a
    single download. Greedy verification emits exactly the tokens the
    k=1 step would; sampling draws each position from its true
    conditional (drafts are deterministic), so the distribution is exact
    though the stream consumes keys differently than the k=1 path.

    ``plan`` (a `serve.sharding.ServePlan`) runs the identical step body
    under shard_map: decode rows shard over the mesh's "data" axis (each
    shard's rows attend only its own page-pool slice — the control block
    carries shard-local slot ids), attention/MLP heads shard over "model"
    with psum seams after the wo- and down-projections, and sampling
    folds the data-shard index into the key so concurrent rows draw
    independent noise. ``plan=None`` is the exact single-device graph.

    ``layout`` (a `paged_state.StateLayout`) generalizes the graph to
    heterogeneous stacks: LOCAL_ATTN layers scatter into the same KV pool
    but attend a ring gather windowed by the control block's base column,
    SSD/RGLRU layers read/advance their O(1) state slot in the
    RecurrentStore arrays riding behind the six pool arrays (zeros in its
    place at a row's first step, the control block's ``fresh`` column).
    Pure-ATTN stacks trace the identical legacy graph with or without a
    layout.

    ``drafts`` (k > 1): whether rows may carry draft tokens, whose kept
    count the accept rule decides inside the graph — then each recurrent
    layer emits its k candidate states for the checkpoint commit. A wide
    step without drafts (prompt chunks, decode rows riding them) knows
    every row's count up front (``keep_fixed``): each recurrent layer
    reads and writes its slots once, an SSD layer through its chunk
    form."""
    cfg = model.cfg
    gs = len(model.group_kinds)
    s = num_slots
    tp = plan.tp if plan is not None else 1
    dp = plan.dp if plan is not None else 1
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    lay = layout if layout is not None else StateLayout(cfg, 1)
    if k > 1:
        return _build_spec_step(model, num_slots, k, backend=backend,
                                greedy=greedy, temperature=temperature,
                                plan=plan, layout=lay, drafts=drafts)
    cc = lay.cols(s, 1)
    rec_of = {n: i for i, n in enumerate(rec_array_names(lay))}
    n_rec = len(rec_of)

    def rows_of(g, i):
        """Substrate rows of group-position i at (traced) group index g."""
        kv_r, ssd_r, rg_r = lay.kv_rank[i], lay.ssd_rank[i], lay.rg_rank[i]
        return (None if kv_r is None else g * lay.kv_per_group + kv_r,
                None if ssd_r is None else g * lay.ssd_per_group + ssd_r,
                None if rg_r is None else g * lay.rg_per_group + rg_r)

    def tail_rows_of(i):
        return lay.tail_kv[i], lay.tail_ssd[i], lay.tail_rg[i]

    def fused_step(params, arrays, tokens, control, key):
        kv = tuple(arrays[:6])
        rec = list(arrays[6:])
        kf, vf, kq, vq, ks, vs = kv
        ll, c, t = kf.shape[0], kf.shape[1], kf.shape[2]
        table = control[:, :s]
        positions = control[:, cc.pos]
        lengths = control[:, cc.len]
        # flat (layer, slot, row) scatter index base for the step's rows
        row_base = control[:, cc.tail] * t + control[:, cc.row]
        rec_slots = control[:, cc.rec] if lay.has_rec else None
        fresh = control[:, cc.fresh] if lay.has_rec else None
        ring_base = control[:, cc.base] if lay.has_ring else None
        flat_kv = (ll * c * t,) + kf.shape[3:]

        x = model._embed_in(params, {"tokens": tokens[:, None]})

        def layer_step(carry, kind, p, row_kv, row_ssd, row_rg):
            x, kf, vf = carry[0], carry[1], carry[2]
            rec = list(carry[3:])
            mixer, _mlp = kind
            if mixer in (ATTN, LOCAL_ATTN):
                with jax.named_scope(_mixer_scope(mixer)):
                    h = rms_norm(x, p["norm1"])
                    ap = p["attn"]
                    q, k_new, v_new = decode_qkv(cfg, ap, h, positions)
                    idx = row_kv * (c * t) + row_base
                    kf = kf.reshape(flat_kv).at[idx] \
                        .set(k_new[:, 0].astype(kf.dtype)).reshape(kf.shape)
                    vf = vf.reshape(flat_kv).at[idx] \
                        .set(v_new[:, 0].astype(vf.dtype)).reshape(vf.shape)
                    if mixer == ATTN:
                        y = api.run("paged_attention", q[:, 0], kf, vf, kq, vq,
                                    ks, vs, table, lengths,
                                    jnp.asarray(row_kv, jnp.int32),
                                    backend=backend)
                    else:
                        k_all, v_all = gather_ring_kv((kf, vf, kq, vq, ks, vs),
                                                      row_kv, table)
                        y = ring_attend(q, k_all, v_all, lengths=lengths,
                                        base=ring_base,
                                        positions=positions[:, None],
                                        window=lay.window, page_tokens=t)[:, 0]
                    y = jnp.einsum("bhk,hkd->bd", y.astype(x.dtype), ap["wo"])
                    if tp > 1:      # complete the head-sharded partial sum
                        y = jax.lax.psum(y, "model")
                    x = x + y[:, None]
            elif mixer != MIXER_NONE:   # SSD / RGLRU: the row's slot
                with jax.named_scope(_mixer_scope(mixer)):
                    h = rms_norm(x, p["norm1"])
                    names, row = (("ssd_conv", "ssd_state"), row_ssd) \
                        if mixer == SSD else (("rg_h", "rg_conv"), row_rg)
                    ids = [rec_of[n] for n in names]
                    y, new = rec_advance(
                        cfg, mixer, p["ssm" if mixer == SSD else "rglru"], h,
                        tuple(rec[i] for i in ids), row, rec_slots, fresh,
                        None, tp=tp)
                    for i, a in zip(ids, new):
                        rec[i] = a
                    x = x + y
            with jax.named_scope("mlp"):
                x = _mlp_tail_tp(cfg, kind, p, x, tp)
            return (x, kf, vf, *rec)

        def group_body(carry, xs):
            gp, g = xs
            for i, kind in enumerate(model.group_kinds):
                carry = layer_step(carry, kind, gp[f"l{i}"], *rows_of(g, i))
            return carry, None

        carry, _ = jax.lax.scan(
            group_body, (x, kf, vf, *rec),
            (params["groups"], jnp.arange(model.n_groups)))
        for i, kind in enumerate(model.tail_kinds):
            carry = layer_step(carry, kind, params["tail"][f"t{i}"],
                               *tail_rows_of(i))
        x, kf, vf = carry[0], carry[1], carry[2]
        rec = list(carry[3:])

        with jax.named_scope("logits"):
            x = rms_norm(x, params["final_norm"])
            logits = lm_head_apply(cfg, params["embed"], x)[:, 0]
            if greedy:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                if dp > 1:      # independent noise per data shard's rows
                    key = jax.random.fold_in(key, jax.lax.axis_index("data"))
                tok = jax.random.categorical(key, logits / temperature,
                                             axis=-1).astype(jnp.int32)
        return tok, (kf, vf, kq, vq, ks, vs, *rec)

    from jax.sharding import PartitionSpec as P
    return _wrap_step(fused_step, model, plan, k=1,
                      control_spec=(P("data"), P("data", None)),
                      out_spec=P("data"),
                      layout=lay if n_rec else None)


def _commit_rec_checkpoints(model, lay, rec, rec_of, group_states,
                            tail_states, rec_slots, keep):
    """Write each row's selected recurrent checkpoint back to its state
    slot — ONE flat scatter per store array, covering every recurrent
    layer (scan groups and tail) at once. ``group_states`` is the scan's
    stacked ys (per rec-bearing group position: leaves (G, k, b, ...)),
    ``tail_states`` the tail layers' (k, b, ...) leaves, ``keep`` (b,)
    the accept rule's per-row token-keep count."""
    G = model.n_groups
    contrib = {i: ([], []) for i in rec_of.values()}   # idx -> rows, vals

    def add(name, rows, vals):
        r, v = contrib[rec_of[name]]
        r.append(rows)
        v.append(vals)

    gi = 0
    for i, (mixer, _mlp) in enumerate(model.group_kinds):
        if mixer not in (SSD, RGLRU):
            continue
        st = group_states[gi]
        gi += 1
        if mixer == SSD:
            names = ("ssd_conv", "ssd_state")
            per, rank = lay.ssd_per_group, lay.ssd_rank[i]
        else:
            names = ("rg_h", "rg_conv")
            per, rank = lay.rg_per_group, lay.rg_rank[i]
        rows = jnp.arange(G, dtype=jnp.int32) * per + rank
        for name, leaf in zip(names, st):
            # (G, k, b, ...) -> per-group checkpoint pick -> (G, b, ...)
            add(name, rows,
                jax.vmap(lambda sl: select_checkpoint(sl, keep))(leaf))
    ti = 0
    for j, (mixer, _mlp) in enumerate(model.tail_kinds):
        if mixer not in (SSD, RGLRU):
            continue
        st = tail_states[ti]
        ti += 1
        if mixer == SSD:
            names = ("ssd_conv", "ssd_state")
            row = lay.tail_ssd[j]
        else:
            names = ("rg_h", "rg_conv")
            row = lay.tail_rg[j]
        for name, leaf in zip(names, st):
            add(name, jnp.asarray([row], jnp.int32),
                select_checkpoint(leaf, keep)[None])
    out = list(rec)
    for idx, (rows_l, vals_l) in contrib.items():
        if not rows_l:
            continue
        a = out[idx]
        rows = jnp.concatenate(rows_l)
        vals = jnp.concatenate(vals_l, axis=0)          # (R, b, ...)
        fidx = (rows[:, None] * a.shape[1]
                + rec_slots[None, :]).reshape(-1)
        flat = a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])
        out[idx] = flat.at[fidx].set(
            vals.reshape((-1,) + vals.shape[2:]).astype(a.dtype)
        ).reshape(a.shape)
    return out


def _build_spec_step(model, num_slots: int, k: int, *, backend: str = "auto",
                     greedy: bool = True, temperature: float = 1.0,
                     plan=None, layout=None, drafts: bool = True):
    """The k-row speculative verify graph behind `build_fused_step(k>1)`;
    see that docstring for the contract.

    With ``drafts``, recurrent layers verify by construction in O(1) per
    token: the pre-step state slot is READ once, the scan emits all k
    candidate post-token states as stacked outputs (never overwriting
    in-scan), and after the accept rule resolves each row's ``keep``
    count, ONE scatter per store array commits checkpoint ``keep - 1``.
    Rollback is selection, not replay."""
    cfg = model.cfg
    gs = len(model.group_kinds)
    s = num_slots
    tp = plan.tp if plan is not None else 1
    dp = plan.dp if plan is not None else 1
    lay = layout if layout is not None else StateLayout(cfg, 1)
    cc = lay.cols(s, k)
    rec_names = rec_array_names(lay)
    rec_of = {n: i for i, n in enumerate(rec_names)}

    def rows_of(g, i):
        kv_r, ssd_r, rg_r = lay.kv_rank[i], lay.ssd_rank[i], lay.rg_rank[i]
        return (None if kv_r is None else g * lay.kv_per_group + kv_r,
                None if ssd_r is None else g * lay.ssd_per_group + ssd_r,
                None if rg_r is None else g * lay.rg_per_group + rg_r)

    def fused_step(params, arrays, control, key):
        kv = tuple(arrays[:6])
        rec = list(arrays[6:])
        kf, vf, kq, vq, ks, vs = kv
        ll, c, t = kf.shape[0], kf.shape[1], kf.shape[2]
        table = control[:, :s]
        tail1 = control[:, cc.tail]
        spill = control[:, cc.spill]
        tail_row = control[:, cc.row]
        pos0 = control[:, cc.pos]
        lengths = control[:, cc.len]                        # row 0's length
        tokens = control[:, cc.tok:cc.tok + k]              # (b, k)
        rec_slots = control[:, cc.rec] if lay.has_rec else None
        fresh = control[:, cc.fresh] if lay.has_rec else None
        ring_base = control[:, cc.base] if lay.has_ring else None
        keeps = (control[:, cc.keep_fixed], control[:, cc.keep_cap]) \
            if lay.has_rec else None
        # without drafts every row's token count is fixed before the step
        # (a chunk row's chunk length, else 1: no row proposes drafts)
        fixed = jnp.clip(jnp.where(keeps[0] >= 0, keeps[0], 1), 1, k) \
            if lay.has_rec and not drafts else None
        offs = jnp.arange(k, dtype=jnp.int32)
        positions = pos0[:, None] + offs[None, :]           # (b, k)
        # per-row scatter target: rows crossing the page boundary go to
        # the spill slot (tail_row < t and k <= t bound r below 2t)
        r = tail_row[:, None] + offs[None, :]
        slot = jnp.where(r < t, tail1[:, None], spill[:, None])
        row_base = slot * t + jnp.where(r < t, r, r - t)    # (b, k)
        flat_kv = (ll * c * t,) + kf.shape[3:]

        x = model._embed_in(params, {"tokens": tokens})     # (b, k, d)

        def layer_step(carry, kind, p, row_kv, row_ssd, row_rg):
            """-> (carry, states): `states` is the stacked (k, b, ...)
            candidate-state leaves of a recurrent layer that the
            post-accept checkpoint commit selects from (``drafts``), else
            None; without drafts a recurrent layer advances its slots in
            place by each row's fixed token count."""
            x, kf, vf = carry[0], carry[1], carry[2]
            rec = list(carry[3:])
            mixer, _mlp = kind
            states = None
            if mixer in (ATTN, LOCAL_ATTN):
                with jax.named_scope(_mixer_scope(mixer)):
                    h = rms_norm(x, p["norm1"])
                    ap = p["attn"]
                    q, k_new, v_new = decode_qkv(cfg, ap, h, positions)
                    idx = (row_kv * (c * t) + row_base).reshape(-1)  # (b * k,)
                    b = k_new.shape[0]
                    kf = kf.reshape(flat_kv).at[idx] \
                        .set(k_new.reshape((b * k,) + k_new.shape[2:])
                             .astype(kf.dtype)).reshape(kf.shape)
                    vf = vf.reshape(flat_kv).at[idx] \
                        .set(v_new.reshape((b * k,) + v_new.shape[2:])
                             .astype(vf.dtype)).reshape(vf.shape)
                    if mixer == ATTN:
                        # ONE KV pass scores all k rows (multi-query-row
                        # kernel path: row j masks to lengths + j)
                        y = api.run("paged_attention", q, kf, vf, kq, vq, ks,
                                    vs, table, lengths,
                                    jnp.asarray(row_kv, jnp.int32),
                                    backend=backend)
                    else:
                        k_all, v_all = gather_ring_kv((kf, vf, kq, vq, ks, vs),
                                                      row_kv, table)
                        y = ring_attend(q, k_all, v_all, lengths=lengths,
                                        base=ring_base, positions=positions,
                                        window=lay.window, page_tokens=t)
                    y = jnp.einsum("bshk,hkd->bsd", y.astype(x.dtype),
                                   ap["wo"])
                    if tp > 1:      # complete the head-sharded partial sum
                        y = jax.lax.psum(y, "model")
                    x = x + y
            elif mixer != MIXER_NONE:   # SSD / RGLRU: the row's slot
                with jax.named_scope(_mixer_scope(mixer)):
                    h = rms_norm(x, p["norm1"])
                    names, row = (("ssd_conv", "ssd_state"), row_ssd) \
                        if mixer == SSD else (("rg_h", "rg_conv"), row_rg)
                    ids = [rec_of[n] for n in names]
                    pm = p["ssm" if mixer == SSD else "rglru"]
                    if drafts:
                        state0 = tuple(rec_read(rec[i], row, rec_slots, fresh)
                                       for i in ids)
                        y, states = rec_scan_tokens(cfg, mixer, pm, h, state0,
                                                    tp=tp)
                    else:
                        y, new = rec_advance(cfg, mixer, pm, h,
                                             tuple(rec[i] for i in ids), row,
                                             rec_slots, fresh, fixed, tp=tp)
                        for i, a in zip(ids, new):
                            rec[i] = a
                    x = x + y
            with jax.named_scope("mlp"):
                x = _mlp_tail_tp(cfg, kind, p, x, tp)
            return (x, kf, vf, *rec), states

        def group_body(carry, xs):
            gp, g = xs
            ys = []
            for i, kind in enumerate(model.group_kinds):
                carry, st = layer_step(carry, kind, gp[f"l{i}"],
                                       *rows_of(g, i))
                if st is not None:
                    ys.append(st)
            return carry, tuple(ys)

        carry, group_states = jax.lax.scan(
            group_body, (x, kf, vf, *rec),
            (params["groups"], jnp.arange(model.n_groups)))
        tail_states = []
        for i, kind in enumerate(model.tail_kinds):
            carry, st = layer_step(
                carry, kind, params["tail"][f"t{i}"],
                lay.tail_kv[i], lay.tail_ssd[i], lay.tail_rg[i])
            if st is not None:
                tail_states.append(st)
        x, kf, vf = carry[0], carry[1], carry[2]
        rec = list(carry[3:])

        with jax.named_scope("logits"):
            x = rms_norm(x, params["final_norm"])
            logits = lm_head_apply(cfg, params["embed"], x)      # (b, k, V)
            if greedy:
                samp = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                if dp > 1:      # independent noise per data shard's rows
                    key = jax.random.fold_in(key, jax.lax.axis_index("data"))
                samp = jax.random.categorical(key, logits / temperature,
                                              axis=-1).astype(jnp.int32)
            # accept rule: draft j (input column j, j >= 1) survives while it
            # equals the model's sampled token after the previous position —
            # the count of the all-match prefix, exactly the tokens the
            # autoregressive path would have produced
            match = (tokens[:, 1:] == samp[:, :-1]).astype(jnp.int32)
            n_acc = jnp.cumprod(match, axis=1).sum(axis=1)
            verdict = jnp.concatenate([samp, n_acc[:, None]], axis=1)

        if lay.has_rec and drafts:
            # commit the per-row state checkpoint: chunked-prefill rows
            # keep their fixed token count, verify rows keep accepted +
            # bonus capped at the row's real proposal count — O(1)
            # rollback is SELECTING checkpoint keep-1, never a replay
            keep_fixed, keep_cap = keeps
            keep = jnp.where(keep_fixed >= 0, keep_fixed,
                             jnp.minimum(n_acc, keep_cap) + 1)
            keep = jnp.clip(keep, 1, k)
            rec = _commit_rec_checkpoints(model, lay, rec, rec_of,
                                          group_states, tail_states,
                                          rec_slots, keep)
        return verdict, (kf, vf, kq, vq, ks, vs, *rec)

    from jax.sharding import PartitionSpec as P
    return _wrap_step(fused_step, model, plan, k=k,
                      control_spec=(P("data", None),),
                      out_spec=P("data", None),
                      layout=lay if rec_names else None)
