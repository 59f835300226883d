"""Serving engines over one model + params:

- `generate` — static-batch path: groups requests into a fixed batch,
  prefills the (left-padded) prompts, then decodes in lockstep. With a
  `PagedKVPool` attached, decode attention is served from real KV pages
  through the registry's paged-attention kernel (tiered int8 slow pages
  included).
- `serve` — continuous batching: a `Scheduler` admits requests into free
  decode rows mid-flight (admission gated on pool headroom), each row
  decodes at its own position/length, and retiring (per-request
  ``max_new_tokens`` or ``eos_token``) frees the request's pool pages, so
  the pool tracks the live working set. Greedy tokens are identical to
  running each request alone through the static-batch paged path.

Paged decode runs in one of two modes (``decode_mode``): ``fused``
(default) executes the whole step as a single jitted, device-resident
graph of any width — two host/device crossings per step, independent of
depth; ``eager`` is the per-layer reference path the fused graph is
tested against. See `serve.paged_decode`.

Speculative multi-token decode (``speculate=k`` on the engine or per
`Request`): a draft proposer (`serve.speculative`) guesses k-1 tokens per
request and one widened fused VERIFY step scores all k rows in a single
jitted graph and a single KV pass — steady state becomes 2 host/device
crossings per accepted *run* of up to k tokens instead of per token.
Greedy outputs are token-for-token identical to the 1-token fused path
for any draft; both engines report per-request ``accept_rate`` and
``tokens_per_step`` in ``last_request_stats``.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import api
from repro.models import Model
from repro.serve import tracing
from repro.serve.kvcache import PagedKVPool, pad_caches
from repro.serve.paged_state import StateLayout
from repro.serve.paged_decode import (MODES, PagedKVState, build_fused_step,
                                      extract_prefill_pages,
                                      paged_decode_step, supports_paged)
from repro.serve.preemption import LRUVictimPolicy, RequestView
from repro.serve.prefix_cache import RadixPrefixCache
from repro.serve.scheduler import (Admission,  # noqa: F401 (re-export)
                                   Request, Scheduler, effective_speculate,
                                   prefix_page_hashes)
from repro.serve.sharding import ServePlan
from repro.serve.speculative import SpecStats, make_draft
from repro.serve.steps import prefill_all_positions


class _Active:
    """One occupied decode row of the continuous batch. A chunked-prefill
    row starts with ``pending`` suffix tokens still to stream into the
    KV pool (``prefilled`` counts tokens already resident, adopted prefix
    included) and an empty ``outs`` — it joins decode once the final
    chunk produces its first token."""

    __slots__ = ("req", "seq", "plen", "outs", "eff_k", "stats",
                 "pending", "prefilled", "hashes")

    def __init__(self, req: Request, seq: int, plen: int, outs: list,
                 eff_k: int = 1):
        self.req, self.seq, self.plen, self.outs = req, seq, plen, outs
        self.eff_k = eff_k
        self.stats = SpecStats()
        self.pending: Optional[np.ndarray] = None
        self.prefilled = 0
        self.hashes: Optional[list] = None

    @property
    def pos(self) -> int:
        """Absolute position of the token being fed this step."""
        return self.plen + len(self.outs) - 1

    @property
    def prefilling(self) -> bool:
        return self.pending is not None and len(self.pending) > 0

    @property
    def finished(self) -> bool:
        if not self.outs:               # still prefilling: no token yet
            return False
        return (len(self.outs) >= self.req.max_new_tokens
                or self.outs[-1] == self.req.eos_token)


class ServeEngine:
    """Engine over one model + params; see module docstring for the two
    decode paths. Cache capacity = prompt_len + max_new tokens.

    ``knee_cache`` (a JSON path, canonically
    ``api.knee_cache_path(checkpoint_dir)``) persists the tiles resolved
    by ``backend="auto"`` across restarts: loaded at construction, saved
    after each generate/serve that resolved something new — a serving
    restart skips the tuning sweep for every shape it already saw."""

    def __init__(self, cfg: ModelConfig, params=None, seed: int = 0,
                 kv_pool: Optional[PagedKVPool] = None,
                 decode_mode: str = "fused",
                 knee_cache=None, speculate: int = 0, draft="ngram",
                 mesh=None):
        self.cfg = cfg
        self.model = Model(cfg)
        self.params = params if params is not None else \
            self.model.init(jax.random.PRNGKey(seed))
        self.kv_pool = kv_pool
        if decode_mode not in MODES:
            raise ValueError(f"decode_mode {decode_mode!r} not in {MODES}")
        self.decode_mode = decode_mode
        # mesh-aware serving (`serve.sharding.ServePlan`): default is the
        # host mesh — on one device that collapses to plan=None, the exact
        # pre-mesh stack; a multi-device mesh shards decode rows over
        # "data" and attention/MLP heads over "model". Only the fused
        # decode graph runs under shard_map (eager is the single-device
        # reference).
        if mesh is None and decode_mode == "fused":
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.plan = ServePlan.from_mesh(mesh) \
            if decode_mode == "fused" else None
        if self.plan is not None:
            self.plan.check_config(cfg)
            self.params = self.plan.shard_params(self.model, self.params)
        self.knee_cache = knee_cache
        if knee_cache is not None:
            api.load_knee_cache(knee_cache)
        # engine-level speculation default (per-Request `speculate` wins);
        # `draft` is "ngram[:N]", "self", or any propose(history, n) object
        self.speculate = int(speculate)
        self._draft_arg = draft
        self._draft = None
        self._next_seq = 0           # pool seq ids are engine-lifetime unique
        self._decode = jax.jit(self.model.forward_decode,
                               donate_argnums=2)
        self._prefill = jax.jit(self.model.forward_prefill)
        self._prefill_all = jax.jit(
            functools.partial(prefill_all_positions, self.model))
        self._fused_cache: dict = {}
        self.stats = {"prefill_s": 0.0, "decode_s": 0.0, "tokens": 0,
                      "decode_steps": 0}
        self.last_request_stats: list[dict] = []

    @property
    def draft(self):
        if self._draft is None:
            self._draft = make_draft(self._draft_arg, self.model,
                                     self.params,
                                     prefill_fn=self._prefill_all)
        return self._draft

    def _check_spec_width(self, k: int):
        """Validate a k-token verify-graph width against the engine setup:
        k > 1 requires the fused paged path — eager stays the 1-token
        reference — and k <= page_tokens (one verify step may cross at
        most one page boundary)."""
        if k <= 1:
            return
        if self.kv_pool is None:
            raise ValueError("speculative decode verifies against the "
                             "page pool — construct the engine with "
                             "kv_pool=")
        if self.decode_mode != "fused":
            raise ValueError(
                f"speculative decode (k={k}) runs over the fused verify "
                f"step; decode_mode={self.decode_mode!r} stays the "
                f"1-token reference")
        t = self.kv_pool.page_tokens
        if k > t:
            raise ValueError(
                f"speculate={k} exceeds page_tokens={t}: one verify "
                f"step may cross at most one page boundary")

    def _resolve_spec(self, requests) -> tuple[int, list[int]]:
        """Effective per-request k (Request.speculate, falling back to the
        engine default) and the verify-graph width (their max)."""
        ks = [effective_speculate(r, self.speculate) for r in requests]
        k = max(ks, default=1)
        self._check_spec_width(k)
        return k, ks

    def _layout(self) -> StateLayout:
        """Paged-state layout for this (config, page_tokens) pair, cached:
        which layers take KV pages / recurrent slots / ring pages."""
        lay = getattr(self, "_layout_cache", None)
        if lay is None:
            lay = StateLayout(self.cfg, self.kv_pool.page_tokens)
            self._layout_cache = lay
        return lay

    @property
    def _hybrid(self) -> bool:
        """True when the stack holds any non-global-attention mixer
        (recurrent slots or ring pages) — served fused-only."""
        lay = self._layout()
        return lay.has_rec or lay.has_ring

    def _require_paged(self):
        if self.kv_pool is None:
            raise ValueError("continuous serving decodes from a page pool — "
                             "construct the engine with kv_pool=")
        if not supports_paged(self.cfg):
            raise NotImplementedError(
                f"{self.cfg.name}: paged serving needs a stack of "
                f"attn/local_attn/ssd/rglru mixers")
        if self._hybrid and self.decode_mode != "fused":
            raise NotImplementedError(
                f"{self.cfg.name}: recurrent/ring layers serve through the "
                f"fused paged step only; decode_mode="
                f"{self.decode_mode!r} stays the global-attention "
                f"reference")

    def _new_state(self, capacity: int, batch_hint: int,
                   tail_slots: int = 1) -> PagedKVState:
        return PagedKVState(self.kv_pool, capacity, self.cfg.num_layers,
                            self.cfg.num_kv_heads, self.cfg.head_dim,
                            mode=self.decode_mode, batch_hint=batch_hint,
                            tail_slots=tail_slots, plan=self.plan,
                            layout=self._layout())

    def _fused_step_fn(self, slots: int, greedy: bool, temperature: float,
                       k: int = 1, drafts: bool = True):
        drafts = drafts and k > 1      # one token carries no draft
        key = (slots, greedy, float(temperature), k, drafts)
        fn = self._fused_cache.get(key)
        if fn is None:
            fn = build_fused_step(self.model, slots, k=k, greedy=greedy,
                                  temperature=temperature, plan=self.plan,
                                  layout=self._layout(), drafts=drafts)
            self._fused_cache[key] = fn
        return fn

    def _fused_step(self, state: PagedKVState, step_fn, k: int, rows, key):
        """One fused step, ``k`` tokens wide, over the current batch rows:
        plain decode (k = 1), speculative verify and prompt chunks alike.

        ``rows``: per batch row, ``None`` (dead/padded) or a dict with
        ``seq`` (pool id), ``last`` (the token this step feeds), ``pos``
        (absolute position of that token), ``eff_k`` (the request's
        per-step token budget), ``limit`` (tokens still allowed before
        max_new, >= 1), ``eos`` (stop token or None), ``stats``
        (`SpecStats`) and, for a row that proposes drafts (``eff_k >
        1``), ``history`` (int32 array: true prompt + emitted tokens).
        Proposes drafts, runs the fused step, and advances the state by
        exactly the per-row kept counts — the accepted prefix + bonus
        token, clamped by limit/eos; everything else rolls back. Returns
        the per-row kept-token lists.

        A row may instead carry a prefill CHUNK (``{"seq", "pos",
        "chunk", "final"}``): up to k TRUE prompt tokens fed through the
        same verify graph — the causal row mask and in-graph accept rule
        need no changes, the row simply advances by the full chunk length
        unconditionally (true tokens are always "accepted"). Columns past
        the chunk repeat its last token; their K/V rows are phantom
        (`end_step` overwrites them). A ``final`` chunk's request keeps
        exactly one token — the argmax/sample after the last prompt
        token, i.e. the request's first generated token — read from
        ``verdict[i, m - 1]``; earlier chunks keep nothing."""
        b = len(rows)
        toks = np.zeros((b, k), np.int32)
        seq_ids = [-1] * b
        pos = np.zeros(b, np.int32)
        proposed = [0] * b
        # recurrent stacks: per-row in-graph state-checkpoint picks —
        # chunk rows commit exactly their chunk length of recurrent
        # state; draft rows commit min(accepted, proposed) + 1 (padding
        # columns must never advance the state even if they "accept")
        keep_fixed = np.ones(b, np.int32)
        keep_cap = np.zeros(b, np.int32)
        for i, r in enumerate(rows):
            if r is None:
                continue
            seq_ids[i] = r["seq"]
            pos[i] = r["pos"]
            chunk = r.get("chunk")
            if chunk is not None:
                m = len(chunk)
                toks[i, :m] = chunk
                keep_fixed[i] = m
                if m < k:               # pad: repeat the last true token
                    toks[i, m:] = chunk[-1]
                continue
            toks[i, 0] = r["last"]
            n_d = min(r["eff_k"], k) - 1
            if n_d > 0:
                drafts = np.asarray(self.draft.propose(r["history"], n_d),
                                    np.int32)
                proposed[i] = len(drafts)
                toks[i, 1:1 + len(drafts)] = drafts
            if proposed[i] < k - 1:     # pad: repeat the last filled token
                toks[i, 1 + proposed[i]:] = toks[i, proposed[i]]
            keep_fixed[i] = -1
            keep_cap[i] = proposed[i]
        verdict = state.run_spec(step_fn, self.params, toks, seq_ids, pos,
                                 key, keep_fixed=keep_fixed,
                                 keep_cap=keep_cap)
        kept = [None] * b
        advanced = [0] * b
        for i, r in enumerate(rows):
            if r is None:
                continue
            chunk = r.get("chunk")
            if chunk is not None:
                m = len(chunk)
                kept[i] = [int(verdict[i, m - 1])] if r["final"] else []
                advanced[i] = m
                continue
            # padding columns never count as accepted (a non-speculative
            # row always keeps exactly its 1 bonus token)
            n_acc = min(int(verdict[i, k]), proposed[i])
            cand = [int(x) for x in verdict[i, :n_acc + 1][:r["limit"]]]
            eos = r["eos"]
            if eos is not None and eos in cand:
                cand = cand[:cand.index(eos) + 1]
            kept[i] = cand
            advanced[i] = len(cand)
            st = r.get("stats")
            if st is not None:
                st.steps += 1
                st.proposed += proposed[i]
                st.accepted += min(len(cand), n_acc)
                st.tokens += len(cand)
        state.end_step(seq_ids, advanced)
        return kept

    def _maybe_save_knees(self):
        if self.knee_cache is not None and api.knees_dirty():
            api.save_knee_cache(self.knee_cache)

    # ------------------------------------------------------------------
    # Static lockstep batch
    # ------------------------------------------------------------------
    def generate(self, requests: list[Request], greedy: bool = True,
                 temperature: float = 1.0, seed: int = 0,
                 free_pages: bool = False) -> list[np.ndarray]:
        """Static lockstep decode. Per-request ``eos_token`` truncates the
        returned tokens (eos inclusive, matching `serve`); the lockstep
        batch still decodes ``max_new_tokens`` steps internally. With a
        pool attached, the batch's pages stay live after the call by
        default (inspectable, reusable across calls); pass
        ``free_pages=True`` for a long-lived engine whose pool must track
        only in-flight work — `serve` always frees."""
        b = len(requests)
        plen = max(len(r.prompt) for r in requests)
        max_new = max(r.max_new_tokens for r in requests)
        cap = plen + max_new
        spec_k, eff_ks = self._resolve_spec(requests)
        prompts = np.zeros((b, plen), np.int32)
        for i, r in enumerate(requests):
            prompts[i, plen - len(r.prompt):] = r.prompt   # left-pad

        t0 = time.perf_counter()
        logits, caches = self._prefill(self.params,
                                       {"tokens": jnp.asarray(prompts)})
        paged = self.kv_pool is not None
        plan = self.plan if (paged and self.decode_mode == "fused") else None
        # a mesh plan decodes n_rows >= b rows so every data shard gets an
        # equal block; the extra rows are seq -1 padding (trash slot)
        n_rows = plan.pad_rows(b) if plan is not None else b
        state = None
        if paged:
            self._require_paged()
            # write the real prefill K/V into the pool (seq id = request
            # index offset by the engine-lifetime counter, so repeated
            # generate() calls never alias an earlier call's pages): full
            # pages placed by the pool's tier policy, the partial
            # remainder buffered until decode fills it
            seq_ids = list(range(self._next_seq, self._next_seq + b))
            self._next_seq += b
            state = self._new_state(cap, batch_hint=n_rows,
                                    tail_slots=2 if spec_k > 1 else 1)
            if plan is not None and plan.dp > 1:
                # pin each sequence to its row's data shard BEFORE any
                # prefill write so its pages land on the shard that
                # decodes it
                for i, seq in enumerate(seq_ids):
                    state.bind_seq(seq, plan.shard_of_row(i, n_rows))
            extract_prefill_pages(self.model, caches, state, seq_ids)
        else:
            caches = pad_caches(self.model, caches, cap, plen)
        self.stats["prefill_s"] += time.perf_counter() - t0

        key = jax.random.PRNGKey(seed)
        outs = [[] for _ in range(b)]
        tok = self._sample(logits, greedy, temperature, key)
        for i in range(b):
            outs[i].append(int(tok[i]))

        observe = getattr(self.kv_pool.policy, "observe", None) \
            if paged else None
        fused = paged and self.decode_mode == "fused"
        spec_stats = [SpecStats() for _ in requests]
        t0 = time.perf_counter()
        if spec_k > 1:
            self._generate_spec(requests, eff_ks, spec_k, state, seq_ids,
                                outs, spec_stats, plen, greedy, temperature,
                                key, observe)
        else:
            step_fn = self._fused_step_fn(state.slots, greedy, temperature) \
                if fused else None
            step_seqs = seq_ids + [-1] * (n_rows - b) if paged else None
            if fused:                   # mesh plan: pad rows feed token 0
                tok = np.zeros(n_rows, np.int32)
                tok[:b] = [o[0] for o in outs]
            for step in range(max_new - 1):
                pos = plen + step
                if paged:
                    hits0 = (self.kv_pool.stats["fast_hits"],
                             self.kv_pool.stats["slow_hits"])
                    g0 = state.gather_s
                    if fused:
                        # one int32 control upload (the tokens ride in
                        # it), one sampled-token download
                        key, sub = jax.random.split(key)
                        tok = tok_host = state.run_fused(
                            step_fn, self.params, tok, step_seqs, pos, sub)
                    else:
                        logits = paged_decode_step(self.model, self.params,
                                                   np.asarray(tok), state,
                                                   seq_ids, pos)
                        key, sub = jax.random.split(key)
                        tok = self._sample(logits, greedy, temperature, sub)
                        tok_host = np.asarray(tok)
                    if observe is not None:
                        observe(state.gather_s - g0,
                                self.kv_pool.stats["fast_hits"] - hits0[0],
                                self.kv_pool.stats["slow_hits"] - hits0[1])
                else:
                    logits, caches = self._decode(
                        self.params, {"tokens": tok[:, None]}, caches,
                        jnp.int32(pos))
                    key, sub = jax.random.split(key)
                    tok = self._sample(logits, greedy, temperature, sub)
                    tok_host = np.asarray(tok)
                for i in range(b):
                    outs[i].append(int(tok_host[i]))
                self.stats["decode_steps"] += 1
        self.stats["decode_s"] += time.perf_counter() - t0
        if paged:
            # counter snapshot only — holding the state itself would pin
            # the batch's device pool arrays for the engine's lifetime
            self.last_transfers = state.transfer_counts()
            if free_pages:
                for seq in seq_ids:
                    state.free_seq(seq)
        self._maybe_save_knees()

        def trim(o, r):
            o = o[:r.max_new_tokens]
            if r.eos_token is not None and r.eos_token in o:
                o = o[:o.index(r.eos_token) + 1]   # eos inclusive, as serve
            return np.array(o)

        results = [trim(o, r) for o, r in zip(outs, requests)]
        # count what was actually produced per request (the lockstep batch
        # itself runs max(max_new) - 1 steps; padded rows and post-eos
        # tokens are not "tokens served") — matches serve()'s accounting
        self.stats["tokens"] += sum(len(o) for o in results)
        self.last_request_stats = []
        for res, st in zip(results, spec_stats):
            if st.steps == 0:               # non-speculative lockstep rows
                st.steps = max(1, max_new - 1)
                st.tokens = max(0, len(res) - 1)
            d = st.as_dict()
            d["tokens"] = len(res)          # eos-trimmed, prefill token incl.
            self.last_request_stats.append(d)
        return results

    def _generate_spec(self, requests, eff_ks, spec_k, state, seq_ids,
                       outs, spec_stats, plen, greedy, temperature, key,
                       observe):
        """Static-batch speculative decode loop: rows advance at their own
        accept rates (no lockstep), finished rows turn into seq -1 padding
        until every row has reached its max_new/eos."""
        step_fn = self._fused_step_fn(state.slots, greedy, temperature,
                                      k=spec_k)
        hist = [np.concatenate([np.asarray(r.prompt, np.int32),
                                np.asarray(o, np.int32)])
                for r, o in zip(requests, outs)]

        def is_done(i):
            r = requests[i]
            return (len(outs[i]) >= r.max_new_tokens
                    or (r.eos_token is not None
                        and outs[i][-1] == r.eos_token))

        done = [is_done(i) for i in range(len(requests))]
        while not all(done):
            rows = []
            for i, r in enumerate(requests):
                if done[i]:
                    rows.append(None)
                    continue
                rows.append({"seq": seq_ids[i], "last": outs[i][-1],
                             "history": hist[i],
                             "pos": plen + len(outs[i]) - 1,
                             "eff_k": eff_ks[i],
                             "limit": r.max_new_tokens - len(outs[i]),
                             "eos": r.eos_token, "stats": spec_stats[i]})
            # mesh plan: pad to the equal-block row count (seq -1 rows)
            rows.extend([None] * (state.batch_hint - len(rows)))
            hits0 = (self.kv_pool.stats["fast_hits"],
                     self.kv_pool.stats["slow_hits"])
            g0 = state.gather_s
            key, sub = jax.random.split(key)
            kept = self._fused_step(state, step_fn, spec_k, rows, sub)
            self.stats["decode_steps"] += 1
            if observe is not None:
                observe(state.gather_s - g0,
                        self.kv_pool.stats["fast_hits"] - hits0[0],
                        self.kv_pool.stats["slow_hits"] - hits0[1])
            for i in range(len(requests)):
                if rows[i] is None:
                    continue
                outs[i].extend(kept[i])
                hist[i] = np.concatenate(
                    [hist[i], np.asarray(kept[i], np.int32)])
                done[i] = is_done(i)

    # ------------------------------------------------------------------
    # Continuous batching
    # ------------------------------------------------------------------
    def serve(self, requests: list[Request], max_active: int = 4,
              greedy: bool = True, temperature: float = 1.0, seed: int = 0,
              prefix_cache: bool = True, metrics=None,
              chunked_prefill: Optional[bool] = None,
              prefill_budget: int = 1,
              radix: Optional[bool] = None,
              preempt: bool = True,
              preempt_policy=None) -> list[np.ndarray]:
        """Continuous-batching decode: requests join free rows mid-flight
        and retire at their own lengths; finished requests' pages are
        freed. Returns outputs in submission order. Greedy outputs match
        ``generate([request])`` per request token-for-token (absent
        fast-tier eviction pressure — demotion quantizes shared content).

        A request whose worst-case page need can NEVER fit the pool is
        rejected structurally instead of aborting the workload: its slot
        in the returned list is ``None``, its `Admission` verdict (reason
        + pages needed vs. budget) lands in ``last_rejections`` and its
        ``last_request_stats`` entry carries ``rejected=<reason>``. The
        underlying stepper is `ServeSession` (shared with the async
        streaming front end, `serve.frontend.AsyncServeFrontend`).
        """
        if not requests:
            self.last_rejections = []
            return []
        self._require_paged()
        spec_k, _ = self._resolve_spec(requests)
        order = {id(r): i for i, r in enumerate(requests)}
        if len(order) != len(requests):
            raise ValueError("duplicate Request objects in one serve() call")
        cap = max(len(r.prompt) + r.max_new_tokens for r in requests)
        session = ServeSession(self, capacity=cap, max_active=max_active,
                               speculate=spec_k, greedy=greedy,
                               temperature=temperature, seed=seed,
                               prefix_cache=prefix_cache, metrics=metrics,
                               chunked_prefill=chunked_prefill,
                               prefill_budget=prefill_budget, radix=radix,
                               preempt=preempt,
                               preempt_policy=preempt_policy)
        self.last_rejections = []
        for r in requests:
            verdict = session.submit(r)
            self.last_rejections.append(None if verdict else verdict)
        while not session.done:
            session.step()
        self.last_peak_active = session.sched.peak_active
        self.last_transfers = session.state.transfer_counts()
        self.last_prefix_hit_rate = session.prefix_hit_rate
        self.last_request_stats = [session.request_stats(r)
                                   for r in requests]
        session.close()    # drop radix pins: the pool tracks live work
        self._maybe_save_knees()
        return [session.result(r) for r in requests]

    @staticmethod
    def _sample(logits, greedy, temperature, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature,
                                      axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Step-granular continuous batching: the resumable serving core
# ---------------------------------------------------------------------------
class SwapInError(RuntimeError):
    """A parked sequence's host pages could not be restored to the device
    (injected via ``REPRO_SERVE_FAULT=swap_fail:p`` for testing). The
    session converts it into a structured per-request error event — the
    victim's pages free, the rest of the batch is untouched."""


class StreamEvent:
    """Per-request outcome of one `ServeSession.step`: the tokens the
    request emitted this step (the admission prefill token included) and
    whether it just finished. The streamed tokens are already eos/max_new
    clamped — concatenating a request's events reproduces its final
    output exactly. ``error`` names a structured mid-flight failure
    (e.g. ``"swap_fail"``) on a terminal event; the tokens streamed
    before it stand as the partial result."""

    __slots__ = ("request", "tokens", "done", "error")

    def __init__(self, request: Request, tokens: list, done: bool = False,
                 error: Optional[str] = None):
        self.request, self.tokens, self.done = request, tokens, done
        self.error = error


class _SessionRec:
    """One request's lifecycle record inside a `ServeSession`."""

    __slots__ = ("req", "status", "admission", "active", "row", "result",
                 "stats", "metrics")

    def __init__(self, req: Request, admission: Admission, metrics):
        self.req = req
        self.admission = admission
        self.metrics = metrics
        # waiting|active|preempted|done|cancelled|rejected|error
        self.status = "waiting"
        self.active: Optional[_Active] = None
        self.row = -1
        self.result: Optional[np.ndarray] = None
        self.stats: Optional[dict] = None


def _free_device_bytes():
    """Bytes the default device reports free, or None where it reports
    no memory statistics (the CPU)."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


class ServeSession:
    """Resumable, step-granular continuous-batching loop — the serving
    core that both `ServeEngine.serve` (closed batch) and the async
    streaming front end (`serve.frontend.AsyncServeFrontend`) drive.

    ``submit`` queues a request and returns a structured `Admission`
    verdict — a request that can never fit is rejected without touching
    the rest of the workload. ``step`` runs one admission round plus one
    fused decode step over the live rows and returns per-request
    `StreamEvent`s. ``cancel`` retires a request mid-decode: its row and
    page reservations free immediately, its pool pages drop their refs,
    and the tokens streamed so far become its (partial) result.

    ``capacity`` (in tokens) sizes the page table once for the session's
    lifetime — a longer request is rejected with reason ``capacity``.
    ``speculate`` fixes the verify-graph width; a request whose
    per-request k exceeds it is rejected with reason ``speculate``.
    Pass a `serve.metrics.MetricsRegistry` as ``metrics`` to collect
    queue-wait / TTFT / per-token latencies per request."""

    def __init__(self, engine: ServeEngine, capacity: int,
                 max_active: int = 4, speculate: Optional[int] = None,
                 greedy: bool = True, temperature: float = 1.0,
                 seed: int = 0, prefix_cache: bool = True, metrics=None,
                 chunked_prefill: Optional[bool] = None,
                 prefill_budget: int = 1, radix: Optional[bool] = None,
                 preempt: bool = True, preempt_policy=None):
        engine._require_paged()
        k = max(1, engine.speculate if speculate is None else int(speculate))
        engine._check_spec_width(k)
        self.engine = engine
        self.pool = engine.kv_pool
        self.capacity = int(capacity)
        self.spec_k = k
        self.max_active = max_active
        self.greedy, self.temperature = greedy, float(temperature)
        self.prefix_cache = prefix_cache
        self.metrics = metrics
        fused = engine.decode_mode == "fused"
        # chunked prefill streams prompt suffixes through the widened
        # fused verify graph in page-sized chunks riding the decode batch
        # (None -> on for the fused mode); eager keeps the monolithic
        # reference prefill
        if chunked_prefill and not fused:
            raise ValueError(
                f"chunked prefill rides the fused verify graph; "
                f"decode_mode={engine.decode_mode!r} stays monolithic")
        hybrid = engine._hybrid
        if hybrid and chunked_prefill is not None and not chunked_prefill:
            # the monolithic session prefill right-pads its bucket, which
            # a recurrent scan cannot ignore — hybrid stacks stream their
            # prompts through the chunked path unconditionally
            raise ValueError(
                f"{engine.cfg.name}: recurrent/ring stacks prefill through "
                f"chunked prefill only; drop chunked_prefill=False")
        self.chunked = fused if chunked_prefill is None \
            else bool(chunked_prefill)
        self.prefill_budget = max(1, int(prefill_budget))
        # radix prefix tree: pins completed prompts' pages so later
        # requests adopt cached prefixes (adoption itself needs the
        # chunked path; with chunked off the tree still pins/credits and
        # the pool dedups by content hash). A recurrent stack cannot
        # adopt: its per-sequence state is not content-addressable.
        self.radix = False if hybrid else \
            (bool(prefix_cache) if radix is None else bool(radix))
        if hybrid:
            self.prefix_cache = prefix_cache = False
        plan = engine.plan
        # under a mesh plan the decode batch carries an equal block of
        # rows per data shard; admission fills rows (and page budget)
        # per shard, so max_active rounds up to a multiple of dp
        n_rows = plan.pad_rows(max_active) if plan is not None \
            else max_active
        dp = plan.dp if plan is not None else 1
        self.prefix_index = RadixPrefixCache(
            self.pool, engine.cfg.num_layers, shards=dp,
            on_release=self._release_pinned) if self.radix else None
        self.sched = Scheduler(self.pool, engine.cfg.num_layers,
                               max_active=max_active,
                               default_speculate=engine.speculate,
                               data_shards=dp,
                               rows_per_shard=n_rows // dp,
                               prefix_index=self.prefix_index,
                               layout=engine._layout())
        # a chunk-fill step reuses the spill-slot protocol (decode rows
        # riding a wide step may cross their page boundary), so chunked
        # sessions need the second tail slot even at k == 1
        self.state = engine._new_state(
            self.capacity, batch_hint=n_rows,
            tail_slots=2 if (k > 1 or self.chunked) else 1)
        if k > 1:
            self._check_checkpoints(max(k, self.pool.page_tokens)
                                    if self.chunked else k, n_rows)
        # prefix-cache hit accounting (pages adopted / adoptable pages)
        self.pages_adopted_total = 0
        self.pages_needed_total = 0
        self._rows: list[Optional[_Active]] = [None] * n_rows
        self._recs: dict[int, _SessionRec] = {}
        self._key = jax.random.PRNGKey(seed)
        self._observe = getattr(self.pool.policy, "observe", None)
        self._fused = fused
        self.steps = 0
        self.peak_live_pages = 0
        # SLO-aware preemption: when the admission round leaves a
        # strictly-more-urgent head blocked, park an eligible active row
        # (swap its KV to the host tier) to free a seat. Eligibility is
        # the scheduler's deterministic rule; the policy only ranks.
        self.preempt_enabled = bool(preempt)
        self.preempt_policy = preempt_policy if preempt_policy is not None \
            else LRUVictimPolicy()
        self._preempt_observe = getattr(self.preempt_policy, "observe",
                                        None)
        self.preemptions = 0      # rows parked to the host tier
        self.resumes = 0          # parked rows re-placed
        self._step_misses = 0     # deadline misses since last policy reward
        self._pending_events: list[StreamEvent] = []
        # fault injection (tests): REPRO_SERVE_FAULT=swap_fail:p makes a
        # resume's swap-in fail with probability p — the victim surfaces
        # a structured error event, the batch keeps decoding
        self._fault: Optional[tuple[str, float]] = None
        fault = os.environ.get("REPRO_SERVE_FAULT")
        if fault:
            kind, _, p = fault.partition(":")
            self._fault = (kind, float(p) if p else 1.0)
        self._fault_rng = np.random.default_rng(seed ^ 0x5EED)
        self._debug = bool(os.environ.get("REPRO_SERVE_DEBUG"))

    def _check_checkpoints(self, k: int, rows: int):
        """Speculative verify over recurrent layers holds k candidate
        states of every recurrent layer for every row until the accept
        rule picks one; refuse a session whose checkpoints cannot fit
        the device memory left beside its weights and state."""
        lay = self.engine._layout()
        plan = self.engine.plan
        need = k * rows * lay.rec_state_bytes()
        if plan is not None:
            need //= plan.dp * plan.tp
        free = _free_device_bytes()
        if need and free is not None and need > free:
            raise ValueError(
                f"{self.engine.cfg.name}: speculative verify {k} tokens "
                f"wide over {rows} rows holds {need} bytes of recurrent-"
                f"state checkpoints per device ({k} x "
                f"{lay.rec_state_bytes()} bytes a row), more than the "
                f"{free} bytes the device has free; serve it with "
                f"speculate <= 1")

    # -- lifecycle ----------------------------------------------------------
    @property
    def done(self) -> bool:
        """True when nothing is waiting and no decode row is occupied."""
        return self.sched.done

    @property
    def queue_depth(self) -> int:
        return len(self.sched.waiting)

    @property
    def n_active(self) -> int:
        return sum(a is not None for a in self._rows)

    def submit(self, req: Request) -> Admission:
        """Queue a request (FIFO). Returns the structured admission
        verdict; on rejection the request is fully accounted (result
        ``None``, stats carry the reason) but never does work."""
        if id(req) in self._recs:
            raise ValueError("Request object already submitted to this "
                             "session")
        t = self.pool.page_tokens
        tail = 2 if (self.spec_k > 1 or self.chunked) else 1
        need_tokens = len(req.prompt) + req.max_new_tokens
        lay = self.engine._layout()
        pages = -(-need_tokens // t)
        if lay.has_ring:                # ring layers recycle: O(window)
            pages = min(pages, lay.ring_pages())
        eff_k = effective_speculate(req, self.engine.speculate)
        if lay.n_kv and pages + tail > self.state.slots:
            verdict = Admission(
                False, reason="capacity",
                pages_needed=lay.pages_needed(need_tokens,
                                              tail_slots=tail),
                pages_budget=self.sched._budget(),
                detail=f"request spans {need_tokens} KV tokens = {pages} "
                       f"pages + {tail} tail slot(s), beyond the session "
                       f"page table of {self.state.slots} slots "
                       f"({self.state.slots * t} tokens); raise the "
                       f"session capacity")
        elif eff_k > self.spec_k:
            verdict = Admission(
                False, reason="speculate",
                detail=f"request speculates {eff_k} tokens/step but the "
                       f"session verify graph is {self.spec_k} wide")
        else:
            verdict = self.sched.submit(req)
        m = self.metrics.submit() if self.metrics is not None else None
        if m is not None:
            m.deadline_s = req.deadline
        rec = _SessionRec(req, verdict, m)
        self._recs[id(req)] = rec
        if not verdict:
            rec.status = "rejected"
            rec.stats = {"rejected": verdict.reason, "tokens": 0,
                         **verdict.as_dict()}
            if m is not None:
                m.on_reject(verdict.reason)
        return verdict

    def cancel(self, req: Request) -> bool:
        """Cancel a submitted request: a waiting one leaves the queue; an
        active one retires — its row and reservation free immediately and
        its pool pages drop their refs (prefix-shared pages survive via
        other holders). The tokens streamed so far become its partial
        result. Returns False if it already finished/was never
        submitted."""
        rec = self._recs.get(id(req))
        if rec is None or rec.status in ("done", "cancelled", "rejected",
                                         "error"):
            return False
        outs: list = []
        stats = SpecStats()
        if rec.status == "waiting":
            self.sched.remove_waiting(req)
        elif rec.status == "preempted":
            # a swapped-out sequence: it sits in the waiting queue
            # (parked) and holds no row — free its host-tier pages and
            # parked tail, drop the scheduler's parked bookkeeping
            act = rec.active
            outs, stats = act.outs, act.stats
            self.sched.remove_waiting(req)
            self.state.free_seq(act.seq)
        else:
            act = rec.active
            outs, stats = act.outs, act.stats
            self.state.free_seq(act.seq)
            self._rows[rec.row] = None
            self.sched.retire(req)
        rec.status = "cancelled"
        rec.active = None
        rec.result = np.array(outs[:req.max_new_tokens], np.int64)
        d = stats.as_dict()
        d["tokens"] = len(rec.result)
        d["cancelled"] = True
        rec.stats = d
        if rec.metrics is not None:
            rec.metrics.on_cancel()
        return True

    def result(self, req: Request) -> Optional[np.ndarray]:
        """Final (or partial, if cancelled) output tokens; None while the
        request is still queued/decoding, and None forever if rejected."""
        rec = self._recs.get(id(req))
        return None if rec is None else rec.result

    def request_stats(self, req: Request) -> Optional[dict]:
        rec = self._recs.get(id(req))
        return None if rec is None else rec.stats

    def admission(self, req: Request) -> Optional[Admission]:
        rec = self._recs.get(id(req))
        return None if rec is None else rec.admission

    def transfer_counts(self) -> tuple[int, int]:
        return self.state.transfer_counts()

    def _release_pinned(self, pid: int):
        # radix-tree unpin destroyed a pool page: recycle its device slot
        self.state.release_page(pid)

    @property
    def prefix_hit_rate(self) -> Optional[float]:
        """Pages adopted / adoptable prompt pages across the session's
        chunked admissions; None before any chunked admission."""
        if self.pages_needed_total == 0:
            return None
        return self.pages_adopted_total / self.pages_needed_total

    def close(self):
        """Release the session's cross-request state: unpin every radix
        tree node (pages whose last holder was the tree are destroyed and
        their device slots recycled), so a drained, closed session leaves
        ``pool.live_pages == 0``."""
        if self.prefix_index is not None:
            self.prefix_index.clear()

    # -- the step -----------------------------------------------------------
    def _finish(self, rec: _SessionRec):
        act = rec.active
        if rec.req.deadline is not None and self.sched.overdue(rec.req):
            # finished past its SLO: feeds the preemption policy's
            # per-step miss penalty (the learned victim ranking)
            self._step_misses += 1
        self.state.free_seq(act.seq)
        self._rows[rec.row] = None
        self.sched.retire(rec.req)
        rec.status = "done"
        rec.active = None
        rec.result = np.array(act.outs[:rec.req.max_new_tokens], np.int64)
        d = act.stats.as_dict()
        d["tokens"] = len(rec.result)   # eos-trimmed, prefill token incl.
        rec.stats = d
        if rec.metrics is not None:
            rec.metrics.on_finish(len(rec.result),
                                  accept_rate=d.get("accept_rate"))

    # -- preemption / resume ------------------------------------------------
    def preempt(self, req: Request) -> bool:
        """Park an active request: its KV pages swap to the host tier,
        its row and reservation free for more urgent work, and it
        re-enters the waiting queue at its urgency position. Resuming
        (automatic at a later admission round, or explicit via `resume`)
        restores the pages bit-identically, so its greedy output is
        token-for-token what the never-preempted run produces. Returns
        False unless the request is currently active."""
        rec = self._recs.get(id(req))
        if rec is None or rec.status != "active":
            return False
        self._preempt_rec(rec)
        return True

    def resume(self, req: Request) -> bool:
        """Explicitly un-park a preempted request now (the admission loop
        also resumes parked requests by urgency order on its own).
        Returns False if it is not parked or its shard has no free
        row/page headroom yet."""
        rec = self._recs.get(id(req))
        if rec is None or rec.status != "preempted":
            return False
        if not self.sched.try_resume(req):
            return False
        return self._place_resumed(rec, self._pending_events)

    def _preempt_rec(self, rec: _SessionRec):
        act = rec.active
        self.state.swap_out(act.seq)
        self._rows[rec.row] = None
        rec.row = -1
        rec.status = "preempted"
        self.sched.preempt(rec.req)
        self.preemptions += 1
        if rec.metrics is not None:
            rec.metrics.on_preempt()

    def _place_resumed(self, rec: _SessionRec, events: list) -> bool:
        """Give a just-re-reserved parked request a decode row back and
        swap its pages in. A failed swap-in (fault injection) surfaces as
        a structured terminal error event: the scheduler reservation and
        every page the victim held free, nothing else in the batch is
        touched."""
        req, act = rec.req, rec.active
        shard = self.sched.assigned_shard(req)
        rps = len(self._rows) // self.sched.data_shards
        row_i = next(i for i in range(shard * rps, (shard + 1) * rps)
                     if self._rows[i] is None)
        try:
            if self._fault is not None and self._fault[0] == "swap_fail" \
                    and self._fault_rng.random() < self._fault[1]:
                # fires BEFORE any state mutation: the sequence is still
                # cleanly parked, so free_seq below releases exactly its
                # host pages + parked tail
                raise SwapInError(
                    f"injected swap-in fault for seq {act.seq}")
            self.state.swap_in(act.seq)
        except SwapInError as e:
            self.sched.retire(req)
            self.state.free_seq(act.seq)
            rec.status = "error"
            rec.active = None
            rec.result = np.array(act.outs[:req.max_new_tokens], np.int64)
            d = act.stats.as_dict()
            d["tokens"] = len(rec.result)
            d["error"] = "swap_fail"
            d["detail"] = str(e)
            rec.stats = d
            if rec.metrics is not None:
                rec.metrics.on_error("swap_fail")
            events.append(StreamEvent(req, [], done=True,
                                      error="swap_fail"))
            return False
        self._rows[row_i] = act
        rec.row = row_i
        rec.status = "active"
        self.resumes += 1
        if rec.metrics is not None:
            rec.metrics.on_resume()
        return True

    def _maybe_preempt(self) -> bool:
        """One preemption pass after a blocked admission round: if the
        waiting head strictly outranks some active row (scheduler's
        deterministic eligibility), ask the policy which eligible victim
        to park and park it. Returns True when a row was freed (the
        caller re-runs admission). Candidates shrink every pass, so the
        admit/preempt loop terminates."""
        if not self.preempt_enabled:
            return False
        sched = self.sched
        head = sched.head_blocked()
        if head is None:
            return False
        # a parked head can only resume on its own shard — victims on
        # other shards free nothing it can use
        need_shard = sched.assigned_shard(head) if sched.is_parked(head) \
            else None
        cands = [rec for rec in self._recs.values()
                 if rec.status == "active"
                 and sched.preempts(head, rec.req)
                 and (need_shard is None
                      or sched.assigned_shard(rec.req) == need_shard)]
        if not cands:
            return False
        now = sched._clock()

        def slack(r):
            if r.deadline is None:
                return None
            sub = sched._submit_s.get(id(r))
            return None if sub is None else sub + r.deadline - now

        views = []
        for rec in cands:
            act = rec.active
            views.append(RequestView(
                priority=rec.req.priority,
                deadline_slack_s=slack(rec.req),
                tokens_done=len(act.outs),
                tokens_left=rec.req.max_new_tokens - len(act.outs),
                prefilling=act.prefilling,
                pages=len(self.pool.seq_pages(act.seq)),
                admit_seq=sched._order.get(id(rec.req), 0)))
        head_view = RequestView(
            priority=head.priority, deadline_slack_s=slack(head),
            tokens_left=head.max_new_tokens,
            queue_depth=len(sched.waiting))
        pick = self.preempt_policy.pick(head_view, views)
        if pick is None:
            return False
        self._preempt_rec(cands[pick])
        return True

    def _reject_late(self, events: list):
        """Surface scheduler late rejections: a queue head that can never
        fit even after full pin eviction, a head whose deadline expired
        while it waited, or a parked request no batch can re-host. A
        never-admitted request is accounted like a submit-time rejection;
        a shed *parked* one already did work — its swapped pages free and
        it terminates as a structured error with its partial result."""
        for req, verdict in self.sched.late_rejections:
            rec = self._recs[id(req)]
            rec.admission = verdict
            if rec.active is not None:       # shed while parked
                act = rec.active
                self.state.free_seq(act.seq)
                rec.status = "error"
                rec.active = None
                rec.result = np.array(act.outs[:req.max_new_tokens],
                                      np.int64)
                d = act.stats.as_dict()
                d["tokens"] = len(rec.result)
                d["error"] = verdict.reason
                d.update(verdict.as_dict())
                rec.stats = d
                if rec.metrics is not None:
                    rec.metrics.on_error(verdict.reason)
                events.append(StreamEvent(req, [], done=True,
                                          error=verdict.reason))
                continue
            rec.status = "rejected"
            rec.stats = {"rejected": verdict.reason, "tokens": 0,
                         **verdict.as_dict()}
            if rec.metrics is not None:
                rec.metrics.on_reject(verdict.reason)
            events.append(StreamEvent(req, [], done=True,
                                      error=verdict.reason))
        self.sched.late_rejections.clear()

    def _admit(self, events: list):
        eng = self.engine
        while True:
            # loop: an admitted request finishing at its very first token
            # frees its row + reservation, unblocking the queue head
            # again; a blocked round may park an eligible active row
            # (preemption) and retry
            batch = self.sched.admit()
            self._reject_late(events)
            if not batch:
                if self._maybe_preempt():
                    continue
                return
            for req in batch:
                rec = self._recs[id(req)]
                if rec.status == "preempted":
                    # a parked request the scheduler just re-reserved:
                    # swap its pages back in and rejoin mid-decode
                    self._place_resumed(rec, events)
                    continue
                seq = eng._next_seq
                eng._next_seq += 1
                # the scheduler picked the request's data shard at admit();
                # choose its row inside that shard's block and bind the
                # sequence BEFORE the prefill writes, so its pages land on
                # the shard that will decode it
                shard = self.sched.assigned_shard(req)
                rps = len(self._rows) // self.sched.data_shards
                row_i = next(i for i in range(shard * rps, (shard + 1) * rps)
                             if self._rows[i] is None)
                self.state.bind_seq(seq, shard)
                toks = np.asarray(req.prompt, np.int32)
                plen = len(toks)
                act = _Active(req, seq, plen, [],
                              eff_k=effective_speculate(req, eng.speculate))
                if self.chunked:
                    # adopt the radix-cached prefix (the exact pages the
                    # admission gate credited) and queue the suffix for
                    # page-sized chunk fills riding the decode steps —
                    # no prefill work happens at admission time
                    hashes = self.sched._prompt_hashes(req) \
                        if self.radix else \
                        (prefix_page_hashes(toks, self.pool.page_tokens)
                         if self.prefix_cache else [])
                    match = self.sched.take_match(req) \
                        if self.radix else None
                    adopted = match.pages if match is not None else 0
                    t = self.pool.page_tokens
                    self.state.adopt_prefix(
                        seq, match.groups if match is not None else (),
                        pending_hashes=hashes[adopted:])
                    act.pending = toks[adopted * t:]
                    act.prefilled = adopted * t
                    act.hashes = hashes
                    self.pages_adopted_total += adopted
                    self.pages_needed_total += self.sched.adopt_cap(req)
                    self._rows[row_i] = act
                    rec.active, rec.row, rec.status = act, row_i, "active"
                    if rec.metrics is not None:
                        rec.metrics.on_admit()
                    continue
                t0 = time.perf_counter()
                # right-pad to a power-of-two bucket: bounded compile
                # count across prompt lengths, exact prefix under the
                # causal mask
                bucket = 8
                while bucket < plen:
                    bucket *= 2
                padded = np.zeros(bucket, np.int32)
                padded[:plen] = toks
                logits_all, caches = eng._prefill_all(
                    eng.params, {"tokens": jnp.asarray(padded[None])})
                logits = logits_all[:, plen - 1]
                want_hashes = self.prefix_cache or self.radix
                hashes = ([prefix_page_hashes(toks, self.pool.page_tokens)]
                          if want_hashes else None)
                # adopt the radix-cached prefix pages by reference (the
                # prefill compute still runs full-length for the logits,
                # but the cached pages are never re-written — they stay
                # tree-shared instead of merely content-deduped)
                match = self.sched.take_match(req) if self.radix else None
                adopted = match.pages if match is not None else 0
                if adopted:
                    self.state.adopt_prefix(seq, match.groups)
                    self.pages_adopted_total += adopted
                self.pages_needed_total += self.sched.adopt_cap(req)
                extract_prefill_pages(eng.model, caches, self.state, [seq],
                                      page_hashes=hashes, valid_len=plen,
                                      skip_pages=[adopted])
                if self.radix and hashes:
                    # pin the completed prompt's full pages so later
                    # requests are credited for (and, chunked, adopt) them
                    self.prefix_index.insert(hashes[0], shard)
                eng.stats["prefill_s"] += time.perf_counter() - t0
                self._key, sub = jax.random.split(self._key)
                tok = int(eng._sample(logits, self.greedy, self.temperature,
                                      sub)[0])
                eng.stats["tokens"] += 1
                act.outs.append(tok)
                self._rows[row_i] = act
                rec.active, rec.row, rec.status = act, row_i, "active"
                if rec.metrics is not None:
                    rec.metrics.on_admit()
                    rec.metrics.on_tokens(1)
                done = act.finished
                if done:
                    self._finish(rec)
                events.append(StreamEvent(req, [tok], done=done))

    def step(self) -> list[StreamEvent]:
        """One admission round + one decode step over the live rows.
        Returns the per-request token events (admission prefill tokens
        included); an idle session returns an empty list.

        When chunked-prefill rows are live, the step widens to
        ``max(spec_k, page_tokens)`` columns: up to ``prefill_budget``
        chunk rows stream one prompt page each through the verify graph
        while every decode row keeps decoding in the same fused launch —
        long prompts admit page-by-page without stalling in-flight
        requests.

        The step is the span ``serve.step`` (`serve.tracing`), with the
        counts ``live`` (rows holding a request), ``wide`` (1 if a prompt
        chunk widened it), ``tokens`` (tokens fed that the rows kept) and
        ``prompt`` (of which prompt tokens)."""
        with tracing.span("serve.step", step_num=self.steps) as sp:
            events: list[StreamEvent] = list(self._pending_events)
            self._pending_events.clear()
            with tracing.span("serve.admit"):
                self._admit(events)
            rows = self._rows
            if all(a is None for a in rows):
                # unreachable: submit() rejects instead
                if not self.sched.done:
                    raise RuntimeError("scheduler stalled with waiting "
                                       "requests and no active rows")
                sp.set(live=0, wide=0, tokens=0, prompt=0)
                return events
            eng, pool, state = self.engine, self.pool, self.state
            t = pool.page_tokens
            chunk_rows: dict[int, tuple[int, bool]] = {}   # row -> (m, final)
            wide = any(a is not None and a.prefilling for a in rows)
            t0 = time.perf_counter()
            hits0 = (pool.stats["fast_hits"], pool.stats["slow_hits"])
            g0 = state.gather_s
            if self._fused:
                # one fused step k tokens wide (k = 1: plain decode): up
                # to k rows per live request, mixed freely with eff_k=1
                # (plain) rows and prefill chunk rows; the tokens ride in
                # the control block
                k = max(self.spec_k, t) if wide else self.spec_k
                step_fn = eng._fused_step_fn(state.slots, self.greedy,
                                             self.temperature, k=k,
                                             drafts=self.spec_k > 1)
                budget = self.prefill_budget
                srows: list[Optional[dict]] = []
                for act in rows:
                    if act is None:
                        srows.append(None)
                        continue
                    if act.prefilling:
                        if budget <= 0:
                            srows.append(None)   # over budget: wait a step
                            continue
                        budget -= 1
                        # fill to the page boundary, never across it: one
                        # chunk completes at most one page, so the fill path
                        # in end_step sees whole pages exactly as decode does
                        m = min(t - act.prefilled % t, len(act.pending))
                        final = m == len(act.pending)
                        chunk_rows[len(srows)] = (m, final)
                        srows.append({"seq": act.seq, "pos": act.prefilled,
                                      "chunk": act.pending[:m],
                                      "final": final})
                        continue
                    row = {"seq": act.seq, "last": act.outs[-1],
                           "pos": act.pos, "eff_k": act.eff_k,
                           "limit": act.req.max_new_tokens - len(act.outs),
                           "eos": act.req.eos_token, "stats": act.stats}
                    if act.eff_k > 1:   # only a drafting row reads history
                        row["history"] = np.concatenate(
                            [np.asarray(act.req.prompt, np.int32),
                             np.asarray(act.outs, np.int32)])
                    srows.append(row)
                self._key, sub = jax.random.split(self._key)
                kept = eng._fused_step(state, step_fn, k, srows, sub)
            else:
                n_rows = len(rows)
                pos = np.zeros(n_rows, np.int32)
                seq_ids = [-1] * n_rows
                tokens = np.zeros(n_rows, np.int32)
                for i, act in enumerate(rows):
                    if act is not None:
                        pos[i], seq_ids[i] = act.pos, act.seq
                        tokens[i] = act.outs[-1]
                logits = paged_decode_step(eng.model, eng.params, tokens,
                                           state, seq_ids, pos)
                self._key, sub = jax.random.split(self._key)
                sampled = eng._sample(logits, self.greedy,
                                      self.temperature, sub)
                with tracing.span("serve.device_wait"):
                    toks = np.asarray(sampled)
                kept = [None] * n_rows
                for i, act in enumerate(rows):
                    if act is not None:
                        kept[i] = [int(toks[i])]
                        act.stats.steps += 1
                        act.stats.tokens += 1
            dt = time.perf_counter() - t0
            eng.stats["decode_s"] += dt
            eng.stats["decode_steps"] += 1
            self.steps += 1
            self.sched.observe_step(dt)   # service-rate EMA (deadline sheds)
            if self._observe is not None:
                self._observe(state.gather_s - g0,
                              pool.stats["fast_hits"] - hits0[0],
                              pool.stats["slow_hits"] - hits0[1])
            live = sum(a is not None for a in rows)
            with tracing.span("serve.deliver"):
                fed = prompt = 0
                for i, act in enumerate(rows):
                    if act is None:
                        continue
                    rec = self._recs[id(act.req)]
                    if i in chunk_rows:
                        m, final = chunk_rows[i]
                        fed += m
                        prompt += m
                        act.prefilled += m
                        act.pending = act.pending[m:]
                        if not final:
                            continue    # mid-prefill: nothing to stream yet
                        tok = int(kept[i][0])    # first generated token
                        act.outs.append(tok)
                        act.pending = None
                        eng.stats["tokens"] += 1
                        if self.radix and act.hashes:
                            # prompt fully resident: pin its full pages so
                            # later requests adopt them
                            self.prefix_index.insert(
                                act.hashes, self.sched.assigned_shard(act.req))
                        if rec.metrics is not None:
                            rec.metrics.on_tokens(1)
                        done = act.finished
                        if done:
                            self._finish(rec)
                        events.append(StreamEvent(act.req, [tok], done=done))
                        continue
                    if kept[i] is None:   # over-budget prefill row idled
                        continue
                    new = [int(x) for x in kept[i]]
                    act.outs.extend(new)
                    fed += len(new)
                    eng.stats["tokens"] += len(new)
                    if rec.metrics is not None:
                        rec.metrics.on_tokens(len(new))
                    done = act.finished
                    if done:
                        self._finish(rec)
                    events.append(StreamEvent(act.req, new, done=done))
            sp.set(live=live, wide=int(wide), tokens=fed, prompt=prompt)
            if self._preempt_observe is not None:
                # per-step reward for the learned victim ranking: decode
                # latency + the deadline misses the finishes above counted
                self._preempt_observe(dt, self._step_misses)
                self._step_misses = 0
            if self._debug:     # REPRO_SERVE_DEBUG: per-step pool invariants
                pins = self.prefix_index.pin_counts() \
                    if self.prefix_index is not None else None
                pool.check_invariants(pins=pins)
                state.check_invariants()
                state._device.check_invariants()
            self.peak_live_pages = max(self.peak_live_pages, pool.live_pages)
            return events
