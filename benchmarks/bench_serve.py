"""Serve-layer benchmarks: per-token decode latency + host-sync counts
across the two decode modes, steady-state host bookkeeping, and
continuous-batching throughput.

The headline suite decodes the same batch through ``fused`` (one jitted
device-resident graph per token) and ``eager`` (per-layer reference: ~2
host crossings per layer per token), reporting per-token latency and the
explicit host<->device transfer count per token
(`PagedKVState.transfer_counts`). The acceptance bar is fused beating
eager on per-token latency with a depth-independent transfer count
(~2/token). Note interpret-mode Pallas on CPU charges every kernel for
total operand size, which *understates* the fused path's advantage: on
real hardware eager pays per-layer dispatch + round-trip latency the
fused graph never sees."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.runmeta import mesh_from_env, run_metadata
from repro.configs import smoke_config
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import PagedKVPool
from repro.serve.metrics import toks_per_s, us_per

PLEN = 64          # multiple of PAGE_TOKENS: prefill emits only full pages
NEW = 12
PAGE_TOKENS = 8
SEED = 0


def _reqs(cfg, n, seed=0, new=NEW):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, cfg.vocab_size, PLEN).astype(np.int32),
                    new) for _ in range(n)]


def run():
    cfg = smoke_config("starcoder2-7b")
    params = None
    rows = []
    batch = 4
    meta = run_metadata(seed=SEED)
    mesh = mesh_from_env()        # REPRO_SERVE_MESH=DxM shards the engines
    rows.append(("serve.run_meta", 0.0,
                 f"commit={meta['git_commit']}_seed={meta['seed']}"
                 f"_devices={meta['devices']}"
                 f"_mesh={meta['mesh'] or 'host'}"))
    step_us = {}
    for mode in ("eager", "fused"):
        pool = PagedKVPool(page_tokens=PAGE_TOKENS)
        eng = ServeEngine(cfg, params=params, kv_pool=pool, decode_mode=mode,
                          seed=SEED, mesh=mesh if mode == "fused" else None)
        params = eng.params
        eng.generate(_reqs(cfg, batch))        # warm the jit caches
        eng.stats["decode_s"] = 0.0
        eng.stats["decode_steps"] = 0
        eng.generate(_reqs(cfg, batch, seed=1))
        steps = max(eng.stats["decode_steps"], 1)
        us = us_per(eng.stats["decode_s"], steps)
        step_us[mode] = us
        h2d, d2h = eng.last_transfers
        rows.append((f"serve.decode_step.b{batch}.{mode}", us,
                     f"plen={PLEN}_t={PAGE_TOKENS}"))
        rows.append((f"serve.host_sync.b{batch}.{mode}",
                     (h2d + d2h) / steps,
                     f"h2d={h2d}_d2h={d2h}_steps={steps}"))
    rows.append((f"serve.decode_step.b{batch}.eager_over_fused", 0.0,
                 f"{step_us['eager'] / step_us['fused']:.2f}x"))

    # isolated steady-state per-step HOST work (no kernel, no model): the
    # fused path's host side is pure bookkeeping — touch + page-table/
    # control build + tail counters (O(batch)); its row scatter happens
    # inside the jitted step graph and is charged to the decode_step rows
    # above
    from repro.serve.paged_decode import PagedKVState
    t, hkv, hd, b, npages = PAGE_TOKENS, 4, 16, 4, 256
    pool = PagedKVPool(page_tokens=t)
    state = PagedKVState(pool, capacity=(npages // b + 16) * t,
                         num_layers=1, hkv=hkv, hd=hd)
    rng = np.random.default_rng(0)
    for seq in range(b):
        kv = rng.standard_normal((npages // b * t, hkv, hd)) \
            .astype(np.float32)
        state.write_prefill(0, seq, kv, kv.copy())
    seqs = list(range(b))
    pos = np.zeros(b, np.int32)

    def step():
        state.begin_step(seqs, pos)
        state.end_step(seqs)

    for _ in range(t + 2):                     # warm all shapes/slots
        step()
    n = 50
    t0 = time.perf_counter()
    for _ in range(n):
        step()
    rows.append(("serve.gather_steady.fused_bookkeeping",
                 us_per(time.perf_counter() - t0, n),
                 f"pool={npages}pages_b={b}"))

    # continuous batching (fused): staggered per-request lengths, 2 rows
    pool = PagedKVPool(page_tokens=PAGE_TOKENS)
    eng = ServeEngine(cfg, params=params, kv_pool=pool, seed=SEED,
                      mesh=mesh)
    reqs = _reqs(cfg, 4, seed=2)
    for i, r in enumerate(reqs):
        r.max_new_tokens = NEW - 3 + 2 * i         # per-request lengths
    t0 = time.time()
    outs = eng.serve(reqs, max_active=2)
    wall = time.time() - t0
    tok = sum(len(o) for o in outs)
    rows.append(("serve.continuous.tok_per_s", us_per(wall, tok),
                 f"{toks_per_s(tok, wall):.1f}tok_s_live_pages={len(pool.pages)}"))

    # speculative multi-token decode: k-token verify steps over the fused
    # graph vs the 1-token fused/eager baselines. The headline metric is
    # host syncs per accepted token — steady state ~2 / (1 + E[accepted])
    # per verify step vs ~2/token for k=1 fused and ~2/layer/token for
    # eager. Decode-attributable syncs isolate the decode path: a
    # max_new=1 run measures the prefill-attributable transfer floor
    # (identical across configs) and is subtracted out. `self` drafting
    # (the serving model drafts for itself, acceptance ~1) shows the
    # k-scaling ceiling; `ngram` (free prompt-lookup drafts) the
    # realistic operating point.
    spec_new = 17

    def spec_run(mode, k, draft):
        pool = PagedKVPool(page_tokens=PAGE_TOKENS)
        eng = ServeEngine(cfg, params=params, kv_pool=pool,
                          decode_mode=mode, speculate=k, draft=draft,
                          seed=SEED, mesh=mesh if mode == "fused" else None)
        eng.generate(_reqs(cfg, batch, seed=4, new=spec_new))  # warm jits
        pre = eng.generate(_reqs(cfg, batch, seed=5, new=1))
        pre_syncs = sum(eng.last_transfers)
        pre_tok = sum(len(o) for o in pre)
        t0 = time.time()
        outs = eng.generate(_reqs(cfg, batch, seed=5, new=spec_new))
        wall = time.time() - t0
        syncs = sum(eng.last_transfers) - pre_syncs
        toks = sum(len(o) for o in outs) - pre_tok
        rates = [d["accept_rate"] for d in eng.last_request_stats
                 if d["accept_rate"] is not None]
        rate = sum(rates) / len(rates) if rates else None
        return wall, syncs, max(toks, 1), rate

    spec_syncs = {}
    for mode, k, draft in (("eager", 0, "ngram"), ("fused", 1, "ngram"),
                           ("fused", 2, "ngram"), ("fused", 4, "ngram"),
                           ("fused", 8, "ngram"), ("fused", 4, "self"),
                           ("fused", 8, "self")):
        wall, syncs, toks, rate = spec_run(mode, k, draft)
        name = f"{mode}.k{max(k, 1)}.{draft}" if k > 1 else f"{mode}.k1"
        spec_syncs[name] = syncs / toks
        rates = "" if rate is None else f"_accept={rate:.2f}"
        rows.append((f"serve.spec.tok.{name}", us_per(wall, toks),
                     f"{toks_per_s(toks, wall):.1f}tok_s{rates}"))
        rows.append((f"serve.spec.syncs_per_token.{name}", syncs / toks,
                     f"decode_syncs={syncs}_tokens={toks}"))
    for name, v in spec_syncs.items():
        if name.startswith("fused.k") and name != "fused.k1":
            rows.append((f"serve.spec.syncs_vs_k1.{name}", 0.0,
                         f"{v / spec_syncs['fused.k1']:.2f}x"))
    return rows
