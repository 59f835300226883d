"""Fused jitted decode step: the whole per-token step as one
device-resident graph must reproduce the per-layer eager paged path
token-for-token (static + continuous, dead rows, int8 slow tier, mid-run
LRU demotion), while crossing the host/device boundary exactly twice per
step — independent of the number of layers — at every width."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import PagedKVPool


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("starcoder2-7b")


@pytest.fixture(scope="module")
def params(cfg):
    return ServeEngine(cfg).params


def _reqs(cfg, n=2, plen=12, new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                    new) for _ in range(n)]


def _engine(cfg, params, mode, **pool_kw):
    pool = PagedKVPool(page_tokens=pool_kw.pop("page_tokens", 4), **pool_kw)
    return ServeEngine(cfg, params=params, kv_pool=pool, decode_mode=mode)


# ---------------------------------------------------------------------------
# Token-for-token equivalence against the eager reference
# ---------------------------------------------------------------------------
def test_fused_matches_eager_static(cfg, params):
    eager = _engine(cfg, params, "eager")
    fused = _engine(cfg, params, "fused")
    outs_e = eager.generate(_reqs(cfg))
    outs_f = fused.generate(_reqs(cfg))
    for a, b in zip(outs_e, outs_f):
        np.testing.assert_array_equal(a, b)
    # the fused pool really served real pages across every layer
    pool = fused.kv_pool
    assert pool.stats["fast_hits"] > 0
    assert {p.layer for p in pool.pages.values()} == set(range(cfg.num_layers))


def test_fused_matches_eager_continuous_with_dead_rows(cfg, params):
    """Staggered lengths through max_active=2 rows: rows retire at
    different steps, so the fused batch decodes with seq_id = -1 padding
    rows whose scatters hit the scratch slot and whose logits are
    ignored."""
    def staggered():
        rs = _reqs(cfg, n=4, new=3)
        for i, r in enumerate(rs):
            r.max_new_tokens = 3 + i       # retire at different steps
        return rs
    eager = _engine(cfg, params, "eager")
    fused = _engine(cfg, params, "fused")
    outs_e = eager.serve(staggered(), max_active=2)
    outs_f = fused.serve(staggered(), max_active=2)
    for a, b in zip(outs_e, outs_f):
        np.testing.assert_array_equal(a, b)
    assert len(fused.kv_pool.pages) == 0       # retirement freed everything


def test_fused_matches_eager_all_slow_tier(cfg, params):
    class AllSlow:
        def place(self, feats):
            return "slow"

    outs = {}
    for mode in ("eager", "fused"):
        eng = _engine(cfg, params, mode, placement_policy=AllSlow())
        outs[mode] = eng.generate(_reqs(cfg))
        assert eng.kv_pool.stats["slow_hits"] > 0
        assert eng.kv_pool.stats["fast_hits"] == 0
        assert all(p.quantized for p in eng.kv_pool.pages.values())
    for a, b in zip(outs["eager"], outs["fused"]):
        np.testing.assert_array_equal(a, b)


def test_fused_matches_eager_under_lru_demotion(cfg, params):
    """A tiny fast tier forces mid-run LRU demotions (version bumps the
    device mirror must pick up as int8 rewrites) — both paths see the
    same quantized content and agree."""
    outs = {}
    for mode in ("eager", "fused"):
        eng = _engine(cfg, params, mode, fast_capacity_pages=3)
        outs[mode] = eng.generate(_reqs(cfg, new=8))
        assert eng.kv_pool.stats["evictions"] > 0
    for a, b in zip(outs["eager"], outs["fused"]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# One builder for every width
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("drafts", [False, True])
def test_width_one_and_width_four_steps_sample_alike(cfg, params, drafts):
    """Plain rows (the last token, then padding: no drafts) through a
    4-wide step sample the greedy token the 1-wide step samples, step
    after step across page boundaries (the 4-wide step's rows spill),
    beside a dead row; the 1-wide verdict's count column is 0."""
    from repro.serve.paged_decode import (PagedKVState, build_fused_step,
                                          extract_prefill_pages)

    eng = _engine(cfg, params, "fused")
    prompts = np.stack([r.prompt for r in _reqs(cfg, plen=6)])
    logits, caches = jax.jit(eng.model.forward_prefill)(
        eng.params, {"tokens": jnp.asarray(prompts)})
    first = np.append(np.asarray(jnp.argmax(logits, -1), np.int32), 0)
    seqs = [0, 1, -1]

    def run(k):
        state = PagedKVState(PagedKVPool(page_tokens=4), 32, cfg.num_layers,
                             cfg.num_kv_heads, cfg.head_dim, batch_hint=3,
                             tail_slots=2)
        extract_prefill_pages(eng.model, caches, state, [0, 1])
        step = build_fused_step(eng.model, state.slots, k=k, drafts=drafts)
        tok, outs = first, []
        for j in range(6):
            verdict = state.run_spec(step, eng.params,
                                     np.repeat(tok[:, None], k, axis=1),
                                     seqs, np.full(3, 6 + j, np.int32),
                                     jax.random.PRNGKey(j))
            assert verdict.shape == (3, k + 1)
            if k == 1:
                assert not verdict[:, 1].any()
            state.end_step(seqs, [1, 1, 0])
            tok = verdict[:, 0]
            outs.append(tok[:2])
        for seq in (0, 1):
            state.free_seq(seq)
        return np.stack(outs)

    np.testing.assert_array_equal(run(1), run(4))


# ---------------------------------------------------------------------------
# Host <-> device transfer accounting
# ---------------------------------------------------------------------------
def test_fused_steady_state_two_transfers_per_token(cfg):
    """Every step, the first included: one int32 control upload (the
    tokens ride in it) + one verdict download; in steady state (no page
    fills, mirror synced) zero device-pool scatters/readbacks besides —
    at every depth. The eager reference pays ~2 crossings per *layer*
    per token instead."""
    from repro.serve.paged_decode import (PagedKVState, build_fused_step,
                                          extract_prefill_pages)

    per_depth = {}
    for num_layers in (2, 4):
        c = dataclasses.replace(cfg, num_layers=num_layers)
        eng = ServeEngine(c, kv_pool=PagedKVPool(page_tokens=16))
        prompt = np.asarray(_reqs(c, n=1, plen=20)[0].prompt)
        state = PagedKVState(eng.kv_pool, 32, c.num_layers,
                             c.num_kv_heads, c.head_dim, mode="fused")
        logits, caches = jax.jit(eng.model.forward_prefill)(
            eng.params, {"tokens": jnp.asarray(prompt[None])})
        extract_prefill_pages(eng.model, caches, state, [0])
        fused = build_fused_step(eng.model, state.slots)
        key = jax.random.PRNGKey(0)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # first step syncs prefill pages into the mirror
        tok = state.run_fused(fused, eng.params, tok, [0], 20, key)
        assert (state.h2d, state.d2h) == (1, 1)
        writes0 = state._device.writes
        h0, d0 = state.transfer_counts()
        for s in range(3):                 # tail rows 5..7 of 16: no fill
            tok = state.run_fused(fused, eng.params, tok, [0], 21 + s, key)
        h1, d1 = state.transfer_counts()
        assert state._device.writes == writes0     # no scatters, no syncs
        per_depth[num_layers] = (h1 - h0, d1 - d0)
        assert per_depth[num_layers] == (3, 3)     # 2 transfers per token
        assert (state.h2d, state.d2h) == (4, 4)
    assert per_depth[2] == per_depth[4]            # independent of depth


def test_numpy_decode_mode_is_refused(cfg, params):
    """Two decode modes remain: the fused step and the eager reference."""
    with pytest.raises(ValueError, match="numpy"):
        ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=4),
                    decode_mode="numpy")


def test_eager_transfers_scale_with_depth_fused_do_not(cfg, params):
    """End-to-end engine accounting: over a whole generate() call the
    eager path's transfer count grows with num_layers, the fused path's
    decode-attributable count does not (prefill page writes are layer-
    proportional in both)."""
    counts = {}
    for mode in ("eager", "fused"):
        eng = _engine(cfg, params, mode, page_tokens=16)
        eng.generate(_reqs(cfg, n=1, new=6))
        counts[mode] = sum(eng.last_transfers)
    assert counts["fused"] < counts["eager"]


def test_device_pool_sync_growth_keeps_layer_indices():
    """A sync batch whose slot allocations outgrow the pool mid-batch must
    compute its flattened (layer * capacity + slot) scatter indices
    against the FINAL capacity — with the stale pre-growth capacity,
    every layer > 0 page lands in the wrong cell of the grown arrays."""
    from repro.serve.device_pool import DevicePagePool

    rng = np.random.default_rng(0)
    num_layers, t, hkv, hd = 2, 2, 1, 2
    pool = PagedKVPool(page_tokens=t)
    dp = DevicePagePool(num_layers, t, hkv, hd, init_slots=8)
    groups, content = [], {}
    for seq in range(12):                  # 12 groups > 8 slots -> _grow()
        group = []
        for layer in range(num_layers):
            k = rng.standard_normal((t, hkv, hd)).astype(np.float32)
            pid = pool.put(seq, k, k + 1.0, layer=layer)
            content[pid] = k
            group.append(pid)
        groups.append(tuple(group))
    dp.sync(pool, groups)
    assert dp.capacity == 16
    kf = np.asarray(dp.arrays[0])
    vf = np.asarray(dp.arrays[1])
    for group in groups:
        slot = dp.slot_of[group[0]]
        for layer, pid in enumerate(group):
            np.testing.assert_array_equal(kf[layer, slot], content[pid])
            np.testing.assert_array_equal(vf[layer, slot], content[pid] + 1.0)


# ---------------------------------------------------------------------------
# Stacked kernel form
# ---------------------------------------------------------------------------
def _stacked_inputs(n_layers=3):
    from repro.kernels.paged_attention.spec import example_inputs
    inps = [example_inputs(seed=layer) for layer in range(n_layers)]
    names = ("k_pages", "v_pages", "k_quant", "v_quant", "k_scale", "v_scale")
    stacked = [jnp.stack([jnp.asarray(i[n]) for i in inps]) for n in names]
    return inps, stacked, names


def test_stacked_kernel_matches_flat_per_layer():
    from repro.kernels import api

    inps, stacked, names = _stacked_inputs()
    q = jnp.asarray(inps[0]["q"])
    table = jnp.asarray(inps[0]["page_table"])
    lengths = jnp.asarray(inps[0]["lengths"])
    for layer, inp in enumerate(inps):
        want = api.run("paged_attention", q,
                       *(jnp.asarray(inp[n]) for n in names),
                       table, lengths, backend="ref")
        for backend in ("pallas", "ref"):
            got = api.run("paged_attention", q, *stacked, table, lengths,
                          jnp.int32(layer), backend=backend)
            np.testing.assert_allclose(got, want, atol=5e-5)


def test_stacked_kernel_traces_under_jit_scan():
    """The fused decode step scans the layer stack with a *traced* layer
    index — the kernel's scalar-prefetched layer operand must trace."""
    from repro.kernels import api

    inps, stacked, names = _stacked_inputs()
    q = jnp.asarray(inps[0]["q"])
    table = jnp.asarray(inps[0]["page_table"])
    lengths = jnp.asarray(inps[0]["lengths"])

    @jax.jit
    def all_layers(q):
        def body(_, layer):
            return None, api.run("paged_attention", q, *stacked, table,
                                 lengths, layer, backend="pallas")
        _, outs = jax.lax.scan(body, None, jnp.arange(len(inps)))
        return outs

    outs = all_layers(q)
    for layer, inp in enumerate(inps):
        want = api.run("paged_attention", q,
                       *(jnp.asarray(inp[n]) for n in names),
                       table, lengths, backend="ref")
        np.testing.assert_allclose(outs[layer], want, atol=5e-5)


def test_stacked_kernel_requires_consistent_layer_arg():
    from repro.kernels.paged_attention.paged_attention import \
        paged_attention_pallas
    inps, stacked, _ = _stacked_inputs(2)
    q = jnp.asarray(inps[0]["q"])
    table = jnp.asarray(inps[0]["page_table"])
    lengths = jnp.asarray(inps[0]["lengths"])
    with pytest.raises(ValueError, match="layer"):
        paged_attention_pallas(q, *stacked, table, lengths)   # no layer
    flat = [jnp.asarray(inps[0][n]) for n in
            ("k_pages", "v_pages", "k_quant", "v_quant",
             "k_scale", "v_scale")]
    with pytest.raises(ValueError, match="layer"):
        paged_attention_pallas(q, *flat, table, lengths, jnp.int32(0))


# ---------------------------------------------------------------------------
# Knee persistence + token accounting satellites
# ---------------------------------------------------------------------------
def test_knee_cache_persists_and_preloads(tmp_path, cfg, params):
    from repro.kernels import api

    api.invalidate_caches()                # force a fresh resolution
    path = tmp_path / "knee_cache.json"
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=4),
                      knee_cache=path)
    eng.generate(_reqs(cfg, n=1))
    assert path.exists()
    import json
    entries = json.loads(path.read_text())
    assert any(e["kernel"] == "paged_attention" for e in entries)
    assert not api.knees_dirty()           # engine saved what it resolved

    # a restart preloads the file: the same shapes resolve without any
    # re-tuning (nothing becomes dirty again)
    api.invalidate_caches()
    assert api.load_knee_cache(path) == len(entries)
    eng2 = ServeEngine(cfg, params=params,
                       kv_pool=PagedKVPool(page_tokens=4), knee_cache=path)
    eng2.generate(_reqs(cfg, n=1))
    assert not api.knees_dirty()


def test_generate_token_stats_count_actual_output(cfg, params):
    """stats["tokens"] counts tokens actually returned per request — not
    b * max(max_new_tokens), and not max_new for an eos-truncated row."""
    eng = _engine(cfg, params, "fused")
    for seed in range(6):
        [base] = eng.generate(_reqs(cfg, n=1, new=8, seed=seed))
        stop = next((i for i in range(1, len(base))
                     if base[i] not in base[:i]), None)
        if stop is not None:
            break
    else:
        pytest.skip("all greedy streams are single-token under these seeds")
    [req] = _reqs(cfg, n=1, new=8, seed=seed)
    req.eos_token = int(base[stop])
    before = eng.stats["tokens"]
    [out] = eng.generate([req])
    assert eng.stats["tokens"] - before == len(out) == stop + 1
