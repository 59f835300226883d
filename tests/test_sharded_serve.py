"""Mesh-sharded serving: the dp x tp fused decode path must be
token-for-token identical to the single-device stack (plain and
speculative), keep the 2-transfers-per-token property at every mesh
size, and keep every per-shard kernel call local (no cross-device page
gather).

The mesh tests need >= 8 devices; the default tier-1 run (one CPU
device) skips them and the CI multi-device job runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``. The scheduler's
per-shard admission tests are pure host logic and always run.
"""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.serve.engine import Request, ServeEngine, ServeSession
from repro.serve.kvcache import PagedKVPool
from repro.serve.scheduler import Scheduler

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="mesh tests need XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8")


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


def _engine(cfg, params, mesh_shape, **kw):
    from repro.launch.mesh import make_serve_mesh
    d, m = mesh_shape
    return ServeEngine(cfg, params=params,
                       kv_pool=PagedKVPool(page_tokens=8),
                       mesh=make_serve_mesh(d, m), **kw)


# ---------------------------------------------------------------------------
# Token-for-token equivalence vs the single-device fused path
# ---------------------------------------------------------------------------
@needs8
@pytest.mark.parametrize("mesh_shape", [(1, 4), (4, 1), (8, 1), (2, 4)])
def test_sharded_greedy_matches_single_device(cfg, params, mesh_shape):
    ref = _engine(cfg, params, (1, 1))
    outs_ref = ref.generate(_reqs(cfg))
    eng = _engine(cfg, params, mesh_shape)
    outs = eng.generate(_reqs(cfg))
    for a, b in zip(outs_ref, outs):
        np.testing.assert_array_equal(a, b)


@needs8
@pytest.mark.parametrize("mesh_shape", [(2, 2), (2, 4)])
def test_sharded_speculative_matches_greedy(cfg, params, mesh_shape):
    """Greedy k=4 verify over the sharded graph accepts/rejects exactly
    like the unsharded stream, so the emitted tokens match the plain
    single-device greedy decode."""
    ref = _engine(cfg, params, (1, 1))
    outs_ref = ref.generate(_reqs(cfg, new=10))
    eng = _engine(cfg, params, mesh_shape, speculate=4, draft="ngram")
    outs = eng.generate(_reqs(cfg, new=10))
    for a, b in zip(outs_ref, outs):
        np.testing.assert_array_equal(a, b)


@needs8
def test_sharded_continuous_matches_single_device(cfg, params):
    def staggered():
        rs = _reqs(cfg, n=4, new=3)
        for i, r in enumerate(rs):
            r.max_new_tokens = 3 + i
        return rs

    ref = _engine(cfg, params, (1, 1))
    outs_ref = ref.serve(staggered(), max_active=2)
    eng = _engine(cfg, params, (2, 2))
    outs = eng.serve(staggered(), max_active=2)
    for a, b in zip(outs_ref, outs):
        np.testing.assert_array_equal(a, b)
    assert len(eng.kv_pool.pages) == 0


@needs8
@pytest.mark.parametrize("spec_k", [1, 4])
def test_sharded_chunked_prefill_matches_monolithic(cfg, params, spec_k):
    """Radix-adopted + chunked-prefill serving on a 2x2 mesh is
    token-for-token identical to the monolithic-prefill path (the radix
    tree keys per data shard, so adoption never pulls a remote page);
    plain and k=4 speculative."""
    def shared_head():
        rng = np.random.default_rng(7)
        head = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        rs = []
        for i in range(4):
            tail = rng.integers(0, cfg.vocab_size, 5 + i).astype(np.int32)
            rs.append(Request(np.concatenate([head, tail]), 3 + i,
                              speculate=spec_k if spec_k > 1 else None))
        return rs

    kw = {"speculate": spec_k, "draft": "ngram"} if spec_k > 1 else {}
    ref = _engine(cfg, params, (2, 2), **kw)
    outs_ref = ref.serve(shared_head(), max_active=2,
                         chunked_prefill=False, radix=False)
    eng = _engine(cfg, params, (2, 2), **kw)
    outs = eng.serve(shared_head(), max_active=2)
    for a, b in zip(outs_ref, outs):
        np.testing.assert_array_equal(a, b)
    assert len(eng.kv_pool.pages) == 0     # serve() dropped the pins


@needs8
def test_sharded_preempt_resume_matches_single_device(cfg, params):
    """Preempt one active row on EACH data shard of a 2x2 mesh: the
    victims swap to the host tier, auto-resume onto their original
    shard when rows free, and every output is token-for-token identical
    to its solo single-device decode."""
    reqs = _reqs(cfg, n=4, plen=12, new=8, seed=3)
    ref = _engine(cfg, params, (1, 1))
    want = [ref.generate([Request(r.prompt.copy(), r.max_new_tokens)])[0]
            for r in reqs]

    eng = _engine(cfg, params, (2, 2))
    ses = ServeSession(eng, capacity=64, max_active=4)
    for r in reqs:
        ses.submit(r)
    for _ in range(3):
        ses.step()
    by_shard = {}
    for r in reqs:                     # first request seen on each shard
        by_shard.setdefault(ses.sched.assigned_shard(r), r)
    assert sorted(by_shard) == [0, 1]
    for r in by_shard.values():
        assert ses.preempt(r)
    assert eng.kv_pool.stats["swap_out_bytes"] > 0
    while not ses.done:
        ses.step()
    for r, w in zip(reqs, want):
        np.testing.assert_array_equal(ses.result(r), w)
    assert ses.preemptions == 2 and ses.resumes == 2
    ses.close()
    assert eng.kv_pool.live_pages == 0


# ---------------------------------------------------------------------------
# Transfer accounting: 2 host<->device crossings per token, mesh-blind
# ---------------------------------------------------------------------------
@needs8
def test_transfers_per_token_mesh_independent(cfg, params):
    """The whole-generate transfer count is identical at every mesh size
    (a sharded control upload is still ONE logical h2d), and each extra
    decode token costs exactly one upload + one download regardless of
    dp/tp."""
    counts = {}
    for mesh_shape in ((1, 1), (4, 1), (1, 4), (2, 4)):
        per_new = {}
        for new in (6, 10):
            eng = _engine(cfg, params, mesh_shape)
            eng.generate(_reqs(cfg, new=new))
            per_new[new] = eng.last_transfers
        counts[mesh_shape] = per_new
        h6, d6 = per_new[6]
        h10, d10 = per_new[10]
        assert (h10 - h6, d10 - d6) == (4, 4), mesh_shape
    assert len({tuple(sorted(c.items())) for c in counts.values()}) == 1


@needs8
def test_sharded_steady_state_two_transfers_per_token(cfg):
    """The low-level steady-state idiom of test_fused_decode on a
    tp-sharded mesh: one control upload and one download a step, the
    first included; once the mirror is synced, 3 tokens cost exactly
    (3, 3) transfers and zero pool scatters."""
    from repro.launch.mesh import make_serve_mesh
    from repro.serve.paged_decode import (PagedKVState, build_fused_step,
                                          extract_prefill_pages)
    from repro.serve.sharding import ServePlan

    import jax.numpy as jnp

    plan = ServePlan.from_mesh(make_serve_mesh(1, 4))
    eng = ServeEngine(cfg, kv_pool=PagedKVPool(page_tokens=16),
                      mesh=make_serve_mesh(1, 4))
    prompt = np.asarray(_reqs(cfg, n=1, plen=20)[0].prompt)
    state = PagedKVState(eng.kv_pool, 32, cfg.num_layers,
                         cfg.num_kv_heads, cfg.head_dim, mode="fused",
                         plan=plan)
    logits, caches = jax.jit(eng.model.forward_prefill)(
        eng.params, {"tokens": jnp.asarray(prompt[None])})
    extract_prefill_pages(eng.model, caches, state, [0])
    fused = build_fused_step(eng.model, state.slots, plan=plan)
    key = jax.random.PRNGKey(0)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    tok = state.run_fused(fused, eng.params, tok, [0], 20, key)
    assert (state.h2d, state.d2h) == (1, 1)
    writes0 = state._device.writes
    h0, d0 = state.transfer_counts()
    for s in range(3):
        tok = state.run_fused(fused, eng.params, tok, [0], 21 + s, key)
    h1, d1 = state.transfer_counts()
    assert state._device.writes == writes0
    assert (h1 - h0, d1 - d0) == (3, 3)
    assert (state.h2d, state.d2h) == (4, 4)


@needs8
def test_control_block_matches_the_pool_on_a_2x2_mesh(cfg, params):
    """The page-table rows kept by page events, on shard-local slots:
    every step's control block equals the one rebuilt from the pool and
    the mirror (`test_page_table.reference_control`) through chunked
    prefill with a shared head, a preemption and its resume."""
    from test_page_table import check_every_step

    eng = _engine(cfg, params, (2, 2))
    ses = ServeSession(eng, capacity=64, max_active=4)
    counts = check_every_step(ses.state)
    rng = np.random.default_rng(3)
    head = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    reqs = [Request(np.concatenate([head, rng.integers(
        0, cfg.vocab_size, n).astype(np.int32)]), new)
        for n, new in ((5, 9), (11, 6), (3, 8), (7, 7))]
    for r in reqs:
        ses.submit(r)
    for _ in range(5):
        ses.step()
    assert ses.preempt(reqs[0])
    while not ses.done:
        ses.step()
    assert ses.resumes == 1
    assert 0 < sum(b for _, b in counts) < sum(r for r, _ in counts)
    ses.close()


# ---------------------------------------------------------------------------
# Kernel calling convention: per-shard calls are fully local
# ---------------------------------------------------------------------------
@needs8
def test_kernel_head_sharded_shard_map_matches_ref():
    """`head_sharded_specs` under shard_map: page tables carry LOCAL slot
    ids per data shard, kv/q heads split over the model axis, and the
    sharded result equals the global reference with global page ids —
    i.e. no shard ever needed a remote page."""
    from repro.kernels.paged_attention import ref
    from repro.kernels.paged_attention.spec import head_sharded_specs
    from repro.launch.mesh import make_serve_mesh

    dp, tp = 2, 2
    b, pages_local, slots, t, hq, hkv, d = 4, 8, 2, 8, 4, 2, 16
    pages = dp * pages_local
    rng = np.random.default_rng(0)
    kf = rng.normal(size=(pages, t, hkv, d)).astype(np.float32)
    vf = rng.normal(size=(pages, t, hkv, d)).astype(np.float32)
    kq = np.zeros((pages, t, hkv, d), np.int8)
    vq = np.zeros((pages, t, hkv, d), np.int8)
    ks = np.zeros((pages, t, hkv), np.float32)
    vs = np.zeros((pages, t, hkv), np.float32)
    # each data shard's rows draw pages only from its local range
    table_local = np.zeros((b, slots), np.int32)
    table_global = np.zeros((b, slots), np.int32)
    rows_per_shard = b // dp
    for i in range(b):
        shard = i // rows_per_shard
        local = rng.permutation(pages_local)[:slots]
        table_local[i] = local
        table_global[i] = local + shard * pages_local
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    lengths = rng.integers(1, slots * t + 1, b).astype(np.int32)

    expected = ref.paged_attention(q, kf, vf, kq, vq, ks, vs,
                                   table_global, lengths)

    mesh = make_serve_mesh(dp, tp)
    specs = head_sharded_specs(layer_stacked=False)
    args = ("q", "k_pages", "v_pages", "k_quant", "v_quant",
            "k_scale", "v_scale", "page_table", "lengths")
    sharded = jax.jit(jax.shard_map(
        ref.paged_attention, mesh=mesh,
        in_specs=tuple(specs[a] for a in args),
        out_specs=specs["out"], check_vma=False))
    out = sharded(q, kf, vf, kq, vq, ks, vs, table_local, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# Scheduler: per-shard row + page budgets (pure host logic, no devices)
# ---------------------------------------------------------------------------
def _sched(capacity_pages=None, **kw):
    pool = PagedKVPool(page_tokens=4, capacity_pages=capacity_pages)
    return Scheduler(pool, num_layers=2, **kw)


def _req(plen=4, new=4):
    return Request(np.zeros(plen, np.int32), new)


def test_scheduler_unsharded_defaults_unchanged():
    s = _sched(max_active=2)
    r = _req()
    assert s.submit(r)
    assert s.admit() == [r]
    assert s.assigned_shard(r) == 0
    s.retire(r)
    assert s.done


def test_scheduler_rejects_on_per_shard_budget():
    """A request must fit ONE shard's share of the page budget, not the
    whole pool: 2 shards halve the admissible worst case."""
    r = _req(plen=8, new=8)
    whole = _sched(capacity_pages=12, max_active=4)
    need = whole.pages_needed(r)
    assert need == 10 and whole.submit(r)

    halved = _sched(capacity_pages=12, max_active=4, data_shards=2)
    verdict = halved.submit(r)
    assert not verdict
    assert verdict.reason == "pool_capacity"
    assert verdict.pages_budget == 6
    assert "per data shard (x2)" in verdict.detail


def test_scheduler_balances_shards_and_respects_rows():
    """Admission spreads requests over the least-reserved shards and
    stops when every shard's row block is full, even with max_active
    headroom left."""
    s = _sched(max_active=8, data_shards=2, rows_per_shard=1)
    reqs = [_req() for _ in range(3)]
    for r in reqs:
        assert s.submit(r)
    admitted = s.admit()
    assert admitted == reqs[:2]                  # one row per shard
    assert {s.assigned_shard(r) for r in admitted} == {0, 1}
    assert len(s.waiting) == 1
    s.retire(admitted[0])
    assert s.admit() == [reqs[2]]                # freed row reused


def test_scheduler_shard_reservations_release_on_retire():
    s = _sched(capacity_pages=40, max_active=4, data_shards=2)
    reqs = [_req(plen=8, new=8) for _ in range(2)]
    for r in reqs:
        assert s.submit(r)
    s.admit()
    assert s._shard_reserved[0] > 0 and s._shard_reserved[1] > 0
    for r in reqs:
        s.retire(r)
    assert s._shard_reserved == [0, 0]
    assert s._shard_active == [0, 0]
    assert s.done
