"""Speculative multi-token decode over the fused paged-KV graph.

The contract: greedy k-token speculative decode emits EXACTLY the tokens
of the 1-token fused path for ANY draft proposer — drafts only steer
which tokens get verified — across the static batch, the continuous
batch (dead rows included), the int8 slow tier and mid-run LRU demotion;
rejected-row rollback is pure bookkeeping, so the pool never holds
phantom tokens and the transfer counters stay consistent; and a verify
step's 2 host<->device crossings amortize over the whole accepted run,
beating the k=1 fused baseline's syncs-per-token."""
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import PagedKVPool
from repro.serve.speculative import ModelDraft, NGramDraft


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


def _engine(cfg, params, speculate=0, draft="ngram", **pool_kw):
    pool = PagedKVPool(page_tokens=pool_kw.pop("page_tokens", 4), **pool_kw)
    return ServeEngine(cfg, params=params, kv_pool=pool,
                       speculate=speculate, draft=draft)


# ---------------------------------------------------------------------------
# Greedy equivalence: k-token speculative == 1-token fused, any draft
# ---------------------------------------------------------------------------
def test_spec_matches_fused_static(cfg, params):
    base = _engine(cfg, params)
    spec = _engine(cfg, params, speculate=4)
    outs_b = base.generate(_reqs(cfg, new=8))
    outs_s = spec.generate(_reqs(cfg, new=8))
    for a, b in zip(outs_b, outs_s):
        np.testing.assert_array_equal(a, b)
    # the speculative run really advanced multiple tokens per step
    assert any(d["tokens_per_step"] > 1.0 for d in spec.last_request_stats)
    assert all(d["accept_rate"] is not None for d in spec.last_request_stats)


def test_spec_matches_fused_continuous_staggered(cfg, params):
    """Staggered lengths through max_active=2: rows retire at different
    steps, so verify batches carry seq -1 dead rows whose k scatters hit
    the scratch slot and whose verdicts are ignored."""
    def staggered():
        rs = _reqs(cfg, n=4, new=3)
        for i, r in enumerate(rs):
            r.max_new_tokens = 3 + i
        return rs

    base = _engine(cfg, params)
    spec = _engine(cfg, params, speculate=3)
    outs_b = base.serve(staggered(), max_active=2)
    outs_s = spec.serve(staggered(), max_active=2)
    for a, b in zip(outs_b, outs_s):
        np.testing.assert_array_equal(a, b)
    assert len(spec.kv_pool.pages) == 0       # retirement freed everything


def test_spec_matches_fused_all_slow_tier(cfg, params):
    class AllSlow:
        def place(self, feats):
            return "slow"

    outs = {}
    for k in (0, 4):
        eng = _engine(cfg, params, speculate=k,
                      placement_policy=AllSlow())
        outs[k] = eng.generate(_reqs(cfg, new=8))
        assert eng.kv_pool.stats["slow_hits"] > 0
        assert eng.kv_pool.stats["fast_hits"] == 0
    for a, b in zip(outs[0], outs[4]):
        np.testing.assert_array_equal(a, b)


def test_spec_matches_fused_under_lru_demotion(cfg, params):
    outs = {}
    for k in (0, 4):
        eng = _engine(cfg, params, speculate=k, fast_capacity_pages=3)
        outs[k] = eng.generate(_reqs(cfg, new=10))
        assert eng.kv_pool.stats["evictions"] > 0
    for a, b in zip(outs[0], outs[4]):
        np.testing.assert_array_equal(a, b)


def test_spec_matches_fused_self_draft(cfg, params):
    """The serving model drafting for itself: near-total acceptance, and
    still token-for-token with the plain path (verification owns
    correctness, the draft only owns the accept rate)."""
    base = _engine(cfg, params)
    spec = _engine(cfg, params, speculate=4, draft="self")
    outs_b = base.generate(_reqs(cfg, new=9))
    outs_s = spec.generate(_reqs(cfg, new=9))
    for a, b in zip(outs_b, outs_s):
        np.testing.assert_array_equal(a, b)
    rates = [d["accept_rate"] for d in spec.last_request_stats]
    assert all(r is not None and r > 0.5 for r in rates), rates


def test_spec_matches_fused_with_eos_mid_run(cfg, params):
    """An eos sampled inside an accepted run must truncate the output at
    eos (inclusive) exactly like the 1-token path trims it."""
    base = _engine(cfg, params)
    for seed in range(6):
        [out] = base.generate(_reqs(cfg, n=1, new=8, seed=seed))
        if len(set(out.tolist())) < len(out):     # a repeated token exists
            eos = int(out[-1])
            break
    else:
        pytest.skip("no greedy repetition under these seeds")
    [req_b] = _reqs(cfg, n=1, new=8, seed=seed)
    req_b.eos_token = eos
    [want] = base.generate([req_b])
    [req_s] = _reqs(cfg, n=1, new=8, seed=seed)
    req_s.eos_token = eos
    spec = _engine(cfg, params, speculate=4, draft="self")
    [got] = spec.generate([req_s])
    np.testing.assert_array_equal(want, got)
    assert got[-1] == eos


def test_mixed_spec_and_plain_requests_one_batch(cfg, params):
    """One continuous batch freely mixes per-request speculation levels;
    plain rows ride the verify step with padding drafts that never count
    as accepted."""
    def rs():
        out = _reqs(cfg, n=3, new=6)
        out[0].speculate = 1          # plain 1-token rows
        out[2].speculate = 2
        return out

    base = _engine(cfg, params)
    outs_b = base.serve(rs(), max_active=3)
    spec = _engine(cfg, params, speculate=4)
    outs_s = spec.serve(rs(), max_active=3)
    for a, b in zip(outs_b, outs_s):
        np.testing.assert_array_equal(a, b)
    d0, d1, d2 = spec.last_request_stats
    assert d0["proposed"] == 0 and d0["accept_rate"] is None
    assert d0["tokens_per_step"] <= 1.0 + 1e-9
    assert d1["proposed"] >= d2["proposed"] > 0   # k=4 proposes more than k=2


# ---------------------------------------------------------------------------
# Rollback + transfer accounting
# ---------------------------------------------------------------------------
def test_rollback_never_puts_phantom_tokens(cfg, params):
    """Pool pages must cover exactly the ACCEPTED tokens: with page_tokens
    t, each sequence holds floor((plen + emitted - 1) / t) pages per layer
    (the -1: the newest emitted token's KV lands next step), regardless of
    how many speculative rows were scattered and rolled back."""
    t = 4
    eng = _engine(cfg, params, speculate=4, page_tokens=t)
    reqs = _reqs(cfg, n=2, plen=11, new=9)
    outs = eng.generate(reqs)
    for i, (r, o) in enumerate(zip(reqs, outs)):
        want = (len(r.prompt) + len(o) - 1) // t
        assert len(eng.kv_pool.seq_pages(i, 0)) == want, (i, want)
    # per-layer structure stays uniform (ragged counts would raise when a
    # table row is built from the pool, but assert the end state too)
    by_layer = {}
    for p in eng.kv_pool.pages.values():
        by_layer[p.layer] = by_layer.get(p.layer, 0) + 1
    assert len(set(by_layer.values())) == 1
    # retiring after a speculative run frees everything (no leaked slots)
    st = eng.stats
    assert st["tokens"] == sum(len(o) for o in outs)


def test_spec_transfer_counts_beat_k1_baseline(cfg, params):
    """The acceptance bar: host syncs per emitted token strictly below the
    k=1 fused baseline on the same workload (self-draft makes acceptance,
    and therefore the win, deterministic-ish and large)."""
    counts = {}
    for k in (0, 4):
        eng = _engine(cfg, params, speculate=k,
                      draft="self" if k else "ngram", page_tokens=8)
        outs = eng.generate(_reqs(cfg, n=1, plen=16, new=12))
        counts[k] = sum(eng.last_transfers) / sum(len(o) for o in outs)
    assert counts[4] < counts[0], counts


def test_spec_stats_invariants(cfg, params):
    """tokens = sum over steps of (accepted_kept + bonus?) — so
    steps <= tokens <= steps + accepted, and proposed >= accepted."""
    eng = _engine(cfg, params, speculate=4)
    outs = eng.generate(_reqs(cfg, new=8))
    for d, o in zip(eng.last_request_stats, outs):
        assert d["tokens"] == len(o)
        assert d["proposed"] >= d["accepted"] >= 0
        decode_tokens = d["tokens"] - 1          # minus the prefill token
        assert d["steps"] <= decode_tokens <= d["steps"] + d["accepted"]
        assert d["tokens_per_step"] == pytest.approx(
            decode_tokens / d["steps"])


def test_spec_guardrails(cfg, params):
    pool = PagedKVPool(page_tokens=4)
    with pytest.raises(ValueError, match="fused"):
        ServeEngine(cfg, params=params, kv_pool=pool, decode_mode="eager",
                    speculate=4).generate(_reqs(cfg))
    with pytest.raises(ValueError, match="page pool"):
        ServeEngine(cfg, params=params, speculate=4).generate(_reqs(cfg))
    with pytest.raises(ValueError, match="page_tokens"):
        ServeEngine(cfg, params=params, kv_pool=pool,
                    speculate=8).generate(_reqs(cfg))
    # per-request speculate overrides the engine default and hits the
    # same guards
    rs = _reqs(cfg)
    rs[0].speculate = 8
    with pytest.raises(ValueError, match="page_tokens"):
        ServeEngine(cfg, params=params, kv_pool=pool).generate(rs)


def test_scheduler_budgets_spill_page_for_spec_requests(cfg):
    from repro.serve.scheduler import Scheduler
    pool = PagedKVPool(page_tokens=4)
    plain = Request(np.zeros(8, np.int32), 4)
    spec = Request(np.zeros(8, np.int32), 4, speculate=4)
    s = Scheduler(pool, num_layers=2)
    assert s.pages_needed(spec) == s.pages_needed(plain) + 2  # +1 page/layer
    s2 = Scheduler(pool, num_layers=2, default_speculate=4)
    assert s2.pages_needed(plain) == s.pages_needed(spec)


# ---------------------------------------------------------------------------
# Draft proposers
# ---------------------------------------------------------------------------
def test_ngram_draft_prompt_lookup():
    d = NGramDraft(n=3)
    h = np.array([5, 1, 2, 3, 9, 7, 1, 2, 3], np.int32)
    # final trigram (1,2,3) occurred at position 1; continuation was 9, 7
    np.testing.assert_array_equal(d.propose(h, 2), [9, 7])
    np.testing.assert_array_equal(d.propose(h, 4), [9, 7, 1, 2])
    # continuation shorter than requested pads by repeating its last token
    h2 = np.array([7, 1, 2, 3, 1, 2, 3], np.int32)
    np.testing.assert_array_equal(d.propose(h2, 4), [1, 2, 3, 3])
    # no match at any order: repeat the last token
    np.testing.assert_array_equal(
        NGramDraft(n=3).propose(np.array([1, 2, 3], np.int32), 2), [3, 3])
    assert d.propose(h, 0).shape == (0,)


def test_ngram_draft_most_recent_occurrence():
    d = NGramDraft(n=2)
    h = np.array([1, 2, 7, 1, 2, 8, 1, 2], np.int32)
    # (1,2) occurs at 0 and 3; the most recent (3) wins -> continuation 8
    np.testing.assert_array_equal(d.propose(h, 1), [8])


def test_model_draft_is_greedy_continuation(cfg, params):
    eng = ServeEngine(cfg, params=params)
    d = ModelDraft(eng.model, params)
    rng = np.random.default_rng(0)
    hist = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    out = d.propose(hist, 3)
    assert out.shape == (3,)
    # drafting one more token keeps the earlier ones (greedy = prefix-
    # stable for a fixed history)
    np.testing.assert_array_equal(d.propose(hist, 2), out[:2])
