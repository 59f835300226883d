"""Continuous-batching serve engine: staggered admission with per-request
lengths must produce greedy tokens identical to running each request
alone through the static-batch paged path; retiring frees pages back to
the live working set; admission is gated on pool headroom; prefix-shared
prompts are stored once."""
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import PagedKVPool
from repro.serve.paged_decode import PagedKVState


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("starcoder2-7b")


@pytest.fixture(scope="module")
def ref(cfg):
    """Reference engine + per-request static-batch greedy outputs."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(4)]
    news = [3, 6, 4, 5]
    eng = ServeEngine(cfg, kv_pool=PagedKVPool(page_tokens=4))
    expected = [eng.generate([Request(p.copy(), n)])[0]
                for p, n in zip(prompts, news)]
    return eng.params, prompts, news, expected


def _requests(prompts, news):
    return [Request(p.copy(), n) for p, n in zip(prompts, news)]


def test_continuous_matches_per_request_static_greedy(cfg, ref):
    """max_active=2 over 4 requests with different lengths: requests are
    admitted mid-decode as earlier ones retire, and every output matches
    the request run alone through the static paged path token-for-token."""
    params, prompts, news, expected = ref
    pool = PagedKVPool(page_tokens=4)
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    outs = eng.serve(_requests(prompts, news), max_active=2)
    for want, got in zip(expected, outs):
        np.testing.assert_array_equal(want, got)
    assert eng.last_peak_active == 2           # genuinely batched
    # finished requests freed their pages: the pool is back to empty
    assert len(pool.pages) == 0
    assert pool.stats["fast_bytes"] == 0 and pool.stats["slow_bytes"] == 0
    assert pool.stats["freed"] > 0


def test_eos_token_retires_early(cfg, ref):
    params, prompts, _, _ = ref
    pool = PagedKVPool(page_tokens=4)
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    # find a run whose output contains a token first appearing mid-stream
    # (usable as eos); smoke models often repeat one token, so scan prompts
    for p in prompts:
        base = eng.serve([Request(p.copy(), 8)])[0]
        stop = next((i for i in range(1, len(base))
                     if base[i] not in base[:i]), None)
        if stop is not None:
            break
    else:
        pytest.skip("all greedy streams are single-token under this seed")
    out = eng.serve([Request(p.copy(), 8, eos_token=int(base[stop]))])[0]
    assert out.tolist() == base[:stop + 1].tolist()   # eos is included
    assert len(pool.pages) == 0


def test_prefix_shared_prompts_stored_once(cfg, ref):
    params, prompts, _, _ = ref
    pool = PagedKVPool(page_tokens=4)
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    outs = eng.serve([Request(prompts[0].copy(), 4),
                      Request(prompts[0].copy(), 4)], max_active=2)
    np.testing.assert_array_equal(outs[0], outs[1])
    # 12-token prompt = 3 full pages per layer, shared by the 2nd request
    assert pool.stats["shared_puts"] == cfg.num_layers * 3
    assert len(pool.pages) == 0                # shared pages freed last


def test_admission_gated_on_pool_headroom(cfg, ref):
    params, prompts, _, _ = ref
    # budget fits exactly one request's worst case -> requests serialize
    need = cfg.num_layers * (-(-(12 + 4) // 4) + 1)
    pool = PagedKVPool(page_tokens=4, capacity_pages=need)
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    outs = eng.serve([Request(prompts[0].copy(), 4),
                      Request(prompts[1].copy(), 4)], max_active=2)
    assert all(len(o) == 4 for o in outs)
    assert eng.last_peak_active == 1
    assert len(pool.pages) == 0


def test_never_fitting_request_rejected_without_aborting(cfg, ref):
    """An impossible request is rejected at submit time with a structured
    verdict (reason + pages needed vs. budget) — it never does work, and
    the REST of the workload completes normally."""
    params, prompts, _, expected = ref
    need = cfg.num_layers * (-(-(12 + 4) // 4) + 1)
    pool = PagedKVPool(page_tokens=4, capacity_pages=need)
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    outs = eng.serve([Request(prompts[0].copy(), 4),
                      Request(prompts[1].copy(), 40)],   # can never fit
                     max_active=2)
    assert len(outs[0]) == 4                   # first request unaffected
    assert outs[1] is None                     # rejected, not raised
    ok, bad = eng.last_rejections
    assert ok is None
    assert not bad.admitted and bad.reason == "pool_capacity"
    assert bad.pages_needed > bad.pages_budget
    assert "never be admitted" in bad.detail
    assert eng.last_request_stats[1]["rejected"] == "pool_capacity"
    assert len(pool.pages) == 0                # nothing leaked


def test_admission_budget_excludes_preexisting_pages(cfg, ref):
    """Pages left live by a static generate() batch sharing the pool
    shrink the serve budget — the gate reasons about real headroom."""
    params, prompts, _, _ = ref
    need = cfg.num_layers * (-(-(12 + 4) // 4) + 1)
    pool = PagedKVPool(page_tokens=4, capacity_pages=need + 2)
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    eng.generate([Request(prompts[2].copy(), 2)])     # leaves pages live
    assert len(pool.pages) > 0
    [out] = eng.serve([Request(prompts[1].copy(), 4)])
    assert out is None
    [bad] = eng.last_rejections
    assert bad.reason == "pool_capacity" and "already live" in bad.detail


def test_generate_free_pages_returns_pool_to_empty(cfg, ref):
    params, prompts, news, expected = ref
    pool = PagedKVPool(page_tokens=4)
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    outs = eng.generate([Request(prompts[0].copy(), news[0])],
                        free_pages=True)
    np.testing.assert_array_equal(outs[0], expected[0])
    assert len(pool.pages) == 0
    assert pool.stats["fast_bytes"] == 0 and pool.stats["slow_bytes"] == 0


def test_gather_slot_overflow_raises_value_error(cfg, rng):
    """More pages than the page table holds must raise (not a stripped-out
    assert): a `python -O` server must not silently corrupt the table."""
    pool = PagedKVPool(page_tokens=4)
    state = PagedKVState(pool, capacity=8, num_layers=1, hkv=2, hd=8)
    kv = rng.standard_normal((4 * (state.slots + 1), 2, 8)) \
        .astype(np.float32)
    state.write_prefill(0, 0, kv, kv.copy())
    with pytest.raises(ValueError, match="sequence 0"):
        state.begin_step([0], np.zeros(1, np.int32))


def test_continuous_requires_pool_and_attention_stack(cfg):
    eng = ServeEngine(cfg)
    with pytest.raises(ValueError, match="kv_pool"):
        eng.serve([Request(np.arange(4, dtype=np.int32), 2)])
    mla = smoke_config("minicpm3-4b")    # MLA: compressed-kv, not paged
    eng2 = ServeEngine(mla, kv_pool=PagedKVPool(page_tokens=4))
    with pytest.raises(NotImplementedError, match="paged"):
        eng2.serve([Request(np.arange(4, dtype=np.int32), 2)])
