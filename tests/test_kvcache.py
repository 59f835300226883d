"""PagedKVPool behaviour: LRU demotion under fast-capacity pressure, int8
quantize/dequantize round-trip error bounds, hit/eviction/byte stats
accounting (the features Sibyl's placement policy observes), and page
lifecycle — free on retire, ref-counted prefix sharing, O(1) eviction."""
import numpy as np

from repro.serve.kvcache import PagedKVPool, dequantize_page, quantize_page


def _page(rng, t=4, h=2, d=8):
    return rng.standard_normal((t, h, d)).astype(np.float32)


def test_lru_demotes_least_recently_used(rng):
    pool = PagedKVPool(page_tokens=4, fast_capacity_pages=2)
    p0 = pool.put(0, _page(rng), _page(rng))
    p1 = pool.put(0, _page(rng), _page(rng))
    pool.touch(p0)                                 # p1 is now the LRU page
    p2 = pool.put(0, _page(rng), _page(rng))       # overflow -> demote p1
    assert pool.pages[p1].tier == "slow" and pool.pages[p1].quantized
    assert pool.pages[p0].tier == "fast"
    assert pool.pages[p2].tier == "fast"
    assert pool.stats["evictions"] == 1


def test_demotion_cascade_respects_capacity(rng):
    pool = PagedKVPool(page_tokens=4, fast_capacity_pages=3)
    for i in range(8):
        pool.put(i % 2, _page(rng), _page(rng))
    fast = [p for p in pool.pages.values() if p.tier == "fast"]
    assert len(fast) == 3
    assert pool.stats["evictions"] == 5
    # the surviving fast pages are the most recently written
    assert sorted(p.page_id for p in fast) == [5, 6, 7]


def test_quantize_roundtrip_error_bound(rng):
    page = rng.standard_normal((16, 4, 8)).astype(np.float32)
    q, s = quantize_page(page)
    assert q.dtype == np.int8 and np.abs(q).max() <= 127
    # symmetric per-row int8: |deq - x| <= scale / 2 = rowmax / 254
    deq = dequantize_page(q, s)
    assert np.all(np.abs(deq - page) <= s / 2 + 1e-7)


def test_demoted_page_dequantizes_within_bound(rng):
    pool = PagedKVPool(page_tokens=8, fast_capacity_pages=1)
    page_k, page_v = _page(rng, t=8), _page(rng, t=8)
    pid = pool.put(3, page_k, page_v)
    pool.put(3, _page(rng, t=8), _page(rng, t=8))  # demotes pid
    k, v = pool.get(pid)
    for got, want in ((k, page_k), (v, page_v)):
        bound = np.abs(want).max(axis=-1, keepdims=True) / 254 + 1e-7
        assert np.all(np.abs(got - want) <= bound)


def test_hit_and_eviction_stats_accounting(rng):
    pool = PagedKVPool(page_tokens=4, fast_capacity_pages=2)
    ids = [pool.put(i % 2, _page(rng), _page(rng)) for i in range(4)]
    assert pool.stats["evictions"] == 2            # 2 overflows of cap 2
    for pid in ids:
        pool.get(pid)
    assert pool.stats["fast_hits"] == 2            # the 2 surviving fast
    assert pool.stats["slow_hits"] == 2            # the 2 demoted
    assert pool.lru_order() == [ids[2], ids[3]]    # in access order
    # touch() records a hit without dequantizing
    pool.touch(ids[2])
    assert pool.stats["fast_hits"] == 3
    assert pool.lru_order() == [ids[3], ids[2]]
    pool.touch(ids[0])
    assert pool.stats["slow_hits"] == 3


def test_touch_many_ticks_clock_once_per_step(rng):
    """The decode-step gather touches every page it reads through
    touch_many: one clock tick for the whole step (not one per page per
    layer — the old per-layer touch loop advanced the clock num_layers x
    pages times per token, skewing Sibyl's clock-phase recency feature),
    each pid touched once per (pid, step)."""
    pool = PagedKVPool(page_tokens=4)
    pids = [pool.put(0, _page(rng), _page(rng), layer=layer)
            for layer in range(3)]
    c0 = pool.clock
    pool.touch_many(pids[::-1] + pids)             # duplicates deduped
    assert pool.clock == c0 + 1
    assert pool.lru_order() == pids[::-1]          # first occurrence wins
    assert pool.stats["fast_hits"] == 3
    pool.touch_many([])                            # an all-dead step still
    assert pool.clock == c0 + 2                    # advances step time


def test_byte_stats_track_put_eviction_and_free(rng):
    """fast_bytes/slow_bytes are maintained across the page lifecycle —
    not just initialized (they feed Sibyl's pressure features)."""
    pool = PagedKVPool(page_tokens=4, fast_capacity_pages=2)
    k, v = _page(rng), _page(rng)
    page_bytes = k.nbytes + v.nbytes
    pool.put(0, k, v)
    pool.put(0, _page(rng), _page(rng))
    assert pool.stats["fast_bytes"] == 2 * page_bytes
    assert pool.stats["slow_bytes"] == 0
    pool.put(0, _page(rng), _page(rng))        # overflow -> demote 1 page
    assert pool.stats["fast_bytes"] == 2 * page_bytes
    # slow page = int8 values + fp32 per-row scales, for k and v
    q, s = quantize_page(k)
    slow_bytes = 2 * (q.nbytes + s.nbytes)
    assert pool.stats["slow_bytes"] == slow_bytes
    assert pool.pages and all(p.nbytes > 0 for p in pool.pages.values())
    pool.free(0)
    assert pool.stats["fast_bytes"] == 0 and pool.stats["slow_bytes"] == 0
    assert len(pool.pages) == 0


def test_eviction_does_not_rescan_pool(rng, monkeypatch):
    """Eviction under heavy pressure (capacity far below page count) must
    pop the LRU structure, never rescan every page per victim."""
    def boom(self):
        raise AssertionError("O(n) pool rescan in the put/evict hot path")

    monkeypatch.setattr(PagedKVPool, "_fast_pages", boom)
    pool = PagedKVPool(page_tokens=2, fast_capacity_pages=4)
    for i in range(256):
        pool.put(i % 8, _page(rng, t=2), _page(rng, t=2))
    assert pool.stats["evictions"] == 252
    assert len(pool._fast_lru) == 4
    fast = [p.page_id for p in pool.pages.values() if p.tier == "fast"]
    assert sorted(fast) == [252, 253, 254, 255]    # most recently written


def test_free_releases_all_seq_layer_pages(rng):
    """Retiring a request frees its pages across every layer; other
    sequences' pages are untouched."""
    pool = PagedKVPool(page_tokens=4)
    for layer in (0, 1):
        pool.put(0, _page(rng), _page(rng), layer=layer)
        pool.put(1, _page(rng), _page(rng), layer=layer)
    destroyed = pool.free(0)
    assert len(destroyed) == 2
    assert pool.stats["freed"] == 2
    assert pool.seq_pages(0, 0) == [] and pool.seq_pages(0, 1) == []
    assert len(pool.pages) == 2
    assert {p.seq_id for p in pool.pages.values()} == {1}
    # freeing an unknown sequence is a no-op
    assert pool.free(7) == []


def test_prefix_pages_shared_and_refcounted(rng):
    """A prefix page shared by two requests is stored once (ref count 2)
    and never freed while one holder lives."""
    pool = PagedKVPool(page_tokens=4)
    k, v = _page(rng), _page(rng)
    a = pool.put(0, k, v, layer=0, content_hash="h0")
    b = pool.put(1, k, v, layer=0, content_hash="h0")
    assert a == b
    assert pool.pages[a].refs == 2
    assert len(pool.pages) == 1
    assert pool.stats["shared_puts"] == 1
    assert pool.seq_pages(0, 0) == [a] and pool.seq_pages(1, 0) == [a]
    # same content hash on another layer is a distinct page
    c = pool.put(0, k, v, layer=1, content_hash="h0")
    assert c != a
    pool.free(0)
    assert a in pool.pages and pool.pages[a].refs == 1
    assert c not in pool.pages                  # layer-1 page had 1 ref
    pool.free(1)
    assert len(pool.pages) == 0
    assert pool.stats["fast_bytes"] == 0


def test_freed_fast_page_leaves_lru_consistent(rng):
    """free() must unlink fast pages from the LRU so later eviction never
    sees a stale id."""
    pool = PagedKVPool(page_tokens=4, fast_capacity_pages=2)
    pool.put(0, _page(rng), _page(rng))
    pool.put(1, _page(rng), _page(rng))
    pool.free(0)
    assert len(pool._fast_lru) == 1
    pool.put(2, _page(rng), _page(rng))
    pool.put(3, _page(rng), _page(rng))         # overflow -> demote seq 1's
    assert pool.stats["evictions"] == 1
    assert [p.tier for p in pool.pages.values()].count("fast") == 2


def test_capacity_headroom(rng):
    pool = PagedKVPool(page_tokens=4)
    assert pool.headroom() == float("inf")
    pool = PagedKVPool(page_tokens=4, capacity_pages=3)
    pool.put(0, _page(rng), _page(rng))
    assert pool.headroom() == 2
    pool.free(0)
    assert pool.headroom() == 3


def test_seq_pages_ordered_per_sequence_and_layer(rng):
    pool = PagedKVPool(page_tokens=4)
    a = pool.put(0, _page(rng), _page(rng), layer=0)
    b = pool.put(1, _page(rng), _page(rng), layer=0)
    c = pool.put(0, _page(rng), _page(rng), layer=1)
    d = pool.put(0, _page(rng), _page(rng), layer=0)
    assert pool.seq_pages(0, 0) == [a, d]
    assert pool.seq_pages(0, 1) == [c]
    assert pool.seq_pages(1, 0) == [b]
    assert pool.seq_pages(2, 0) == []
