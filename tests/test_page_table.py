"""Page-table rows kept by page events: every step's control block must
equal the one rebuilt from the pool and the device mirror, row by row,
on every path that changes a sequence's pages (prefill, chunk fills,
radix adoption, dedup'd fills, speculative spills, LRU demotion,
preemption, cancellation, ring recycling), and the mirror must hold
each attended page in its current tier; the batched page touches
must demote the same pages and count the same hits as one
``move_to_end`` per page; a steady decode step rebuilds and syncs
nothing."""
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.sibyl.traces import DecodeTraceRecorder
from repro.kernels.paged_attention.paged_attention import live_pages
from repro.serve import tracing
from repro.serve.engine import Request, ServeEngine, ServeSession
from repro.serve.kvcache import PagedKVPool

T = 4          # page tokens: short prompts span several pages


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("starcoder2-7b")


@pytest.fixture(scope="module")
def params(cfg):
    return ServeEngine(cfg, kv_pool=PagedKVPool(page_tokens=T)).params


@pytest.fixture(scope="module")
def ring_cfg():
    return smoke_config("recurrentgemma-2b")


@pytest.fixture(scope="module")
def ring_params(ring_cfg):
    return ServeEngine(ring_cfg).params


def len_column(state, k):
    """The control block's KV-length column."""
    s = state.slots
    if state.layout is not None:
        return state.layout.cols(s, k).len
    return s + 3 if k == 1 else s + 4


def reference_control(state, control, seq_ids, k):
    """``control`` with its page-derived columns rebuilt from the pool
    and the mirror: each live row's page groups zipped from the pool's
    per-layer page lists, their slots, the tail (and spill) slot behind
    them, and the KV length; dead rows the trash slot and length 1."""
    ref = control.copy()
    pool, dev, t, s = state.pool, state._device, state.pool.page_tokens, \
        state.slots
    c_tail = s if state.layout is None else state.layout.cols(s, k).tail
    c_len = len_column(state, k)
    b = len(seq_ids)
    shards = dev.shards if dev is not None else 1
    ref[:, :s] = 0
    for i, seq in enumerate(seq_ids):
        sh = i * shards // b
        if seq < 0:
            if dev is not None:
                ref[i, c_tail] = dev.local_slot(state._trash[sh])
                if k > 1:
                    ref[i, s + 1] = ref[i, c_tail]
            ref[i, c_len] = 1
            continue
        per_layer = [pool.seq_pages(seq, l) for l in range(state.num_layers)]
        groups = list(zip(*per_layer)) if per_layer else []
        n = len(groups)
        if dev is not None and state.num_layers:
            for j, g in enumerate(groups):
                ref[i, j] = dev.local_slot(dev.slot(g[0], sh))
            ref[i, c_tail] = dev.local_slot(state._tail_slot[seq])
            ref[i, n] = ref[i, c_tail]
            if k > 1:
                ref[i, s + 1] = dev.local_slot(state._spill_slot[seq])
                ref[i, n + 1] = ref[i, s + 1]
        ref[i, c_len] = n * t + state.tail_len.get(seq, 0) + 1
    return ref


def check_mirror(state, seq_ids):
    """Every cell a step's live rows attend holds its pool page in the
    page's current tier: float K/V and zero int8 + scales for a fast
    page, the reverse for a slow (demoted) one."""
    dev, pool = state._device, state.pool
    kf, vf, kq, vq, ks, vs = (np.asarray(a) for a in dev.arrays)
    b = len(seq_ids)
    for i, seq in enumerate(seq_ids):
        if seq < 0:
            continue
        sh = i * dev.shards // b
        per_layer = [pool.seq_pages(seq, l) for l in range(state.num_layers)]
        for g in zip(*per_layer):
            slot = dev.slot(g[0], sh)
            for layer, pid in enumerate(g):
                page = pool.pages[pid]
                cell = (kf[layer, slot], vf[layer, slot], kq[layer, slot],
                        vq[layer, slot], ks[layer, slot], vs[layer, slot])
                if page.tier == "fast":
                    want = (*page.data, 0, 0, 0, 0)
                else:
                    (pkq, pks), (pvq, pvs) = page.data
                    want = (0, 0, pkq, pvq, pks[..., 0], pvs[..., 0])
                for got, w in zip(cell, want):
                    np.testing.assert_array_equal(got, np.broadcast_to(
                        w, got.shape))


def check_every_step(state):
    """Wrap ``state.begin_step``: each control block it returns must equal
    `reference_control`, the mirror must hold what the pool holds
    (`check_mirror`), and the state's own invariant check must pass.
    Its ``streamed`` count must be the kernel's `live_pages` over the
    reference's lengths, and ``table`` rows x slots. Returns the list the
    step's ``(rows, rebuilt)`` counts land in."""
    counts = []
    begin = state.begin_step

    def checked(seq_ids, positions, k=1, **kw):
        control = begin(seq_ids, positions, k=k, **kw)
        ref = reference_control(state, control, seq_ids, k)
        np.testing.assert_array_equal(control, ref)
        check_mirror(state, seq_ids)
        state.check_invariants()
        c = tracing.spans(name="serve.begin_step")[-1].counts
        counts.append((c["rows"], c["rebuilt"]))
        s, t = state.slots, state.pool.page_tokens
        lengths = ref[:, len_column(state, k)]
        assert c["streamed"] == live_pages(lengths, k, t, s).sum()
        assert c["table"] == len(seq_ids) * s
        return control

    state.begin_step = checked
    return counts


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _drain(ses, limit=400):
    for _ in range(limit):
        if ses.done:
            return
        ses.step()
    raise AssertionError("session did not drain")


def _plain(cfg, params):
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=T))
    ses = ServeSession(eng, capacity=64, max_active=3, chunked_prefill=False)
    counts = check_every_step(ses.state)
    for n, new, seed in ((9, 10, 0), (14, 6, 1), (5, 12, 2), (11, 7, 3)):
        ses.submit(Request(_prompt(cfg, n, seed), new))
    _drain(ses)
    return ses, counts


def _chunked_radix_dedup(cfg, params):
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=T))
    ses = ServeSession(eng, capacity=64, max_active=3, prefill_budget=2)
    counts = check_every_step(ses.state)
    head = _prompt(cfg, 3 * T, 4)
    same = np.concatenate([head, _prompt(cfg, 3, 5)])
    # two identical prompts prefill side by side: the second's chunk
    # fills dedup onto the first's pages
    for _ in range(2):
        ses.submit(Request(same.copy(), 5))
    _drain(ses)
    # a later request adopts the retired prompts' radix-pinned head
    ses.submit(Request(np.concatenate([head, _prompt(cfg, 6, 6)]), 6))
    _drain(ses)
    st = eng.kv_pool.stats
    assert st["shared_puts"] > 0 and st["adopted_pages"] > 0
    ses.close()
    return ses, counts


def _spec_boundary(cfg, params):
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=T),
                      speculate=4, draft="ngram")
    ses = ServeSession(eng, capacity=96, max_active=2, speculate=4,
                       chunked_prefill=False)
    counts = check_every_step(ses.state)
    motif = _prompt(cfg, 3, 7)
    for new in (20, 13):            # repetitive prompts: drafts accept
        ses.submit(Request(np.tile(motif, 4), new, speculate=4))
    _drain(ses)
    return ses, counts


def _lru_demotion(cfg, params):
    pool = PagedKVPool(page_tokens=T, fast_capacity_pages=4 * cfg.num_layers)
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    ses = ServeSession(eng, capacity=64, max_active=3)
    counts = check_every_step(ses.state)
    for n, new, seed in ((13, 9, 8), (10, 8, 9), (15, 6, 10)):
        ses.submit(Request(_prompt(cfg, n, seed), new))
    _drain(ses)
    assert pool.stats["evictions"] > 0 and pool.stats["slow_hits"] > 0
    return ses, counts


def _preempt_resume(cfg, params):
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=T))
    ses = ServeSession(eng, capacity=64, max_active=2)
    counts = check_every_step(ses.state)
    a = Request(_prompt(cfg, 12, 11), 12)
    ses.submit(a)
    ses.submit(Request(_prompt(cfg, 10, 12), 8))
    for _ in range(6):
        ses.step()
    assert ses.preempt(a)
    _drain(ses)
    assert ses.preemptions == 1 and ses.resumes == 1
    return ses, counts


def _cancel_mid_prefill(cfg, params):
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=T))
    ses = ServeSession(eng, capacity=64, max_active=2)
    counts = check_every_step(ses.state)
    long_req = Request(_prompt(cfg, 7 * T, 13), 4)
    ses.submit(Request(_prompt(cfg, 6, 14), 10))
    ses.submit(long_req)
    for _ in range(3):
        ses.step()
    assert ses._recs[id(long_req)].active.prefilling
    assert ses.cancel(long_req)
    ses.submit(Request(_prompt(cfg, 9, 15), 5))
    _drain(ses)
    return ses, counts


def _ring_recycling(cfg, params):
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=T))
    ses = ServeSession(eng, capacity=128, max_active=2)
    counts = check_every_step(ses.state)
    ses.submit(Request(_prompt(cfg, 24, 16), 40))
    ses.submit(Request(_prompt(cfg, 9, 17), 30))
    _drain(ses)
    assert eng.kv_pool.stats["freed"] > 0      # the window dropped pages
    return ses, counts


CASES = {"plain": _plain, "chunked_radix_dedup": _chunked_radix_dedup,
         "spec_k4_boundary": _spec_boundary, "lru_demotion": _lru_demotion,
         "preempt_resume": _preempt_resume,
         "cancel_mid_prefill": _cancel_mid_prefill}


@pytest.mark.parametrize("case", [*CASES, "ring_recycling"])
def test_control_block_matches_the_pool(case, cfg, params, ring_cfg,
                                        ring_params):
    if case == "ring_recycling":
        _, counts = _ring_recycling(ring_cfg, ring_params)
    else:
        _, counts = CASES[case](cfg, params)
    rows = sum(r for r, _ in counts)
    rebuilt = sum(b for _, b in counts)
    assert rows > 0 and 0 < rebuilt < rows     # reused far more than built


def test_streamed_pages_on_a_batch_with_dead_rows(cfg, params):
    """Two live rows of four: `check_every_step` holds ``streamed`` to the
    kernel's `live_pages` over the reference lengths at every step; each
    dead row adds the one trash page the kernel copies for it, and the
    batch copies far fewer pages than its table has slots."""
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=T))
    ses = ServeSession(eng, capacity=64, max_active=4, chunked_prefill=False)
    check_every_step(ses.state)
    ses.submit(Request(_prompt(cfg, 9, 30), 6))
    ses.submit(Request(_prompt(cfg, 21, 31), 9))
    for _ in range(3):
        ses.step()
        c = tracing.spans(name="serve.begin_step")[-1].counts
        rows = c["table"] // ses.state.slots
        assert c["rows"] == 2 and rows == 4
        # two dead rows' trash pages, and 3 + 6 pages of 4 tokens at least
        assert 2 + 9 <= c["streamed"] < c["table"]
    _drain(ses)


def test_steady_decode_rebuilds_and_syncs_nothing(cfg, params):
    """Tail rows 5..15 of a 16-token page: no page event, so no row is
    rebuilt, the mirror's sync gets no group and writes nothing."""
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=16))
    ses = ServeSession(eng, capacity=64, max_active=2, chunked_prefill=False)
    counts = check_every_step(ses.state)
    dev = ses.state._device
    synced = []
    sync = dev.sync

    def recording_sync(pool, groups, shards=None):
        groups = list(groups)
        synced.append((len(groups), len(dev.stale)))
        return sync(pool, groups, shards)

    dev.sync = recording_sync
    ses.submit(Request(_prompt(cfg, 20, 18), 12))
    ses.step()                       # admission: the row is built once
    assert counts == [(1, 1)] and synced[0][0] == 1
    writes = dev.writes
    for _ in range(9):
        ses.step()
    assert counts[1:] == [(1, 0)] * 9
    assert synced[1:] == [(0, 0)] * 9
    assert dev.writes == writes
    _drain(ses)


def _twin_run(cfg, params, pool):
    """Serve prefix-sharing requests under fast-tier pressure; return the
    pool's hits, evictions, LRU order and slow pages after every step."""
    eng = ServeEngine(cfg, params=params, kv_pool=pool)
    ses = ServeSession(eng, capacity=64, max_active=3)
    head = _prompt(cfg, 2 * T, 19)
    for i, (tail, new) in enumerate(((5, 8), (7, 6), (3, 9), (6, 5))):
        ses.submit(Request(np.concatenate([head, _prompt(cfg, tail, 20 + i)]),
                           new))
    seen = []
    while not ses.done:
        ses.step()
        st = pool.stats
        seen.append((st["fast_hits"], st["slow_hits"], st["evictions"],
                     pool.lru_order(),
                     sorted(p for p, pg in pool.pages.items()
                            if pg.tier == "slow")))
    ses.close()
    return seen


def test_batched_touches_match_one_move_to_end_per_page(cfg, params):
    """A recorder keeps the pool on its per-page path (one record and one
    ``move_to_end`` per page): with it as the reference, the batched
    touches must give the same hits, the same demotion victims and the
    same LRU order after every step."""
    cap = 5 * cfg.num_layers
    batched = PagedKVPool(page_tokens=T, fast_capacity_pages=cap)
    per_page = PagedKVPool(page_tokens=T, fast_capacity_pages=cap)
    per_page.recorder = DecodeTraceRecorder()
    got = _twin_run(cfg, params, batched)
    want = _twin_run(cfg, params, per_page)
    assert got == want
    assert want[-1][2] > 0 and want[-1][1] > 0    # demoted, then read
    assert batched.stats["adopted_pages"] > 0     # prefix-shared pages
