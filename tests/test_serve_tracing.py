"""Spans of the serving step loop (`serve.tracing`): a tiny session driven
through the async front end must record every step with its children at
the right parents, counts equal to what the benchmark's step log rebuilds
from the session's rows, a bounded ring, events on the profiler's host
plane, and the benchmark's span metrics must read them."""
import asyncio
import glob
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.serve import tracing
from repro.serve.engine import Request, ServeEngine
from repro.serve.frontend import AsyncServeFrontend
from repro.serve.kvcache import PagedKVPool
from repro.serve.traffic import MIXES, ROUND_TRIP, run_trace

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
from serving import harness, spec, steplog, tiny  # noqa: E402

# the benchmark metrics that read the spans
SPAN_METRICS = {"host_serial_share", "host_serial_share.batch",
                "begin_step_ms", "end_step_ms", "session_host_ms",
                "frontend_gap_ms", "page_table_reuse_share",
                "page_table_reuse_share.batch"}
CHILDREN = ("serve.admit", "serve.begin_step", "serve.dispatch",
            "serve.device_wait", "serve.end_step", "serve.deliver")


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("starcoder2-7b")


@pytest.fixture(scope="module")
def params(cfg):
    return ServeEngine(cfg, kv_pool=PagedKVPool(page_tokens=4)).params


def _drive(cfg, params, requests, profile_dir=None):
    """Serve ``requests`` through a fresh front end with the step log
    wrapped around the session; returns (step log, the run's spans)."""
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=4))
    log = steplog.StepLog()

    async def go():
        async with AsyncServeFrontend(eng, capacity=48,
                                      max_active=3) as front:
            log.wrap(front.session)
            handles = [await front.submit(r) for r in requests]
            for h in handles:
                await h.result()

    t0 = time.perf_counter()
    if profile_dir is not None:
        jax.profiler.start_trace(profile_dir)
    try:
        asyncio.run(go())
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
    return log, tracing.spans(t0, time.perf_counter())


def _requests(cfg, n=5):
    rng = np.random.default_rng(7)
    return [Request(rng.integers(0, cfg.vocab_size, p).astype(np.int32),
                    max_new_tokens=m)
            for p, m in zip((9, 14, 5, 11, 7)[:n], (6, 3, 8, 4, 5)[:n])]


@pytest.fixture(scope="module")
def served(cfg, params):
    return _drive(cfg, params, _requests(cfg))


def test_every_step_has_its_children_at_the_right_parent(served):
    log, spans = served
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == log.count > 0
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s.parent, []).append(s)
    for st in steps:
        names = [c.name for c in by_parent.get(st.index, [])]
        assert names == list(CHILDREN), names
        assert st.parent == -1
    assert {s.name for s in spans} == {"serve.step",
                                       "serve.frontend.deliver",
                                       *CHILDREN}


def test_step_counts_match_the_step_log(served):
    log, spans = served
    steps = [s for s in spans if s.name == "serve.step"]
    assert any(s.counts["wide"] for s in steps)
    assert any(not s.counts["wide"] for s in steps)
    for rec, st in zip(log.steps, steps):
        c = st.counts
        assert (c["live"], bool(c["wide"]), c["tokens"], c["prompt"]) \
            == (rec.live, rec.wide, rec.tokens, rec.prompt)


def test_step_is_its_children_plus_self_time(served):
    _, spans = served
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    for st in (s for s in spans if s.name == "serve.step"):
        cs = sorted(kids[st.index], key=lambda c: c.start)
        for a, b in zip(cs, cs[1:]):
            assert a.end <= b.start           # children never overlap
        assert st.start <= cs[0].start and cs[-1].end <= st.end
        self_s = st.elapsed - sum(c.elapsed for c in cs)
        assert 0.0 <= self_s <= st.elapsed
        assert st.elapsed == pytest.approx(
            sum(c.elapsed for c in cs) + self_s)


def test_gather_s_is_fed_by_the_bookkeeping_spans(served):
    log, spans = served
    steps = [s for s in spans if s.name == "serve.step"]
    for rec, st in zip(log.steps, steps):
        book = sum(c.elapsed for c in spans if c.parent == st.index
                   and c.name in ("serve.begin_step", "serve.end_step"))
        assert rec.gather_s == pytest.approx(book, rel=1e-9, abs=1e-12)


def test_frontend_delivers_once_per_step(served):
    _, spans = served
    steps = [s for s in spans if s.name == "serve.step"]
    fronts = [s for s in spans if s.name == "serve.frontend.deliver"]
    assert len(fronts) == len(steps)
    for st, fr in zip(steps, fronts):
        assert st.end <= fr.start and fr.parent == -1


def test_span_handle_and_nesting():
    with tracing.span("t.outer", a=1) as outer:
        with tracing.span("t.inner") as inner:
            assert inner.elapsed >= 0.0       # open: time so far
        outer.set(b=2)
    assert inner.parent == outer.index and outer.end >= inner.end
    assert outer.counts == {"a": 1, "b": 2}
    got = tracing.spans(outer.start, inner.start + 1e-9)
    assert [s.name for s in got] == ["t.outer", "t.inner"]
    assert tracing.spans(outer.start, outer.start) == []
    assert tracing.spans(name="t.inner")[-1] is inner


def test_ring_keeps_its_bound():
    t0 = time.perf_counter()
    for i in range(tracing.MAXLEN + 10):
        with tracing.span("t.fill", i=i):
            pass
    assert len(tracing._ring) == tracing.MAXLEN
    kept = tracing.spans(t0, name="t.fill")
    assert len(kept) == tracing.MAXLEN
    assert kept[0].counts["i"] == 10 and kept[-1].counts["i"] \
        == tracing.MAXLEN + 9


def test_spans_land_on_the_profilers_host_plane(cfg, params, tmp_path):
    from jax.profiler import ProfileData
    _, spans = _drive(cfg, params, _requests(cfg, 2), str(tmp_path))
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    names, steps = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    names.append(ev.name)
                    if ev.name == "serve.step":
                        steps.append(dict(ev.stats))
    assert set(names) == {s.name for s in spans}
    assert len(steps) == sum(s.name == "serve.step" for s in spans)
    # the step's counts ride on its profiler event too
    assert all({"step_num", "live", "wide", "tokens", "prompt"} <= set(st)
               for st in steps)
    assert sorted(st["step_num"] for st in steps) \
        == list(range(len(steps)))


def test_decode_p99_during_prefill_reads_the_wide_steps(cfg, params):
    eng = ServeEngine(cfg, params=params, kv_pool=PagedKVPool(page_tokens=4))
    t0 = time.perf_counter()
    out = run_trace(eng, MIXES["chunked"], max_active=3)
    ran = tracing.spans(t0)
    mixed = [s for s in ran if s.name == "serve.step" and s.counts["prompt"]
             and s.counts["tokens"] > s.counts["prompt"]]
    assert mixed, "the chunked mix decodes beside prompt chunks"
    ms = []
    for st in mixed:
        trip = [c for c in ran if c.parent == st.index
                and c.name in ROUND_TRIP]
        assert sorted(c.name for c in trip) == sorted(ROUND_TRIP)
        took = sum(c.elapsed for c in trip)
        assert took < st.elapsed      # the round trip, not the whole step
        ms.append(took * 1e3 / (st.counts["tokens"] - st.counts["prompt"]))
    assert min(ms) <= out["decode_p99_during_prefill_ms"] <= max(ms)


@pytest.mark.parametrize("cell", ["starcoder2-7b-16l.chat",
                                  "starcoder2-7b-16l.completion-batch"])
def test_traced_tiny_run_reports_every_span_metric(cell, tmp_path):
    wl = tiny.workload(tmp_path, cell, 0.2)
    res = harness.run_cell(wl, 2 ** 31 + 11, 2.0, True,
                           jax.devices("cpu")[:1], tiny.PEAKS,
                           time.perf_counter())
    assert res["correct"] is True, res
    want = SPAN_METRICS & {m["name"] for m in spec.metrics_of(
        spec.benchmark(), cell, "per_layer")}
    assert want and want <= set(res["metrics"])
    vals = {k: res["metrics"][k]["value"] for k in want}
    assert all(np.isfinite(v) for v in vals.values()), vals
    for k in ("host_serial_share", "host_serial_share.batch",
              "page_table_reuse_share", "page_table_reuse_share.batch"):
        if k in vals:
            assert 0.0 <= vals[k] <= 100.0
    for k in ("begin_step_ms", "end_step_ms", "session_host_ms",
              "frontend_gap_ms"):
        if k in vals:
            assert vals[k] > 0.0


@pytest.mark.parametrize("metric,missing", [
    ("begin_step_ms", "serve.begin_step"),
    ("end_step_ms", "serve.end_step"),
    ("session_host_ms", "serve.begin_step"),
    ("session_host_ms", "serve.device_wait"),
    ("session_host_ms", "serve.end_step"),
])
def test_span_metric_without_its_spans_reads_none(metric, missing):
    """A child span renamed away leaves its metric with nothing to read:
    None, never the 0 that would read as a perfect score."""
    read = spec.metric_reader(metric)
    t0 = time.perf_counter()
    for _ in range(3):
        with tracing.span("serve.step", step_num=0):
            for name in ("serve.begin_step", "serve.dispatch",
                         "serve.device_wait", "serve.end_step"):
                with tracing.span(name if name != missing
                                  else name + "_renamed"):
                    time.sleep(1e-4)
    ctx = {"window": (t0, time.perf_counter())}
    assert read(ctx) is None
    # the same steps with every child in place do read a value
    t0 = time.perf_counter()
    with tracing.span("serve.step", step_num=0):
        for name in ("serve.begin_step", "serve.device_wait",
                     "serve.end_step"):
            with tracing.span(name):
                time.sleep(1e-4)
    assert read({"window": (t0, time.perf_counter())}) > 0.0
