"""`paged_attention_skip_share`: None where `serve.begin_step` carries no
``streamed``/``table`` counts (a program whose kernel visits every table
slot), and on a tiny traced run of each starcoder2 cell the share of the
batch's table slots that the kernel's calls did not copy, counted apart
from the spans by recomputing each step's live pages from its control
block."""
from __future__ import annotations

import time

import jax
import pytest

from repro.kernels.paged_attention.paged_attention import live_pages
from repro.serve import tracing
from repro.serve.paged_decode import PagedKVState
from serving import harness, spec, tiny


@pytest.mark.parametrize("name", ["paged_attention_skip_share",
                                  "paged_attention_skip_share.batch"])
def test_reads_none_without_the_counts(name):
    read = spec.metric_reader(name)
    t0 = time.perf_counter()
    for _ in range(3):
        with tracing.span("serve.step", step_num=0):
            with tracing.span("serve.begin_step") as sp:
                sp.set(rows=2, rebuilt=0)
    assert read({"window": (t0, time.perf_counter())}) is None
    t0 = time.perf_counter()
    for streamed, table in ((10, 40), (20, 40), (0, 0)):
        with tracing.span("serve.step", step_num=0):
            with tracing.span("serve.begin_step") as sp:
                sp.set(rows=2, rebuilt=0, streamed=streamed, table=table)
    assert read({"window": (t0, time.perf_counter())}) \
        == pytest.approx(62.5)


@pytest.mark.parametrize("cell,name", [
    ("starcoder2-7b-16l.chat", "paged_attention_skip_share"),
    ("starcoder2-7b-16l.completion-batch",
     "paged_attention_skip_share.batch")])
def test_traced_tiny_run_reads_the_share(cell, name, tmp_path, monkeypatch):
    steps = []          # (enclosing serve.step index, pages, table slots)
    begin = PagedKVState.begin_step

    def counting_begin(self, seq_ids, positions, k=1, **kw):
        parent = tracing._stack()[-1]
        control = begin(self, seq_ids, positions, k=k, **kw)
        lengths = control[:, self.layout.cols(self.slots, k).len]
        t = self.pool.page_tokens
        pages = sum(-(-(int(n) + k - 1) // t) for n in lengths)
        steps.append((parent, pages, len(seq_ids) * self.slots))
        assert pages == live_pages(lengths, k, t, self.slots).sum()
        return control

    monkeypatch.setattr(PagedKVState, "begin_step", counting_begin)
    seen = {}
    reader = spec.metric_reader

    def capturing(metric):
        read = reader(metric)
        if metric != name:
            return read

        def read_and_keep(ctx):
            seen["window"] = ctx["window"]
            return read(ctx)
        return read_and_keep

    monkeypatch.setattr(spec, "metric_reader", capturing)
    wl = tiny.workload(tmp_path, cell, 0.2)
    res = harness.run_cell(wl, 2 ** 31 + 29, 2.0, True,
                           jax.devices("cpu")[:1], tiny.PEAKS,
                           time.perf_counter())
    assert res["correct"] is True, res
    w0, w1 = seen["window"]
    in_window = {s.index for s in tracing.spans(w0, w1, "serve.step")}
    pages = sum(p for s, p, _ in steps if s in in_window)
    table = sum(n for s, _, n in steps if s in in_window)
    assert 0 < pages < table
    want = 100.0 * (1.0 - pages / table)
    assert res["metrics"][name]["value"] == pytest.approx(want)
