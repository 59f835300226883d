"""`page_table_reuse_share`: None where `serve.begin_step` carries no
``rows``/``rebuilt`` counts (a program without cached page-table rows),
and on a tiny traced run of each starcoder2 cell the share of live rows
whose table row was not built from the pool, counted apart from the
spans by wrapping the paged state itself."""
from __future__ import annotations

import time

import jax
import pytest

from repro.serve import tracing
from repro.serve.paged_decode import PagedKVState
from serving import harness, spec, tiny


@pytest.mark.parametrize("name", ["page_table_reuse_share",
                                  "page_table_reuse_share.batch"])
def test_reads_none_without_the_counts(name):
    read = spec.metric_reader(name)
    t0 = time.perf_counter()
    for _ in range(3):
        with tracing.span("serve.step", step_num=0):
            with tracing.span("serve.begin_step"):
                pass
    assert read({"window": (t0, time.perf_counter())}) is None
    t0 = time.perf_counter()
    for rows, rebuilt in ((4, 1), (4, 0), (2, 0)):
        with tracing.span("serve.step", step_num=0):
            with tracing.span("serve.begin_step") as sp:
                sp.set(rows=rows, rebuilt=rebuilt)
    assert read({"window": (t0, time.perf_counter())}) \
        == pytest.approx(90.0)


@pytest.mark.parametrize("cell,name", [
    ("starcoder2-7b-16l.chat", "page_table_reuse_share"),
    ("starcoder2-7b-16l.completion-batch", "page_table_reuse_share.batch")])
def test_traced_tiny_run_reads_the_share(cell, name, tmp_path, monkeypatch):
    steps = []          # (enclosing serve.step index, live rows, built)
    build, begin = PagedKVState._build_row, PagedKVState.begin_step
    built = [0]

    def counting_build(self, seq):
        built[0] += 1
        return build(self, seq)

    def counting_begin(self, seq_ids, *a, **kw):
        parent, b0 = tracing._stack()[-1], built[0]
        out = begin(self, seq_ids, *a, **kw)
        steps.append((parent, sum(s >= 0 for s in seq_ids), built[0] - b0))
        return out

    monkeypatch.setattr(PagedKVState, "_build_row", counting_build)
    monkeypatch.setattr(PagedKVState, "begin_step", counting_begin)
    seen = {}          # the window the reader was given
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
    res = harness.run_cell(wl, 2 ** 31 + 17, 2.0, True,
                           jax.devices("cpu")[:1], tiny.PEAKS,
                           time.perf_counter())
    assert res["correct"] is True, res
    w0, w1 = seen["window"]
    in_window = {s.index for s in tracing.spans(w0, w1, "serve.step")}
    rows = sum(r for p, r, _ in steps if p in in_window)
    rebuilt = sum(b for p, _, b in steps if p in in_window)
    assert rows > rebuilt > 0          # admissions inside the window
    want = 100.0 * (1.0 - rebuilt / rows)
    assert res["metrics"][name]["value"] == pytest.approx(want)
