"""The Nemotron-H cell at a size the CPU runs in seconds: the reference
draws weights in the program's layout; prefill and decode through the
served path agree with the reference's full forward on logits, and the
fp8 control does not; the cell's own readers find their spans."""
from __future__ import annotations

import io
import json
import time

import jax
import pytest

from repro.serve import tracing
from serving import harness, spec, tiny

NAME = "nemotron-h-47b-11l.chat-burst"
# published layers 14-18: M-M*-, every kind of layer
STAGE = {"first_layer": 14, "num_hidden_layers": 5}
# Tiny-size limit, from these seeds' readings on the CPU: program 0.0 and
# 0.0, control 0.173 and 0.371.
LIMIT = 0.05


def workload(tmp, limit=LIMIT):
    """The cell at width 64: 2 SSD groups of 2 heads of 32 channels,
    state 16, a 256-token vocabulary, chunks of 8 in the reference."""
    bench = spec.benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == NAME)
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = json.load(open(spec.ROOT / entry["file"]))
    assert cfg["hybrid_override_pattern"][14:19] == "M-M*-"
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, attention_head_dim=16, vocab_size=256,
               mamba_num_heads=4, mamba_head_dim=32, ssm_state_size=16,
               n_groups=2, chunk_size=8,
               num_hidden_layers=STAGE["num_hidden_layers"])
    cfg["program"]["overrides"] = dict(
        num_layers=5, first_layer=STAGE["first_layer"], d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        ssm_state=16, ssm_head_dim=32, ssm_ngroups=2, ssm_chunk=32)
    mix = json.load(open(spec.HERE / "traffic" / f"{wl['traffic']}.json"))
    mix["prompt"].update(median=24, min=8, max=48)
    mix["output"].update(median=8, min=4, max=16)
    cell = json.load(open(spec.HERE / "cells" / f"{NAME}.json"))
    cell.update(max_active=4, capacity=64, fill_s=1.5, drain_s=20,
                rate_rps=3.0)
    cell["correct"] = {"limit": limit, "min_tokens": 24, "min_requests": 2,
                       "max_requests": 4}
    for d in ("configs", "cells", "traffic"):
        (tmp / d).mkdir(parents=True, exist_ok=True)
    (tmp / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp / "cells" / f"{NAME}.json").write_text(json.dumps(cell))
    (tmp / "traffic" / f"{wl['traffic']}.json").write_text(json.dumps(mix))
    bench["configs"] = [dict(entry, file="configs/tiny.json")]
    bench["workloads"] = [wl]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return spec.load_workload(NAME, root=tmp, here=tmp)


def test_served_logits_agree_and_the_control_does_not(tmp_path):
    wl = workload(tmp_path)
    assert wl["config"]["program"]["arch"] == "nemotron-h-47b"
    for seed in (2 ** 31 + 31, 2 ** 31 + 32):
        res = harness.run_cell(wl, seed, 1.5, False, jax.devices("cpu")[:1],
                               tiny.PEAKS, time.perf_counter(),
                               log_file=io.StringIO(), control=True)
        prog = res["compared"]["logit_gap_max"]["value"]
        ctl = res["control"]["compared"]["logit_gap_max"]["value"]
        assert res["correct"] is True, (seed, prog, ctl)
        assert res["control"]["correct"] is False, (seed, prog, ctl)


def test_traced_tiny_run_reads_the_cells_metrics(tmp_path):
    """A traced run reports every per-layer metric the cell lists except
    those read from the device trace's TPU ops (a CPU trace has none),
    and `rec_store_ms` sits inside `begin_step_ms` plus the releases."""
    wl = workload(tmp_path)
    res = harness.run_cell(wl, 2 ** 31 + 33, 1.5, True,
                           jax.devices("cpu")[:1], tiny.PEAKS,
                           time.perf_counter(), log_file=io.StringIO())
    got = set(res["metrics"])
    want = {m["name"] for m in wl["per_layer"]} - {
        "paged_attention_roofline", "ssd_state_roofline",
        "device_idle_share"}
    assert want <= got, want - got
    assert 0 < res["metrics"]["rec_store_ms"]["value"] \
        < res["metrics"]["begin_step_ms"]["value"] + 1.0


@pytest.mark.parametrize("name", ["rec_store_ms", "ssd_state_roofline"])
def test_reads_none_without_the_program_state(name):
    """A program with no recurrent store (the parent of this cell, or a
    starcoder2 run) leaves both metrics out instead of failing."""
    read = spec.metric_reader(name)
    t0 = time.perf_counter()
    with tracing.span("serve.step", step_num=0):
        pass
    ctx = {"window": (t0, time.perf_counter()), "traced": None, "steps": [],
           "trace": None, "config": {}, "reference": object(), "cell": {},
           "peaks": tiny.PEAKS}
    assert read(ctx) is None


def test_state_roofline_counts_the_store_ops_once():
    """The share is the traffic's least time over the ops with a store
    operand; a loop whose event spans those ops is not counted again."""
    from serving import steplog
    cfg = spec.load_workload(NAME)["config"]
    ref = spec.reference(cfg)
    row = ref.ssd_bytes_per_row(cfg)
    steps = [steplog.Step(t, t + 0.05, live, False, live, 0, 0.0, 0.0, 0.0,
                          live) for t, live in ((1.0, 4), (1.1, 6))]
    least = 2 * 10 * row / tiny.PEAKS["hbm_bytes_per_s"]
    store = "f32[5,33,256,64,256]"
    red = {"op_s": {"while.7": 9.0, "dynamic-slice_fusion.1": least,
                    "add_dynamic-update-slice_fusion.2": least,
                    "fusion.3": 5.0},
           "op_detail": {"while.7": f"%while.7 = ({store}) while({store})",
                         "dynamic-slice_fusion.1": f"fusion({store} %p)",
                         "add_dynamic-update-slice_fusion.2":
                             f"fusion({store} %p)",
                         "fusion.3": "fusion(f32[32,256,64,256] %q)"}}
    ctx = {"traced": (0.0, 2.0), "steps": steps, "trace": red,
           "config": cfg, "reference": ref, "cell": {"max_active": 32},
           "peaks": tiny.PEAKS}
    assert spec.metric_reader("ssd_state_roofline")(ctx) \
        == pytest.approx(50.0)
