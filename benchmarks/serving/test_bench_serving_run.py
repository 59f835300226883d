"""Whole runs of the harness on the CPU at a tiny size, with the chip
check skipped: the command's refusal without a TPU, a sound run of each
kind of loop, and runs whose timed path is broken underneath, which must
come out not correct.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving import harness, spec, tiny

SECONDS = 2.0
# the widest gap a sound tiny run may read: sound runs read 0 to 0.003,
# a wrong token 0.5 and more (the tiny models' logits spread less than
# the cells')
LIMIT = 0.2


def res_bench(tmp_path):
    """The BENCHMARK.json `run` wrote for the tiny cell."""
    return spec.benchmark(tmp_path)


def run(tmp_path, name, trace=False):
    wl = tiny.workload(tmp_path, name, LIMIT)
    return harness.run_cell(wl, 2 ** 31 + 3, SECONDS, trace,
                            jax.devices("cpu")[:1], tiny.PEAKS,
                            time.perf_counter())


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.HERE / "run.py"), "--workload",
         "starcoder2-7b-16l.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == "", "no result without a chip"
    assert "no TPU" in p.stderr


def test_open_loop_run_reports_every_end_to_end_metric(tmp_path):
    res = run(tmp_path, "starcoder2-7b-16l.chat")
    assert res["correct"] is True, res
    want = {m["name"] for m in spec.metrics_of(
        spec.benchmark(), "starcoder2-7b-16l.chat", "end_to_end")}
    assert set(res["metrics"]) == want and "itl_p95_ms" in want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] == round(3.0 * SECONDS) and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reports_per_layer_metrics(tmp_path):
    res = run(tmp_path, "mamba2-780m.chat-burst", trace=True)
    assert res["correct"] is True, res
    got = set(res["metrics"])
    listed = spec.metrics_of(res_bench(tmp_path), "mamba2-780m.chat-burst",
                             "per_layer")
    # no device planes in a CPU trace: the device metrics drop out
    host = {m["name"] for m in listed if m["source"] != "device_trace"}
    assert {"batch_occupancy", "prefill_step_share"} <= host <= got
    assert not got - host
    assert 0 < res["metrics"]["batch_occupancy"]["value"] <= 100


def test_closed_loop_run(tmp_path):
    res = run(tmp_path, "starcoder2-7b-16l.completion-batch")
    assert res["correct"] is True, res
    want = {m["name"] for m in spec.metrics_of(
        spec.benchmark(), "starcoder2-7b-16l.completion-batch", "end_to_end")}
    assert set(res["metrics"]) == want == {"output_tok_s", "setup_s"}
    assert res["attempted"] > 0


def _alter_tokens(monkeypatch, vocab):
    """A token altered where it is produced: every sampled token the
    fused step hands back is shifted by one."""
    from repro.serve.paged_decode import PagedKVState
    fused, spec_ = PagedKVState.run_fused, PagedKVState.run_spec

    def run_fused(self, *a, **k):
        host, dev = fused(self, *a, **k)
        return (host + 1) % vocab, dev

    def run_spec(self, *a, **k):
        out = spec_(self, *a, **k).copy()
        out[:, :-1] = (out[:, :-1] + 1) % vocab
        return out

    monkeypatch.setattr(PagedKVState, "run_fused", run_fused)
    monkeypatch.setattr(PagedKVState, "run_spec", run_spec)


def _freeze_state(monkeypatch):
    """A step that returns its state unchanged: the fused step works on a
    copy of the KV pool and recurrent store and hands the old ones back."""
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._fused_step_fn

    def fused_step_fn(self, *a, **k):
        fn = orig(self, *a, **k)

        def frozen(params, arrays, *rest):
            out, _new = fn(params, tuple(jnp.array(x, copy=True)
                                         for x in arrays), *rest)
            return out, tuple(arrays)
        return frozen

    monkeypatch.setattr(ServeEngine, "_fused_step_fn", fused_step_fn)


@pytest.mark.parametrize("name", ["starcoder2-7b-16l.chat",
                                  "mamba2-780m.chat-burst"])
@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, name,
                                          fault):
    if fault == "token_altered":
        _alter_tokens(monkeypatch, 256)
    else:
        _freeze_state(monkeypatch)
    res = run(tmp_path, name)
    assert res["correct"] is False, res["compared"]
    assert res["compared"]["logit_gap_max"]["value"] > LIMIT
    assert np.isfinite(res["compared"]["logit_gap_max"]["value"])


def test_page_budget_holds_the_device_slots():
    """32 rows of 1536 tokens take 104 table slots each, 4096 device slots
    in all; every slot but the trash slot and a spill slot per row is
    budget, in pages of every attention layer."""
    cfg = {"assumed": {"page_tokens": 16}}
    assert harness.pool_budget(cfg, {"max_active": 32, "capacity": 1536},
                               16) == 16 * (4096 - 1 - 32)
    assert harness.pool_budget(cfg, {"max_active": 24, "capacity": 2176},
                               16) == 16 * (4096 - 1 - 24)
    assert harness.pool_budget(cfg, {"max_active": 7, "capacity": 64},
                               0) is None


def test_missing_program_state_stops_the_run():
    """The harness reads a few private fields of the program; where one is
    gone it fails, rather than run with a pool that can grow or report
    without the step's rows."""
    from serving import steplog

    class Bare:
        max_active = 4
        state = object()

        def step(self):
            return []

    with pytest.raises(RuntimeError, match="device page pool"):
        harness.check_pool(Bare(), 100, 2)
    harness.check_pool(Bare(), None, 0)     # no KV pages: nothing to check
    with pytest.raises(RuntimeError, match="_rows"):
        steplog._rows(Bare())


def test_sweep_limit_grows_with_the_prompt():
    from types import SimpleNamespace as NS

    from serving import sweep
    limits = {"ttft_ms": 1000, "ttft_ms_per_prompt_token": 8, "itl_ms": 100}

    def req(prompt_len, ttft_s):
        return NS(segment="window", prompt_len=prompt_len, sched=0.0,
                  admit=0.0, first=ttft_s,
                  deliveries=[[ttft_s, 1, 0], [ttft_s + 0.05, 1, 1]])

    # 2.5 s is within the limit of a 512-token prompt (5.1 s), not of a
    # 64-token one (1.5 s)
    rec = {"w0": 0.0, "w1": 10.0, "reqs": [req(512, 2.5), req(64, 2.5)]}
    got = sweep.attainment(rec, NS(steps=[]), limits)
    assert got["met"] == 0.5 and got["requests"] == 2
