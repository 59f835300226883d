"""Every configuration, cell, mix and per-layer metric of BENCHMARK.json
loads by its name, and the references' weights have the program's layout.
"""
from __future__ import annotations

import json
import math
import shutil

import jax
import pytest

from serving import spec, tiny

BENCH = spec.benchmark()
LISTED = [w["name"] for w in BENCH["workloads"]]
# every cell file, listed in BENCHMARK.json or kept for a later change
CELLS = sorted(p.stem for p in (spec.HERE / "cells").glob("*.json"))
CONFIGS = sorted(p.stem for p in (spec.HERE / "configs").glob("*.json"))


def test_every_listed_workload_has_its_files():
    assert set(LISTED) <= set(CELLS)
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/serving/configs/{c['name']}.json"


@pytest.mark.parametrize("name", CELLS)
def test_every_workload_loads_by_name(tmp_path, name):
    wl = spec.load_workload(name) if name in LISTED \
        else tiny.workload(tmp_path, name)
    assert wl["config"]["name"] == name.split(".", 1)[0]
    cell = wl["cell"]
    assert cell["max_active"] >= 1 and cell["capacity"] >= 1
    assert wl["traffic"]["loop"] in ("open", "closed")
    if wl["traffic"]["loop"] == "open":
        assert cell["rate_rps"] > 0 and "limits" in cell
    else:
        assert cell["clients"] >= cell["max_active"]
    assert cell["correct"]["limit"] > 0
    names = {m["name"] for m in wl["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert wl["per_layer"], "every cell reports a per-layer metric"
    for m in wl["per_layer"]:
        assert m["moves"] in names, (m["name"], m["moves"])


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_has_a_reader(name):
    assert callable(spec.metric_reader(name))


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_weights_have_the_program_layout(name):
    """The weights a reference draws are the tree the program's own
    parameter specs describe: same paths, shapes and dtypes."""
    from repro.configs import get_config
    from repro.models import Model
    cfg = json.load(open(spec.HERE / "configs" / f"{name}.json"))
    ref = spec.reference(cfg)
    prog = cfg["program"]
    model = Model(get_config(prog["arch"], **prog["overrides"]))
    want = model.abstract_params()
    got = jax.eval_shape(lambda: ref.make_params(cfg, 2 ** 31 + 5))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    assert ref.flops_per_token(cfg) > 0


def test_reduced_keys_are_listed_in_the_config_file():
    for c in BENCH["configs"]:
        cfg = json.load(open(spec.ROOT / c["file"]))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["published"]) == set(c["reduced"])


def test_a_new_metric_file_is_found_without_code(tmp_path):
    """Adding a metric is adding its reader file: the loader has no table
    of metric names."""
    shutil.copytree(spec.HERE / "metrics", tmp_path / "metrics")
    (tmp_path / "metrics" / "made_up.ratio.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    assert spec.metric_reader("made_up.ratio", here=tmp_path)({}) == 7.0
    with pytest.raises(spec.SpecError):
        spec.metric_reader("missing", here=tmp_path)


def test_unknown_names_and_chips_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_workload("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")
    pk = spec.peaks("TPU v5 lite")
    assert math.isclose(pk["bf16_flops_per_s"], 197e12)
    assert math.isclose(pk["hbm_bytes_per_s"], 819e9)
