"""Finds a cell's files by the names in `BENCHMARK.json`.

`load_workload(name)` joins the workload entry of `BENCHMARK.json` with
`cells/<name>.json`, `configs/<config>.json` and
`traffic/<traffic>.json`; `metric_reader(name)` imports
`metrics/<name>.py`; `reference(config)` imports
`references/<config["reference"]>.py`. Nothing here lists cells, mixes or
metrics: a later change adds files and entries, not code.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]          # the checkout: BENCHMARK.json lives here


class SpecError(RuntimeError):
    """A name in `BENCHMARK.json` with no file behind it, or a file that
    is not what its name promises."""


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"{path} does not exist")
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"BENCHMARK.json names no {what} {name!r}")


def metrics_of(bench: dict, workload: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` entries this workload reports: an
    entry with a `workloads` list applies to the cells it lists, one
    without it to every cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_workload(name: str, root: Path = ROOT,
                  here: Path = HERE) -> dict:
    """Everything one run of the cell needs, as one dict:
    ``{"name", "chips", "bench", "cell", "config", "traffic",
    "end_to_end", "per_layer"}``."""
    bench = benchmark(root)
    wl = _entry(bench["workloads"], name, "workload")
    cfg_entry = _entry(bench["configs"], wl["config"], "config")
    config = _read_json(Path(root) / cfg_entry["file"])
    config.setdefault("name", wl["config"])
    return {
        "name": name,
        "chips": int(wl["chips"]),
        "bench": bench,
        "cell": _read_json(Path(here) / "cells" / f"{name}.json"),
        "config": config,
        "traffic": _read_json(Path(here) / "traffic"
                              / f"{wl['traffic']}.json"),
        "end_to_end": metrics_of(bench, name, "end_to_end"),
        "per_layer": metrics_of(bench, name, "per_layer"),
    }


def _import(path: Path, modname: str):
    if not path.is_file():
        raise SpecError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE):
    """`read(ctx)` of `metrics/<name>.py`: a number, or None when the run
    gave it nothing to read."""
    mod = _import(Path(here) / "metrics" / f"{name}.py",
                  f"serving_metric_{name.replace('.', '_')}")
    return mod.read


def reference(config: dict, here: Path = HERE):
    """The plain float32 reference module named by the config."""
    ref = config["reference"]
    return _import(Path(here) / "references" / f"{ref}.py",
                   f"serving_reference_{ref}")


def peaks(device_kind: str, here: Path = HERE) -> dict:
    """Published peaks of one chip of this kind. A kind missing from the
    table is an error, never a default."""
    table = _read_json(Path(here) / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
