"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds, for the
benchmark's own tests: the real mix, the real metric readers and the real
reference, on a two-layer model of width 64 and a 256-token vocabulary.
"""
from __future__ import annotations

import json
from pathlib import Path

from serving import spec

SMALL = {
    "starcoder2": {
        "file": {"hidden_size": 64, "intermediate_size": 128,
                 "num_attention_heads": 4, "num_key_value_heads": 2,
                 "num_hidden_layers": 2, "vocab_size": 256},
        "program": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                    "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                    "vocab_size": 256}},
    "mamba2": {
        "file": {"d_model": 64, "n_layer": 2, "vocab_size": 256},
        "assumed": {"d_state": 16, "headdim": 16},
        "program": {"num_layers": 2, "d_model": 64, "vocab_size": 256,
                    "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 32}},
}


def workload(tmp: Path, name: str, limit: float = 1.0) -> dict:
    """The workload ``name`` at the tiny size, with its files written
    under ``tmp``; returns what `spec.load_workload` does. A cell that
    BENCHMARK.json does not list (yet) is found by its file name,
    ``<config>.<traffic>``, with the metrics of the listed cells of its
    loop."""
    bench = spec.benchmark()
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        config, traffic = name.split(".", 1)
        loop = json.load(open(spec.HERE / "traffic" / f"{traffic}.json"))
        like = next(w for w in bench["workloads"] if json.load(open(
            spec.HERE / "traffic" / f"{w['traffic']}.json"))["loop"]
            == loop["loop"])
        for kind in ("end_to_end", "per_layer"):
            for m in bench[kind]:
                if like["name"] in m.get("workloads", [name]):
                    m["workloads"] = m["workloads"] + [name]
        wl = {"name": name, "config": config, "traffic": traffic,
              "chips": 1, "why": "tiny"}
        bench["configs"].append({"name": config, "file":
                                 f"benchmarks/serving/configs/{config}.json",
                                 "reduced": []})
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = json.load(open(spec.ROOT / entry["file"]))
    small = SMALL[cfg["reference"]]
    cfg.update(small["file"])
    cfg["assumed"].update(small.get("assumed", {}))
    cfg["program"]["overrides"] = dict(cfg["program"]["overrides"],
                                       **small["program"])
    mix = json.load(open(spec.HERE / "traffic" / f"{wl['traffic']}.json"))
    mix["prompt"].update(median=24, min=8, max=48)
    mix["output"].update(median=8, min=4, max=16)
    cell = json.load(open(spec.HERE / "cells" / f"{name}.json"))
    cell.update(max_active=4, capacity=64, fill_s=1.5, drain_s=20)
    if "rate_rps" in cell:
        cell["rate_rps"] = 3.0
    if "clients" in cell:
        cell["clients"] = 8
    cell["correct"] = {"limit": limit, "min_tokens": 24, "min_requests": 2,
                       "max_requests": 4}
    for d in ("configs", "cells", "traffic"):
        (tmp / d).mkdir(parents=True, exist_ok=True)
    (tmp / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp / "cells" / f"{name}.json").write_text(json.dumps(cell))
    (tmp / "traffic" / f"{wl['traffic']}.json").write_text(json.dumps(mix))
    bench["configs"] = [dict(entry, file="configs/tiny.json")]
    bench["workloads"] = [wl]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return spec.load_workload(name, root=tmp, here=tmp)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
