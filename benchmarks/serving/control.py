"""Readings that set a cell's correctness limit, on the chip.

    python3 benchmarks/serving/control.py --workload <cell> \
        --seeds 101,102,103 --seconds 51

For each seed, in one process, one run of the cell as the benchmark makes
it (`harness.run_cell`: its traffic at its rate, fill, window, drain),
whose comparison is then made twice on the same sample of finished
requests: of the program's served tokens, and of the control's choices
(the token the reference ranks first when its matmuls run in fp8, the
precision below the configuration's bf16), each judged against the
cell's limit. Prints one line per seed and a JSON summary last; exits 1
when a program run is not correct or a control run is. The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    from pathlib import Path
    here = Path(__file__).resolve().parent
    sys.path.insert(0, str(here.parents[1] / "src"))
    sys.path.insert(0, str(here.parent))
    import jax
    from serving import harness, spec
    from serving.run import enable_compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 2
    enable_compile_cache()
    wl = spec.load_workload(args.workload)
    peaks = spec.peaks(devices[0].device_kind)

    out, bad = [], 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run_cell(wl, seed, args.seconds, False,
                               devices[:wl["chips"]], peaks,
                               time.perf_counter(), control=True)
        prog = res["compared"]["logit_gap_max"]["value"]
        ctl = res["control"]["compared"]["logit_gap_max"]["value"]
        row = {"seed": seed, "program": prog,
               "program_correct": res["correct"], "control": ctl,
               "control_correct": res["control"]["correct"],
               "limit": res["compared"]["logit_gap_max"]["limit"]}
        bad += (not res["correct"]) + res["control"]["correct"]
        print(json.dumps(row), flush=True)
        out.append(row)
    print(json.dumps({"workload": args.workload, "readings": out}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
