"""Knee sweep of an open-loop cell, on the chip: the cell's traffic at
several fixed rates, one engine for all of them.

    python3 benchmarks/serving/sweep.py --workload <cell> \
        --rates 0.5,1,1.5,2 --requests 100 --seed 7 [--max-active 15]

At each rate the window's requests are held to the cell's limits (time to
first token from scheduled arrival at most ``limits.ttft_ms`` plus
``limits.ttft_ms_per_prompt_token`` for each prompt token, so that an
unloaded server meets it for every prompt of the mix; the request's mean
gap between tokens at most ``limits.itl_ms``). Each rate's window is
long enough to hold ``--requests`` scheduled arrivals (bursts count), and
never shorter than ``--seconds``. ``--max-active`` sizes
the batch other than the cell file does, to find the most rows the chip
holds. A request
with no first token by the end of the drain misses. The backlog is
requests arrived minus requests admitted; it grows when it is larger at
the window's end than at its start by more than two requests and a tenth.
The knee is the highest rate at which 90% of the window's requests meet
both limits and the backlog does not grow. One line per rate, a JSON
summary last. Its readings and the rate chosen go into the cell file.
"""
from __future__ import annotations

import argparse
import copy
import json
import math
import sys
import time


def attainment(rec, log, limits) -> dict:
    from serving.stats import percentile
    from serving.steplog import in_window
    w0, w1 = rec["w0"], rec["w1"]
    window = [r for r in rec["reqs"] if r.segment == "window"]
    met = met_ttft = met_tpot = 0
    per_tok = limits.get("ttft_ms_per_prompt_token", 0.0)
    ttfts, tpots = [], []
    for r in window:
        if r.first is None:
            ttfts.append(math.inf)
            continue
        ttft = (r.first - r.sched) * 1e3
        gaps = [(b[0] - a[0]) / b[1] for a, b in zip(r.deliveries,
                                                     r.deliveries[1:])]
        tpot = 1e3 * sum(gaps) / len(gaps) if gaps else 0.0
        ttfts.append(ttft)
        tpots.append(tpot)
        ok_ttft = ttft <= limits["ttft_ms"] + per_tok * r.prompt_len
        met_ttft += ok_ttft
        met_tpot += tpot <= limits["itl_ms"]
        met += ok_ttft and tpot <= limits["itl_ms"]
    steps = in_window(log.steps, w0, w1)
    wide = [s.t1 - s.t0 for s in steps if s.wide]
    narrow = [s.t1 - s.t0 for s in steps if s.wide is False]

    def backlog(t):
        arrived = sum(r.sched <= t for r in rec["reqs"])
        admitted = sum(r.admit is not None and r.admit <= t
                       for r in rec["reqs"])
        return arrived - admitted

    b0, b1 = backlog(w0), backlog(w1)
    n = max(1, len(window))

    def ms(xs, q):
        return round(percentile(xs, q), 1) if xs else None

    return {"requests": len(window), "met": met / n,
            "met_ttft": met_ttft / n, "met_tpot": met_tpot / n,
            "ttft_p50_ms": ms(ttfts, 50), "ttft_p90_ms": ms(ttfts, 90),
            "tpot_p50_ms": ms(tpots, 50), "tpot_p90_ms": ms(tpots, 90),
            "wide_steps": len(wide), "narrow_steps": len(narrow),
            "wide_share": round(sum(wide) / (w1 - w0), 3),
            "occupancy": round(sum(s.live for s in steps) / max(1, len(steps)),
                               2),
            "wide_ms": ms([1e3 * x for x in wide], 50),
            "narrow_ms": ms([1e3 * x for x in narrow], 50),
            "backlog_start": b0, "backlog_end": b1,
            "growing": b1 > b0 + 2 + 0.1 * b0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--drain", type=float, default=20.0)
    ap.add_argument("--max-active", type=int, default=None)
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
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 2
    enable_compile_cache()
    wl = spec.load_workload(args.workload)
    if args.max_active:
        wl["cell"]["max_active"] = args.max_active
    devices = devices[:wl["chips"]]
    built = harness.build(wl, args.seed, devices)

    def say(msg):
        print(msg, file=sys.stderr, flush=True)

    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        w = copy.deepcopy(wl)
        w["cell"]["rate_rps"] = rate
        w["cell"]["drain_s"] = args.drain
        bursts = w["traffic"].get("bursts")
        mean_rate = rate * (1 + (bursts["factor"] - 1) * bursts["length_s"]
                            / bursts["every_s"] if bursts else 1)
        seconds = max(args.seconds, args.requests / mean_rate)
        t = time.perf_counter()
        rec, log, peak, _ = harness.serve(w, args.seed, seconds,
                                          None, devices, say, built=built)
        row = {"rate_rps": rate, "seconds": round(seconds, 1),
               "max_active": w["cell"]["max_active"],
               "peak_bytes": peak,
               **attainment(rec, log, wl["cell"]["limits"])}
        rows.append(row)
        print(json.dumps(row) + f"  ({time.perf_counter() - t:.0f} s)",
              flush=True)
    ok = [r["rate_rps"] for r in rows if r["met"] >= 0.9 and
          not r["growing"]]
    print(json.dumps({"workload": args.workload, "limits":
                      wl["cell"]["limits"], "rows": rows,
                      "knee_rps": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
