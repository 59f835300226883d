"""Serving benchmark, one run of one cell.

    python3 benchmarks/serving/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs its accelerator: without a
TPU, with fewer chips than the cell asks for, or on a chip whose kind has
no entry in `peaks.json`, it prints no result and exits 2. JAX's
persistent compilation cache is kept where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` at the root of the checkout.

The last lines of standard error are the numbers the correctness check
compared, each beside its limit; the last line of standard output is the
result object (`harness.run_cell`). ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the last 20 s of the measured
window (`harness.TRACE_S`) with the profiler and reports its per-layer
metrics instead.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(CHECKOUT / "src"))
sys.path.insert(0, str(HERE.parent))


def _finite(obj):
    """JSON has no infinity: a non-finite number is written as a string."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return str(obj)
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def enable_compile_cache():
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        from serving import harness, spec
        wl = spec.load_workload(args.workload)
    except Exception as e:      # noqa: BLE001 - no result without the files
        print(f"run: cannot load workload {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run: no TPU (JAX found {devices[0].platform}); nothing was "
              f"run", file=sys.stderr)
        return 2
    if len(devices) < wl["chips"]:
        print(f"run: {args.workload} needs {wl['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        peaks = spec.peaks(devices[0].device_kind)
    except spec.SpecError as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"run: {args.workload} seed {args.seed} on {wl['chips']} x "
          f"{devices[0].device_kind}, jax {jax.__version__}, compile cache "
          f"{cache}", file=sys.stderr, flush=True)
    result = harness.run_cell(wl, args.seed, args.seconds, bool(args.trace),
                              devices[:wl["chips"]], peaks, T_START)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
