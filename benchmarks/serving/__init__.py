"""Serving benchmark: one cell of `BENCHMARK.json` per run.

    python3 benchmarks/serving/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell needs is found by name: `configs/<config>.json` (model
sizes and the name of its plain reference in `references/`),
`cells/<workload>.json` (deployment sizes, rate or clients, latency
limits, the correctness limit), `traffic/<traffic>.json` (the mix that the
one generator in `gen.py` reads) and `metrics/<metric>.py` (one reader per
per-layer metric). Adding a cell, a mix or a metric adds files; no code
here holds a table of them.
"""
