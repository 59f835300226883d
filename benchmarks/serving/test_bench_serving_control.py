"""The correctness comparison separates the program from its control: the
same reference put in the program's place at fp8 (the precision below
bf16) comes out not correct where the program comes out correct, on every
seed, through the run's own comparison, at a size a test run holds. On
the chip the same runs, at the cells' own sizes, set each cell's limit
(`control.py`; PERF.md gives the readings)."""
from __future__ import annotations

import io
import time

import jax
import pytest

from serving import harness, tiny

# Tiny-size limits, from these seeds' readings on the CPU: chat program
# 0 to 0.011, control 0.138 to 0.188; mamba2 program 0 to 0.0007,
# control 0.019 to 0.029.
LIMITS = {"starcoder2-7b-16l.chat": 0.05, "mamba2-780m.chat-burst": 0.005}


@pytest.mark.parametrize("name", sorted(LIMITS))
def test_control_reads_wider_than_the_program(tmp_path, name):
    wl = tiny.workload(tmp_path, name, LIMITS[name])
    for seed in (2 ** 31 + 21, 2 ** 31 + 22, 2 ** 31 + 23):
        res = harness.run_cell(wl, seed, 1.5, False, jax.devices("cpu")[:1],
                               tiny.PEAKS, time.perf_counter(),
                               log_file=io.StringIO(),
                               control=True)
        prog = res["compared"]["logit_gap_max"]["value"]
        ctl = res["control"]["compared"]["logit_gap_max"]["value"]
        assert res["correct"] is True, (seed, prog, ctl)
        assert res["control"]["correct"] is False, (seed, prog, ctl)
        assert list(res)[-1] == "compared"
