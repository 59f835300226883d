"""The reduction from a profiler trace to device busy and idle time,
per-operation device time and exposed collective time: on a small trace
recorded on one TPU v5e (`fixtures/v5e_small.xplane.pb`: three calls of a
jitted program holding a Pallas kernel and a matmul, inside the
benchmark's own host spans) and on made-up intervals."""
from __future__ import annotations

import pytest

from serving import trace_reduce as tr

FIXTURE = tr.__file__.rsplit("/", 1)[0] + "/fixtures/v5e_small.xplane.pb"


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.length([(0, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def test_busy_idle_and_exposed_collectives():
    ops = [tr.Op("fusion.1", "", 0, 100), tr.Op("all-reduce.2", "", 80, 60),
           tr.Op("fusion.3", "", 200, 50), tr.Op("all-reduce.4", "", 300, 20)]
    trace = tr.Trace({"/device:TPU:0": ops},
                     [("bench.step", 0, 150), ("bench.step", 190, 400)])
    red = tr.reduce(trace, (0, 400))
    assert red["busy_s"] == pytest.approx(210e-9)   # 0-140, 200-250, 300-320
    assert red["window_s"] == pytest.approx(400e-9)
    assert red["collective_s"] == pytest.approx(80e-9)
    assert red["exposed_s"] == pytest.approx(60e-9)      # 100-140, 300-320
    assert red["op_s"]["fusion.1"] == pytest.approx(100e-9)
    gaps = dict((round(s * 1e9), n) for n, s in red["idle_gaps"])
    assert gaps == {80: "bench.step", 60: tr.OUTSIDE, 50: "bench.step"}
    assert tr.host_activity([("bench.window", 0, 900)], 500, 600) == \
        tr.OUTSIDE
    # a step that only grazes a long gap does not name it
    assert tr.host_activity([("bench.step", 0, 510)], 500, 600) == \
        tr.OUTSIDE
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(100e-9)]


def test_loops_count_through_their_body():
    loop = "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b"
    body = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"
    ops = [tr.Op("while.3", loop, 0, 100), tr.Op("fusion.7", body, 10, 30),
           tr.Op("fusion.7", body, 50, 30)]
    red = tr.reduce(tr.Trace({"d": ops}, []), (0, 100))
    assert red["busy_s"] == pytest.approx(100e-9)
    assert set(red["op_s"]) == {"fusion.7"}
    assert red["op_s"]["fusion.7"] == pytest.approx(60e-9)


def test_two_devices_average():
    a = [tr.Op("fusion", "", 0, 50)]
    b = [tr.Op("fusion", "", 0, 100)]
    red = tr.reduce(tr.Trace({"d0": a, "d1": b}, []), (0, 100))
    assert red["busy_s"] == pytest.approx(75e-9)
    assert red["op_s"]["fusion"] == pytest.approx(150e-9)


def test_recorded_v5e_trace():
    trace = tr.load(FIXTURE)
    assert list(trace.devices) == ["/device:TPU:0"]
    names = [n for n, _, _ in trace.host]
    assert names.count("bench.step") == 3 and "bench.window" in names
    red = tr.reduce(trace)
    # three calls of ~5.2 us each: copy, Pallas kernel, matmul fusion
    assert 14e-6 < red["busy_s"] < 17e-6
    assert red["busy_s"] < red["window_s"]
    kern = tr.kernel_seconds(red, r'custom_call_target="tpu_custom_call"',
                             r"\[512,512\]")
    assert kern == pytest.approx((1984 + 2145 + 1898) * 1e-9)
    assert red["op_s"]["convolution_tanh_fusion"] == \
        pytest.approx((3200 + 3138 + 3118) * 1e-9)
    assert red["collective_s"] == 0 and red["exposed_s"] == 0
    # two idle gaps of ~4 ms between the three calls
    gaps = [s for _, s in red["idle_gaps"] if s > 1e-6]
    assert len(gaps) == 2 and all(3.5e-3 < g < 4.5e-3 for g in gaps)
    b = tr.breakdown(red)
    assert b["device_ops"][0][0] == "convolution_tanh_fusion"
    assert b["idle_gaps"][0][1] == max(gaps)
