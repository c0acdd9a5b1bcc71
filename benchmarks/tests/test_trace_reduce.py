"""`trace_reduce.reduce` on a hand-made trace with known answers, and
on the part of a real chip trace kept beside this file."""

import json
import os

import pytest

import conftest
import trace_reduce

MS = 1_000_000          # ns
K = "%step.11 = s32[2048,128]{1,0} custom-call(s32[9]{0} %x), custom_call_target=\"tpu_custom_call\""
W = "%while.5 = (s32[], s32[]) while((s32[], s32[]) %tuple)"
F = "%fusion.19 = s32[1024]{0} fusion(s32[256,1]{1,0} %copy.3)"


def synthetic(n_devices=1):
    """A 100 ms slice: two programs of 30 ms and 20 ms, each a `while`
    over kernel calls of 9 ms and a 1 ms fusion; the host in the oracle
    for 25 ms of the 50 idle ms."""
    dev = {"modules": [[10 * MS, 40 * MS, "jit_super_step(1)"],
                       [60 * MS, 80 * MS, "jit_super_step(1)"]],
           "ops": [[10 * MS, 40 * MS, W],
                   [10 * MS, 19 * MS, K], [19 * MS, 20 * MS, F],
                   [20 * MS, 29 * MS, K], [29 * MS, 30 * MS, F],
                   [30 * MS, 39 * MS, K], [39 * MS, 40 * MS, F],
                   [60 * MS, 80 * MS, W],
                   [60 * MS, 69 * MS, K], [69 * MS, 70 * MS, F],
                   [70 * MS, 79 * MS, K], [79 * MS, 80 * MS, F]]}
    return {"devices": {str(i): dev for i in range(n_devices)},
            "host": [[0, 1 * MS, "bench:lease"],
                     [40 * MS, 55 * MS, "bench:oracle"],
                     [85 * MS, 95 * MS, "bench:oracle"],
                     [99 * MS, 100 * MS, "bench:complete"]]}


@pytest.mark.parametrize("n_devices", [1, 4])
def test_known_busy_idle_and_kernel_times(n_devices):
    r = trace_reduce.reduce(synthetic(n_devices), " custom-call(")
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["kernel_s"] == pytest.approx(0.045)
    assert r["kernel_calls"] == 5
    assert r["kernel_whole_s"] == pytest.approx(0.045)
    assert r["n_devices"] == n_devices
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["custom-call step.11"] == pytest.approx(0.045)
    assert ops["fusion fusion.19"] == pytest.approx(0.005)
    # a `while` spans its body: nothing of it is its own
    assert ops.get("while while.5", 0.0) == pytest.approx(0.0)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["bench:oracle"] == pytest.approx(0.025)
    assert gaps["bench:lease"] == pytest.approx(0.001)
    assert gaps["host:other"] == pytest.approx(0.050 - 0.025 - 0.002)


def test_slice_clips_what_straddles_its_edges():
    t = synthetic()
    t["host"][0] = [15 * MS, 16 * MS, "bench:lease"]    # opens mid-kernel
    r = trace_reduce.reduce(t, " custom-call(")
    assert r["window_s"] == pytest.approx(0.085)
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["kernel_s"] == pytest.approx(0.040)
    assert r["kernel_calls"] == 4            # the straddling call is out


def test_nothing_to_read_gives_nothing():
    assert trace_reduce.reduce({"devices": {}, "host": []}, "x") is None
    t = synthetic()
    t["host"] = []
    assert trace_reduce.reduce(t, " custom-call(") is None


def test_recorded_chip_trace():
    path = os.path.join(conftest.HERE, "trace_small.json")
    with open(path) as fh:
        trace = json.load(fh)
    with open(os.path.join(conftest.HERE, "trace_small.expect.json")) as fh:
        want = json.load(fh)
    r = trace_reduce.reduce(trace, " custom-call(")
    for key in ("window_s", "busy_s", "kernel_s", "kernel_calls",
                "kernel_whole_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9), key
    assert 0 < r["kernel_s"] <= r["busy_s"] <= r["window_s"]
    assert r["breakdown"]["device_ops"][0][0].startswith("custom-call")
