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
    loop = [[0, 1 * MS, "bench:lease", None],
            [40 * MS, 55 * MS, "bench:oracle", None],
            [85 * MS, 95 * MS, "bench:oracle", None],
            [99 * MS, 100 * MS, "bench:complete", None]]
    return {"devices": {str(i): dev for i in range(n_devices)},
            "host": [{"line": "python3", "events": loop}]}


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


def test_idle_goes_to_the_innermost_span_of_the_loop_s_thread():
    """The program's stations beside the harness's spans: a gap is the
    innermost one's, counted once, and another thread's spans name
    nothing."""
    t = synthetic()
    t["host"][0]["events"] += [
        [60 * MS, 98 * MS, "dprf:probe", 2],
        [84 * MS, 96 * MS, "dprf:decode", 2],
        [98.5 * MS, 100 * MS, "dprf:complete", 2]]
    t["host"].insert(0, {"line": "warmup", "events": [
        [0, 100 * MS, "dprf:submit", 9]]})
    r = trace_reduce.reduce(t, " custom-call(")
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "bench:oracle"
    want = {"bench:oracle": 25, "dprf:probe": 4 + 2, "dprf:decode": 1 + 1,
            "dprf:complete": 0.5, "bench:complete": 1, "bench:lease": 1,
            "host:other": 9 + 5 + 0.5}
    assert {k: round(v * 1e3, 6) for k, v in gaps} == want
    assert sum(v for _, v in gaps) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_slice_clips_what_straddles_its_edges():
    t = synthetic()
    # opens mid-kernel
    t["host"][0]["events"][0] = [15 * MS, 16 * MS, "bench:lease", None]
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
    t = synthetic()
    t["host"][0]["events"].pop()         # no `bench:complete`: no end
    assert trace_reduce.reduce(t, " custom-call(") is None


def recorded():
    import make_trace_small
    with open(os.path.join(conftest.HERE, "trace_small.json")) as fh:
        return make_trace_small.unpack(json.load(fh))


def test_recorded_chip_trace():
    """The last 0.7 s of an `md5-mask.crack` slice (PR 30, on the chip;
    `make_trace_small.py` made it and what is expected of it): eleven
    units, one of them probed."""
    import make_trace_small
    trace = recorded()
    with open(os.path.join(conftest.HERE, "trace_small.expect.json")) as fh:
        want = json.load(fh)
    r = trace_reduce.reduce(trace, " custom-call(")
    for key in make_trace_small.KEPT:
        assert r[key] == pytest.approx(want[key], rel=1e-9), key
    gaps = r["breakdown"]["idle_gaps"]
    assert [k for k, _ in gaps] == [k for k, _ in want["idle_gaps"]]
    assert [v for _, v in gaps] == pytest.approx(
        [v for _, v in want["idle_gaps"]], rel=1e-9)
    assert 0 < r["kernel_s"] <= r["busy_s"] <= r["window_s"]
    assert r["breakdown"]["device_ops"][0][0].startswith("custom-call")
    # the idle has the program's names: the sampler's probed unit first,
    # and next to nothing under no span at all
    idle = r["window_s"] - r["busy_s"]
    assert gaps[0][0] == "dprf:probe"
    assert sum(v for _, v in gaps) == pytest.approx(idle)
    assert dict(gaps).get("host:other", 0.0) < 0.01 * idle
    # the pipeline was drained: the close follows the last program
    assert 0 <= r["close_after_program_s"] < 0.010


# -- `mask_kernel_roofline`'s guard ------------------------------------------

BATCH = 4194304


def roofline_obs(trace, calls_in_flight=None):
    """What the reader takes, around a reduced trace: an `md5-mask` job
    on one v5e chip with that many batches' candidates in flight during
    the slice (the trace's own kernel calls, where none is given)."""
    import traffic
    r = trace_reduce.reduce(trace, " custom-call(")
    if calls_in_flight is None:
        calls_in_flight = r["kernel_calls"]
    plant = traffic.Plant(0, b"abcdefghi", "", "tail")
    return {"trace": r, "t_close": 100.0, "n_devices": 1,
            "device_kind": "TPU v5 lite",
            "cfg": {"engine": "md5", "targets": 1,
                    "flags": {"batch": BATCH}},
            "plan": traffic.Plan(0, "", "md5", 0, 0, 0, 0, [], [plant]),
            "units": [(0, int(calls_in_flight) * BATCH, 99.0, None)],
            "tail_units": [(0, BATCH, 1.0, 2.0)]}    # long completed


@pytest.mark.parametrize("trace", [synthetic, recorded])
def test_roofline_guard_passes_one_custom_call_a_batch(trace):
    import metrics.mask_kernel_roofline as reader
    value = reader.read(roofline_obs(trace()))
    assert 0 < value < 100
    if trace is recorded:               # the chip's own kernel: 37.5 %
        assert 36.5 < value < 38.5


@pytest.mark.parametrize("trace", [synthetic, recorded])
def test_roofline_guard_raises_on_two_custom_calls_a_batch(trace):
    """A second custom call in the programs (a gather's, say) would be
    counted as the kernel's: twice the lanes the ledger has in flight."""
    import metrics.mask_kernel_roofline as reader
    t = trace()
    calls = trace_reduce.reduce(t, " custom-call(")["kernel_calls"]
    for dev in t["devices"].values():
        dev["ops"] += [[s, e, "%gather.1 = s32[8] custom-call(s32[8] %p)"]
                       for s, e, n in dev["ops"] if " custom-call(" in n]
    with pytest.raises(RuntimeError, match="more than one custom call"):
        reader.read(roofline_obs(t, calls))
