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


# -- `kernel_sweeps` and `mask_kernel_roofline` -------------------------------

BATCH = 4194304


def kernel_obs(trace, sweeps, n_devices=1):
    """What the two readers take, around a reduced trace: an `md5-mask`
    job on v5e chips whose units in flight during the slice hold the
    lanes the trace's kernel calls swept, over `sweeps`."""
    import traffic
    r = trace_reduce.reduce(trace, " custom-call(")
    in_flight = round(r["kernel_calls"] * BATCH * n_devices / sweeps)
    plant = traffic.Plant(0, b"abcdefghi", "", "tail")
    return {"trace": r, "t_close": 100.0, "n_devices": n_devices,
            "device_kind": "TPU v5 lite",
            "cfg": {"engine": "md5", "targets": 1,
                    "flags": {"batch": BATCH}},
            "plan": traffic.Plan(0, "", "md5", 0, 0, 0, 0, [], [plant]),
            # in flight: one unit completed inside the slice, one never
            "units": [(0, in_flight // 2, 99.0, 99.99),
                      (0, in_flight - in_flight // 2, 99.0, None)],
            # neither was in flight: completed long before the slice;
            # a one-target job's tail, leased after the window's close
            "tail_units": [(0, BATCH, 1.0, 2.0),
                           (0, 400 * BATCH, 100.001, 110.0)]}


@pytest.mark.parametrize("sweeps", [0.9, 1.9, None])
@pytest.mark.parametrize("trace,n", [(synthetic, 1),
                                     (lambda: synthetic(4), 4),
                                     (recorded, 1)],
                         ids=["synthetic", "synthetic4", "recorded"])
def test_kernel_sweeps_and_the_roofline_s_numerator(trace, n, sweeps):
    """Hashed once (the calls sweep 0.9 of the units in flight: the
    units at the slice's edges are counted whole) or swept twice (1.9):
    the roofline is, to the digit, calls x lanes over the kernel's
    seconds, the kernel's share as it was called; `kernel_sweeps`
    alone says which it was, and nothing is raised.  No kernel call in
    the trace: both read nothing."""
    import metrics.kernel_sweeps as sweeps_reader
    import metrics.mask_kernel_roofline as roofline
    import work
    t = trace()
    if sweeps is None:
        for dev in t["devices"].values():
            dev["ops"] = [e for e in dev["ops"]
                          if " custom-call(" not in e[2]]
        obs = kernel_obs(t, 1.0, n)
        assert obs["trace"]["kernel_calls"] == 0
        assert sweeps_reader.read(obs) is None
        assert roofline.read(obs) is None
        return
    obs = kernel_obs(t, sweeps, n)
    r = obs["trace"]
    as_called = (100.0 * (r["kernel_calls"] * BATCH / r["kernel_whole_s"])
                 * work.ops_of(obs) / work.peak_int32("TPU v5 lite"))
    assert sweeps_reader.read(obs) == pytest.approx(sweeps, rel=1e-6)
    value = roofline.read(obs)
    assert 0 < value < 100
    assert value == as_called
    if trace is recorded:               # the chip's own kernel: 37.5 %
        assert 36.5 < value < 38.5


@pytest.mark.parametrize("sweeps", [2.6, 3.2])
def test_more_sweeps_than_any_program_makes_is_an_error(sweeps):
    """A redrive reads at most 2.  Over `work.MAX_SWEEPS` the calls are
    miscounted, and neither `kernels` reader gives a number."""
    import metrics.kernel_sweeps as sweeps_reader
    import metrics.mask_kernel_roofline as roofline
    obs = kernel_obs(synthetic(), sweeps)
    for reader in (sweeps_reader, roofline):
        with pytest.raises(RuntimeError, match="KERNEL_EVENT matches"):
            reader.read(obs)


@pytest.mark.parametrize("extra", [1, 2])
def test_other_custom_calls_counted_as_the_kernel_s(extra):
    """What the guard was written for (PR 29): an entry driver whose
    `KERNEL_EVENT` matches other custom calls beside the kernel's.  One
    more a batch cannot be told from a window swept twice by the count
    alone: `kernel_sweeps` reads 2 where the program sweeps once, and
    says so in the ledger.  Two more a batch (PR 29's three events)
    read 3, which no program does: an error."""
    import metrics.kernel_sweeps as sweeps_reader
    import metrics.mask_kernel_roofline as roofline
    t = synthetic()
    honest = trace_reduce.reduce(synthetic(), " custom-call(")["kernel_calls"]
    for dev in t["devices"].values():
        dev["ops"] += [[s, e, f"%gather.{k} = s32[8] custom-call(s32[8] %p)"]
                       for s, e, n in dev["ops"] if " custom-call(" in n
                       for k in range(extra)]
    obs = kernel_obs(t, 1.0 + extra)
    assert obs["trace"]["kernel_calls"] == (1 + extra) * honest
    if extra == 1:
        assert sweeps_reader.read(obs) == pytest.approx(2.0)
        assert 0 < roofline.read(obs) < 100
    else:
        for reader in (sweeps_reader, roofline):
            with pytest.raises(RuntimeError, match="KERNEL_EVENT matches"):
                reader.read(obs)
