"""`TraceRecorder.station()`: the stations of a unit's way through the
sweep loop (telemetry/trace.py, STATIONS) -- self time, cost, the
unit's id in a profiler trace, the job's `host=` field, the lint."""

import contextlib
import glob
import hashlib
import os
import re
import subprocess
import sys
import threading
import timeit

import pytest

from dprf_tpu.cli import main as cli_main
from dprf_tpu.engines import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
from dprf_tpu.runtime.dispatcher import Dispatcher
from dprf_tpu.runtime.worker import CpuWorker
from dprf_tpu.telemetry.registry import MetricsRegistry
from dprf_tpu.telemetry import trace as trace_mod
from dprf_tpu.telemetry.trace import (STATIONS, TraceRecorder,
                                      format_stations, get_tracer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def recorder(**kw):
    return TraceRecorder(registry=MetricsRegistry(), **kw)


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(trace_mod, "_perf", c)
    return c


# -- self time ---------------------------------------------------------------

def test_station_nests_and_reports_exact_self_time(clock):
    rec = recorder(enabled=True)
    with rec.station("resolve", unit=3):
        clock.now += 1.0
        with rec.station("wait", unit=3):
            clock.now += 4.0
        clock.now += 0.5
        with rec.station("decode", unit=3):
            clock.now += 2.0
        clock.now += 0.25
    with rec.station("wait", unit=4):
        clock.now += 8.0
    assert rec.station_table() == {"resolve": (1, 1.75),
                                   "wait": (2, 12.0),
                                   "decode": (1, 2.0)}
    # in STATIONS order, whatever order they were opened in
    assert list(rec.station_table()) == ["resolve", "wait", "decode"]


def test_a_child_on_another_recorder_is_still_taken_out(clock):
    """The loop's recorder and the process's default one need not be
    the same object: stations nest on the thread, not the recorder."""
    outer, inner = recorder(enabled=True), recorder(enabled=True)
    with outer.station("probe", unit=1):
        clock.now += 1.0
        with inner.station("decode", unit=1):
            clock.now += 3.0
    assert outer.station_table() == {"probe": (1, 1.0)}
    assert inner.station_table() == {"decode": (1, 3.0)}


def test_a_station_left_by_an_exception_is_counted_and_closed(clock):
    rec = recorder(enabled=True)
    with pytest.raises(ValueError):
        with rec.station("submit", unit=1):
            clock.now += 2.0
            raise ValueError("the dispatch failed")
    with rec.station("submit", unit=2):
        clock.now += 1.0
    assert rec.station_table() == {"submit": (2, 3.0)}


def test_an_undeclared_station_name_is_refused():
    with pytest.raises(KeyError):
        recorder(enabled=True).station("per_lane")


def test_format_stations_reports_the_job_not_the_process():
    before = {"lease": (2, 0.5), "submit": (2, 1.0)}
    after = {"lease": (5, 0.75), "submit": (2, 1.0), "wait": (3, 2.0)}
    assert format_stations(after, since=before) == "lease:0.250,wait:2.000"
    assert format_stations({}, since=before) == ""


def test_the_table_survives_more_threads_than_cores():
    rec = recorder(enabled=True)
    n_threads, n_each = 4 * (os.cpu_count() or 4), 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        for i in range(n_each):
            with rec.station("resolve", unit=i):
                with rec.station("wait", unit=i):
                    pass

    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    table = rec.station_table()
    assert table["resolve"][0] == table["wait"][0] == n_threads * n_each
    # self time: a parent is never charged less than nothing
    assert table["resolve"][1] > -1e-6


# -- cost --------------------------------------------------------------------

def _per_call_us(fn, number=20000):
    return 1e6 * min(timeit.repeat(fn, number=number, repeat=7)) / number


def test_disabled_it_records_nothing_and_costs_what_nullcontext_costs():
    rec = recorder(enabled=False)
    null = contextlib.nullcontext()

    def plain():
        with null:
            pass

    def station():
        with rec.station("submit", unit=7):
            pass

    assert _per_call_us(station) - _per_call_us(plain) < 1.0
    assert rec.station_table() == {}


def test_enabled_with_no_trace_running_it_costs_under_5_us():
    import jax  # noqa: F401 -- the annotation is built once jax is there
    rec = recorder(enabled=True)

    def station():
        with rec.station("submit", unit=7):
            pass

    assert _per_call_us(station) < 5.0
    assert rec.station_table()["submit"][0] > 0


def test_it_does_not_import_jax():
    code = ("import sys\n"
            "from dprf_tpu.telemetry.trace import TraceRecorder\n"
            "rec = TraceRecorder(enabled=True)\n"
            "with rec.station('lease'):\n"
            "    pass\n"
            "assert rec.station_table()['lease'][0] == 1\n"
            "sys.exit(7 if 'jax' in sys.modules else 0)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- the job -----------------------------------------------------------------

def _cpu_job(rec, mask="?l?l?l?l", unit_size=1 << 16):
    reg = MetricsRegistry()
    eng = get_engine("md5")
    gen = MaskGenerator(mask)
    targets = [eng.parse_target("ff" * 16)]      # unmatchable: a sweep
    disp = Dispatcher(gen.keyspace, unit_size, registry=reg, recorder=rec)
    spec = JobSpec(engine="md5", device="cpu", attack="mask",
                   attack_arg=mask, keyspace=gen.keyspace,
                   fingerprint="stations")
    return Coordinator(spec, targets, disp, CpuWorker(eng, gen, targets),
                       registry=reg, recorder=rec)


#: the phase sampler's cadence (DPRF_PERF_SAMPLE) and whether a job so
#: run passes the station `probe`: unset, no unit leaves `submit`
SAMPLED = pytest.mark.parametrize(
    "sample, probed", [(None, False), ("16", True)],
    ids=["default", "DPRF_PERF_SAMPLE=16"])


@pytest.fixture
def cadence(monkeypatch, sample):
    if sample is None:
        monkeypatch.delenv("DPRF_PERF_SAMPLE", raising=False)
    else:
        monkeypatch.setenv("DPRF_PERF_SAMPLE", sample)


@SAMPLED
def test_self_seconds_sum_to_the_loops_elapsed(cadence, probed):
    """What the stations leave out of `Coordinator.run` is the loop's
    own bookkeeping: on a job whose units are worth the while, under a
    twentieth.  (The loop never sleeps here: a unit is always
    leasable.)"""
    rec = get_tracer()
    before = rec.station_table()
    result = _cpu_job(rec).run()
    assert result.exhausted
    table = rec.station_table()
    named = sum(s - before.get(name, (0, 0.0))[1]
                for name, (_, s) in table.items())
    assert 0.95 * result.elapsed <= named <= result.elapsed
    units = -(-456976 // (1 << 16))

    def passed(name):
        return (table.get(name, (0, 0.0))[0]
                - before.get(name, (0, 0.0))[0])

    for name in ("resolve", "complete"):
        assert passed(name) == units
    # sampled, unit 0 is the sampler's: probed, not submitted
    assert passed("probe") == int(probed)
    assert passed("submit") == units - int(probed)


def _crack(tmp_path, capsys, *extra):
    hashes = tmp_path / "h.txt"
    hashes.write_text(hashlib.md5(b"zzy").hexdigest() + "\n")
    rc = cli_main(["crack", "--engine", "md5", "-a", "mask", "?l?l?l",
                   str(hashes), "--unit-size", "4096", "--no-potfile",
                   *extra])
    cap = capsys.readouterr()
    ran = [ln for ln in cap.err.splitlines() if " ran " in ln]
    assert rc == 0 and len(ran) == 1, cap.err
    return cap.out, dict(f.split("=", 1) for f in ran[0].split()
                         if "=" in f)


@SAMPLED
def test_the_ran_line_carries_host_by_station(tmp_path, capsys,
                                              monkeypatch, cadence,
                                              probed):
    out, ran = _crack(tmp_path, capsys, "--device", "cpu")
    assert "zzy" in out
    host = dict(f.split(":") for f in ran["host"].split(","))
    # `targets` is the job's own, open around the hash file's parse
    assert set(host) == {"targets", "lease", "submit", "resolve",
                         "verify", "complete"} | ({"probe"} if probed
                                                     else set())
    assert list(host) == [s for s in STATIONS if s in host]
    assert all(re.fullmatch(r"\d+\.\d{3}", v) for v in host.values())
    assert float(host["submit"]) > 0
    # DPRF_TRACE=0 is read when the recorder is made: the same switch
    monkeypatch.setattr(get_tracer(), "enabled", False)
    out_off, ran_off = _crack(tmp_path, capsys, "--device", "cpu")
    assert "host" not in ran_off
    assert out_off == out


def test_the_ran_line_counts_probe_dispatches_only_when_asked(
        tmp_path, capsys, monkeypatch):
    """A device job (XLA on the CPU): unset, no dispatch is the
    sampler's; DPRF_PERF_SAMPLE=2 sweeps every other unit per batch,
    and the job prints what it printed without."""
    def shapes(ran):
        return {k: int(n) for k, n in
                (f.split(":") for f in ran["dispatch"].split(","))}

    monkeypatch.delenv("DPRF_PERF_SAMPLE", raising=False)
    out, ran = _crack(tmp_path, capsys, "--batch", "1024")
    assert "zzy" in out and "probe" not in shapes(ran)
    assert "probe:" not in ran["host"]
    monkeypatch.setenv("DPRF_PERF_SAMPLE", "2")
    out_sampled, ran_sampled = _crack(tmp_path, capsys,
                                      "--batch", "1024")
    assert shapes(ran_sampled)["probe"] > 0
    assert "probe:" in ran_sampled["host"]
    assert out_sampled == out


@SAMPLED
def test_the_unit_id_rides_every_span_but_lease(tmp_path, capsys,
                                                cadence, probed):
    """A profiler trace of a small device job (XLA on the CPU): the
    stations are events of the host's plane, `wait` lies inside a
    `resolve`, and all but `lease` (and `targets`, which is the job's,
    before any unit) carry their unit's id."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=opts)
    try:
        _crack(tmp_path, capsys, "--batch", "1024")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" /
                          "*" / "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats).get("unit"))
                           for e in line.events
                           if e.name.startswith("dprf:")]
    names = {e[0] for e in events}
    assert {"dprf:" + s for s in ("lease", "submit", "resolve",
                                  "wait", "complete")} <= names
    assert ("dprf:probe" in names) == probed
    assert names <= {"dprf:" + s for s in STATIONS}
    for name, _, _, unit in events:
        assert (unit is None) == (name in ("dprf:lease",
                                           "dprf:targets")), name
    resolves = [e for e in events if e[0] == "dprf:resolve"]
    for name, s, e, unit in events:
        if name == "dprf:wait":
            assert any(r[1] <= s and e <= r[2] and r[3] == unit
                       for r in resolves)
    # a unit or a dispatch each, never a batch: 17,576 candidates in
    # units of 4,096 are five units at most (the plant lies in the
    # last); sampled, unit 0 passes `probe` and not `submit`
    submitted = [e[3] for e in events if e[0] == "dprf:submit"]
    units = sorted({e[3] for e in events if e[0] == "dprf:resolve"})
    assert submitted == units[int(probed):] and len(units) <= 5
    assert [e[3] for e in events if e[0] == "dprf:probe"] \
        == units[:int(probed)]


# -- the lint ----------------------------------------------------------------

def test_check_metrics_refuses_an_undeclared_station(tmp_path):
    pkg = tmp_path / "pkg"
    (pkg / "telemetry").mkdir(parents=True)
    (pkg / "telemetry" / "trace.py").write_text(
        'SPAN_NAMES = ("lease",)\nSTATIONS = ("lease", "submit")\n')
    (pkg / "a.py").write_text(
        'def f(tracer, u):\n'
        '    with tracer.station("submit", unit=u):\n'
        '        pass\n'
        '    with tracer.station("per_lane", unit=u):\n'
        '        pass\n')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_metrics.py"),
         str(pkg)], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "station 'per_lane' not declared" in proc.stdout
    assert "'submit'" not in proc.stdout
