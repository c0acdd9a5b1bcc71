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
from typing import NamedTuple

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
    with outer.station("submit", unit=1):
        clock.now += 1.0
        with inner.station("decode", unit=1):
            clock.now += 3.0
    assert outer.station_table() == {"submit": (1, 1.0)}
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


@pytest.mark.parametrize("name", ["per_lane", "probe"])
def test_an_undeclared_station_name_is_refused(name):
    """`probe`: a unit is swept one way, so no station stands for a
    second."""
    assert len(STATIONS) == 8
    with pytest.raises(KeyError):
        recorder(enabled=True).station(name)


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


def test_telemetry_imports_neither_runtime_nor_parallel():
    """The arrows point one way: the loops import telemetry, which
    therefore holds no sweep of a unit."""
    code = ("import importlib, pkgutil, sys\n"
            "import dprf_tpu.telemetry as t\n"
            "for m in pkgutil.iter_modules(t.__path__):\n"
            "    importlib.import_module('dprf_tpu.telemetry.' + m.name)\n"
            "bad = sorted(m for m in sys.modules if m.startswith(\n"
            "    ('dprf_tpu.runtime', 'dprf_tpu.parallel')))\n"
            "sys.exit('imported: %s' % bad if bad else 0)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- the job, by worker family -----------------------------------------------

class Family(NamedTuple):
    """One of the ways a unit's sweep is implemented (whose `process`
    a worker runs), as `dprf crack` selects it."""
    device: str         # --device
    attack: str         # -a
    devices: int        # --devices
    pallas: str         # DPRF_PALLAS
    worker: str         # the class the `ran` line names
    fused: str          # the fused dispatch shape a large unit takes
    shapes: frozenset   # every shape its submit may queue


FAMILIES = {
    # the compiled hash kernel, interpreted on the CPU
    "kernel": Family("jax", "mask", 1, "1", "PallasMaskWorker", "loop",
                     frozenset({"batch", "scan", "loop", "wide"})),
    # one program across the CPU's forced devices (tests/conftest.py)
    "sharded": Family("jax", "mask", 2, "auto", "ShardedMaskWorker",
                      "sshard", frozenset({"batch", "sshard"})),
    # the word-window loop, candidates made on the device
    "wordlist": Family("jax", "wordlist", 1, "auto",
                       "DeviceWordlistWorker", "wsuper",
                       frozenset({"wbatch", "wsuper", "wwide"})),
    # a `process` of the worker's own, run inside `submit`: no
    # PendingUnit, so no `wait` or `decode`, and no dispatch to count
    "serial": Family("cpu", "mask", 1, "auto", "CpuWorker", "",
                     frozenset()),
}

BY_FAMILY = pytest.mark.parametrize("family", list(FAMILIES))

MASK, PLANT = "?l?l?l?d", b"zzy9"        # 175,760 candidates
WORDS = [b"w%04d" % i for i in range(3000)] + [PLANT]
#: units of 16 strides (8 of the kernel's 4,096-lane tile): large
#: enough for the fused shape, a remainder left for the per-batch one
UNIT, WORD_UNIT, BATCH, WORD_BATCH = 32768, 1024, 1024, 128


@pytest.fixture
def fam(monkeypatch, family):
    monkeypatch.setenv("DPRF_PALLAS", FAMILIES[family].pallas)
    return FAMILIES[family]


def _passed(rec, before):
    """{station: (times passed, self seconds)} since `before`."""
    return {name: (n - before.get(name, (0, 0.0))[0],
                   s - before.get(name, (0, 0.0))[1])
            for name, (n, s) in rec.station_table().items()}


@BY_FAMILY
def test_self_seconds_sum_to_the_loops_elapsed(fam):
    """What the stations leave out of `Coordinator.run` is the loop's
    own bookkeeping: on a job whose units are worth the while, under a
    twentieth.  (The loop never sleeps here: a unit is always
    leasable.)  The worker is the one `dprf crack` would build, and
    the job finds what the oracle finds."""
    from dprf_tpu.cli import _select_worker
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.runtime.workunit import WorkUnit
    from dprf_tpu.utils.logging import Log
    oracle = get_engine("md5")
    # the plant and a digest nothing hashes to: the job sweeps it all
    targets = [oracle.parse_target(hashlib.md5(PLANT).hexdigest()),
               oracle.parse_target("ff" * 16)]
    if fam.attack == "wordlist":
        gen = WordlistRulesGenerator(WORDS, None, max_len=16)
        unit_size, batch = WORD_UNIT, WORD_BATCH
    else:
        gen = MaskGenerator(MASK)
        unit_size, batch = UNIT, BATCH
    worker = _select_worker("md5", fam.device, fam.attack, gen, targets,
                            batch, 16, oracle, fam.devices,
                            Log(quiet=True))
    assert type(worker).__name__ == fam.worker
    if hasattr(worker, "warmup"):
        worker.warmup()      # the loop joins a compile outside a station
    rec, reg = get_tracer(), MetricsRegistry()
    disp = Dispatcher(gen.keyspace, unit_size, registry=reg, recorder=rec)
    spec = JobSpec(engine="md5", device=fam.device, attack=fam.attack,
                   attack_arg=MASK, keyspace=gen.keyspace,
                   fingerprint="stations")
    before = rec.station_table()
    result = Coordinator(spec, targets, disp, worker, registry=reg,
                         recorder=rec, oracle=oracle).run()
    assert result.exhausted
    want = CpuWorker(oracle, gen, targets).process(
        WorkUnit(-1, 0, gen.keyspace))
    assert result.found == {h.target_index: h.plaintext for h in want} \
        == {0: PLANT}
    table = _passed(rec, before)
    named = sum(s for _, s in table.values())
    assert 0.95 * result.elapsed <= named <= result.elapsed
    units = -(-gen.keyspace // unit_size)
    # once a unit; `decode` and `verify` where a unit reported: one
    # did.  (`lease` also when it finds nothing left to hand out.)
    want_passed = {"submit": units, "resolve": units, "verify": 1,
                   "complete": units}
    if fam.shapes:
        want_passed.update(wait=units, decode=1)
    assert {name: n for name, (n, _) in table.items()
            if n and name != "lease"} == want_passed
    assert table["lease"][0] >= units
    shapes = getattr(worker, "dispatches", {})
    assert set(shapes) <= fam.shapes and (not fam.fused
                                          or shapes[fam.fused] > 0)


def _crack(tmp_path, capsys, fam):
    hashes = tmp_path / "h.txt"
    hashes.write_text(hashlib.md5(PLANT).hexdigest() + "\n")
    if fam.attack == "wordlist":
        words = tmp_path / "words.txt"
        words.write_bytes(b"\n".join(WORDS) + b"\n")
        attack = [str(words), "--unit-size", str(WORD_UNIT),
                  "--batch", str(WORD_BATCH)]
    else:
        attack = [MASK, "--unit-size", str(UNIT), "--batch", str(BATCH)]
    rc = cli_main(["crack", "--engine", "md5", "-a", fam.attack, *attack,
                   str(hashes), "--no-potfile", "--device", fam.device,
                   "--devices", str(fam.devices)])
    cap = capsys.readouterr()
    ran = [ln for ln in cap.err.splitlines() if " ran " in ln]
    assert rc == 0 and len(ran) == 1, cap.err
    return cap.out, dict(f.split("=", 1) for f in ran[0].split()
                         if "=" in f)


@BY_FAMILY
def test_the_ran_line_carries_host_by_station(tmp_path, capsys,
                                              monkeypatch, fam):
    out, ran = _crack(tmp_path, capsys, fam)
    assert PLANT.decode() in out
    assert ran["worker"] == fam.worker
    host = dict(f.split(":") for f in ran["host"].split(","))
    # `targets` is the job's own, open around the hash file's parse
    assert set(host) == {"targets", "lease", "submit", "resolve",
                         "verify", "complete"} | (
                             {"wait", "decode"} if fam.shapes else set())
    assert list(host) == [s for s in STATIONS if s in host]
    assert all(re.fullmatch(r"\d+\.\d{3}", v) for v in host.values())
    assert float(host["submit"]) > 0
    # the shapes the family's own submit queues, the fused one among
    # them, and no other
    if fam.shapes:
        shapes = {k: int(n) for k, n in
                  (f.split(":") for f in ran["dispatch"].split(","))}
        assert set(shapes) <= fam.shapes and shapes[fam.fused] > 0
    else:
        assert ran["dispatch"] == "none"
    # DPRF_TRACE=0 is read when the recorder is made: the same switch
    monkeypatch.setattr(get_tracer(), "enabled", False)
    out_off, ran_off = _crack(tmp_path, capsys, fam)
    assert "host" not in ran_off
    assert out_off == out


@BY_FAMILY
def test_the_unit_id_rides_every_span_but_lease(tmp_path, capsys, fam):
    """A profiler trace of a small job (on the CPU): the stations are
    events of the host's plane, `wait` lies inside a `resolve`, and
    all but `lease` (and `targets`, which is the job's, before any
    unit) carry their unit's id."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=opts)
    try:
        _crack(tmp_path, capsys, fam)
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
    assert names == {"dprf:" + s for s in STATIONS
                     if fam.shapes or s not in ("wait", "decode")}
    for name, _, _, unit in events:
        assert (unit is None) == (name in ("dprf:lease",
                                           "dprf:targets")), name
    resolves = [e for e in events if e[0] == "dprf:resolve"]
    for name, s, e, unit in events:
        if name in ("dprf:wait", "dprf:decode"):
            assert any(r[1] <= s and e <= r[2] and r[3] == unit
                       for r in resolves)
    # a unit or a dispatch each, never a batch: every unit passes
    # `submit`, `resolve`, `complete` (and `wait`, where it is pending)
    # once, under its own id; the plant lies in the last, which alone
    # is decoded and verified
    units = sorted(e[3] for e in resolves)
    assert units == list(range(len(units))) and 2 <= len(units) <= 6
    for once in ("submit", "complete") + (("wait",) if fam.shapes
                                          else ()):
        assert sorted(e[3] for e in events
                      if e[0] == "dprf:" + once) == units, once
    for last in ("verify",) + (("decode",) if fam.shapes else ()):
        assert [e[3] for e in events
                if e[0] == "dprf:" + last] == units[-1:], last


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
