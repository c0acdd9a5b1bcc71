"""`span_reduce.reduce` on a hand-made trace with known answers and on
the part of a real chip trace kept beside this file; `load` on a trace
made here; the three readers over both."""

import importlib
import json
import os

import pytest

import conftest
import make_spans_small
import span_reduce

MS = 1_000_000          # ns
READERS = ("idle_unnamed_pct", "dispatch_idle_pct", "decode_pct")


def synthetic():
    """A 100 ms slice.  The device runs [10, 40] and [60, 80]; the
    loop's thread leases, submits unit 1, resolves unit 0 (waits, then
    decodes with the oracle inside), probes unit 2 (one nested decode)
    and completes; 5 ms of it under no span at all.  (`probe` was a
    station of the program until PR 32; here it stands for any
    station the reducer has no list of: its idle is its own.)"""
    ev = lambda s, e, name, unit=None: [s * MS, e * MS, name, unit]
    loop = [ev(0, 2, "bench:lease"), ev(0.5, 1.5, "dprf:lease"),
            ev(2, 8, "dprf:submit", 1),
            ev(10, 58, "dprf:resolve", 0), ev(10, 41, "dprf:wait", 0),
            ev(42, 57, "dprf:decode", 0), ev(44, 54, "bench:oracle"),
            ev(60, 95, "dprf:probe", 2), ev(85, 90, "dprf:decode", 2),
            ev(96, 100, "dprf:complete", 0), ev(97, 100, "bench:complete")]
    other = [ev(5, 70, "dprf:submit", 9)]       # another thread's
    return {"modules": [[10 * MS, 40 * MS, "jit_super_step(1)"],
                        [60 * MS, 80 * MS, "jit_super_step(1)"]],
            "host": [{"line": "warmup", "events": other},
                     {"line": "python", "events": sorted(loop)}]}


def read(name, reduction):
    reader = importlib.import_module("metrics." + name)
    return reader.read({"span_reduce": reduction})


def test_self_time_goes_to_the_innermost_span():
    r = span_reduce.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.100)
    want = {"bench:lease": 1, "dprf:lease": 1, "dprf:submit": 6, "none": 5,
            "dprf:resolve": 2, "dprf:wait": 31, "dprf:decode": 10,
            "bench:oracle": 10, "dprf:probe": 30, "dprf:complete": 1,
            "bench:complete": 3}
    assert {k: round(v * 1e3, 6) for k, v in r["self_s"].items()} == want
    # the oracle inside the decode is counted once: everything adds up
    assert sum(r["self_s"].values()) == pytest.approx(r["window_s"])
    assert r["verify_path_s"] == pytest.approx(0.020)


def test_idle_lands_under_the_station_open_at_the_time():
    r = span_reduce.reduce(synthetic())
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["idle_s"] == pytest.approx(0.050)
    want = {"unnamed": 6, "lease": 1, "submit": 6, "wait": 1, "resolve": 2,
            "decode": 20, "probe": 10, "complete": 4}
    got = {k: round(v * 1e3, 6) for k, v in r["idle_by_station_s"].items()}
    assert got == want
    assert sum(r["idle_by_station_s"].values()) == pytest.approx(r["idle_s"])


def test_the_three_readers():
    r = span_reduce.reduce(synthetic())
    assert read("idle_unnamed_pct", r) == pytest.approx(12.0)   # of the idle
    assert read("dispatch_idle_pct", r) == pytest.approx(11.0)  # of the slice
    assert read("decode_pct", r) == pytest.approx(20.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_without_a_trace(name):
    reader = importlib.import_module("metrics." + name)
    assert reader.read({"trace_dir": None}) is None
    assert reader.read({}) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_where_the_program_has_no_stations(name):
    """The parent commit's trace: the harness's spans alone."""
    t = synthetic()
    t["host"] = [{"line": ln["line"],
                  "events": [e for e in ln["events"]
                             if e[2].startswith("bench:")]}
                 for ln in t["host"]]
    assert span_reduce.reduce(t) is None
    assert read(name, span_reduce.reduce(t)) is None


def test_nothing_to_read_gives_nothing():
    assert span_reduce.reduce({"modules": [], "host": []}) is None
    t = synthetic()
    t["modules"] = []
    assert span_reduce.reduce(t) is None
    t = synthetic()
    t["host"] = t["host"][:1]            # no line holds a bench:lease
    assert span_reduce.reduce(t) is None


def test_the_slice_clips_what_straddles_its_edges():
    t = synthetic()
    loop = t["host"][1]["events"]
    loop[:] = [e for e in loop if e[2] not in ("bench:lease", "dprf:lease")]
    loop.append([4 * MS, 5 * MS, "bench:lease", None])   # inside the submit
    r = span_reduce.reduce(t)
    assert r["window_s"] == pytest.approx(0.096)
    assert r["self_s"]["dprf:submit"] == pytest.approx(0.003)
    assert r["idle_by_station_s"]["submit"] == pytest.approx(0.004)


def test_the_trace_is_loaded_once_a_run(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(span_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(span_reduce, "load",
                        lambda path: calls.append(path) or synthetic())
    obs = {"trace_dir": str(tmp_path)}
    values = [importlib.import_module("metrics." + n).read(obs)
              for n in READERS]
    assert calls == [str(tmp_path)]
    assert values == pytest.approx([12.0, 11.0, 20.0])
    # a work directory the trace never reached: nothing, and no raise
    monkeypatch.undo()
    assert span_reduce.spans({"trace_dir": str(tmp_path)}) is None


def test_load_reads_annotations_with_their_unit_ids(tmp_path):
    """A trace made here, on the CPU: no device plane, so no programs,
    but the host's plane as the chip's trace has it."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:lease"):
            with jax.profiler.TraceAnnotation("dprf:lease"):
                pass
        with jax.profiler.TraceAnnotation("dprf:submit", unit=7):
            with jax.profiler.TraceAnnotation("not:ours", unit=7):
                pass
    finally:
        jax.profiler.stop_trace()
    trace = span_reduce.load(span_reduce.find_xplane(str(tmp_path)))
    assert trace["modules"] == []
    loop = span_reduce.loop_events(trace)
    assert [(e[2], e[3]) for e in loop] == [
        ("bench:lease", None), ("dprf:lease", None), ("dprf:submit", 7)]
    assert all(e[0] <= e[1] for e in loop)


def test_a_traced_run_off_the_chip_reads_nothing_and_does_not_raise(tmp_path):
    """The whole way from `run.measure` to the readers, on the CPU:
    the trace is there and holds the stations, but no device plane, so
    the three report nothing (never 0) and the run goes on."""
    import jax
    import run
    bench = {"workloads": [{"name": "tiny-md5.crack"}], "end_to_end": [],
             "per_layer": [{"name": n, "unit": "%"}
                           for n in READERS + ("lease_pct",)]}
    r = run.measure("tiny-md5.crack", 2**31 + 26, 1.5, True, jax.devices(),
                    str(tmp_path / "wd"), platform="cpu", interpret=True,
                    bench=bench, data_root=conftest.DATA, reach_chip_s=0.0)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"lease_pct"}
    host = dict(f.split(":") for f in r["ran"]["host"].split(","))
    assert {"lease", "submit", "resolve", "wait", "complete"} <= set(host)
    assert "probe" not in host


def test_check_counts_what_a_sound_trace_must_not_have():
    c = make_spans_small.check(synthetic())
    assert c["stations_seen"] == ["complete", "decode", "lease", "probe",
                                  "resolve", "submit", "wait"]
    assert c["without_unit_id"] == 0
    assert c["wait_outside_resolve"] == 0
    assert c["decode_outside_resolve_or_probe"] == 0
    assert c["program_lease_outside_harness_lease"] == 0
    assert c["harness_complete_outside_program_complete"] == 0
    assert c["events_per_unit_not_probed_max"] == 4      # unit 0's
    t = synthetic()
    loop = t["host"][1]["events"]
    loop.append([98 * MS, 99 * MS, "dprf:wait", None])
    loop.append([8 * MS, 9 * MS, "dprf:decode", 5])
    c = make_spans_small.check(t)
    assert c["without_unit_id"] == 1 and c["wait_outside_resolve"] == 1
    assert c["decode_outside_resolve_or_probe"] == 1


def test_recorded_chip_trace():
    """The last 6 s of an `ntlm-1k.crack` slice (PR 26, on the chip;
    `make_spans_small.py` made it)."""
    with open(os.path.join(conftest.HERE, "spans_small.json")) as fh:
        trace = json.load(fh)
    with open(os.path.join(conftest.HERE, "spans_small.expect.json")) as fh:
        want = json.load(fh)
    r = span_reduce.reduce(trace)
    for key in ("window_s", "busy_s", "idle_s", "verify_path_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9), key
    for key in ("self_s", "idle_by_station_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9), key
    # the numbers close, and the oracle's hashing lies inside the decode
    assert sum(r["self_s"].values()) == pytest.approx(r["window_s"])
    assert sum(r["idle_by_station_s"].values()) == pytest.approx(r["idle_s"])
    assert r["busy_s"] + r["idle_s"] == pytest.approx(r["window_s"])
    assert r["verify_path_s"] >= r["self_s"]["bench:oracle"]
    assert r["idle_by_station_s"].get("unnamed", 0.0) < 0.1 * r["idle_s"]
    c = make_spans_small.check(trace)
    assert c["without_unit_id"] == 0
    assert c["wait_outside_resolve"] == 0
    assert c["decode_outside_resolve_or_probe"] == 0
    assert c["program_lease_outside_harness_lease"] == 0
    assert c["harness_complete_outside_program_complete"] <= 1
    assert 0 < c["events_per_unit_not_probed_max"] < 20
