"""`chip_spans.reduce` on a hand-made two-chip trace with known
answers; the three readers over it, over a trace without the host's
verification stations and without a trace; `load` on a trace made
here; the whole way from `run.measure` on the CPU."""

import importlib

import pytest

import chip_spans
import conftest

MS = 1_000_000          # ns
READERS = ("reprobe_wait_pct", "verify_idle_pct", "oracle_span_pct")


def synthetic():
    """A 100 ms slice on two chips.  The loop's thread leases, submits
    unit 1, resolves unit 0 (waits, then decodes: readback, re-probes
    enqueued, the singles to the oracle, the wait for the re-probes,
    their lanes to the oracle), verifies its hits (one oracle call)
    and completes.  Chip 1 runs [8, 40] and [75, 100]; chip 0's
    program runs on to 45 (the re-probes), so its module event spans
    five of the ten milliseconds chip 1 idles under `dprf:oracle`."""
    ev = lambda s, e, name, unit=None: [s * MS, e * MS, name, unit]
    loop = [ev(0, 2, "bench:lease"), ev(0.5, 1.5, "dprf:lease"),
            ev(2, 8, "dprf:submit", 1),
            ev(8, 60, "dprf:resolve", 0), ev(8, 10, "dprf:wait", 0),
            ev(10, 58, "dprf:decode", 0), ev(10, 12, "dprf:readback", 0),
            ev(12, 14, "dprf:reprobe", 0), ev(14, 20, "dprf:oracle", 0),
            ev(15, 19, "bench:oracle"), ev(20, 40, "dprf:reprobe_wait", 0),
            ev(40, 50, "dprf:oracle", 0), ev(41, 49, "bench:oracle"),
            ev(60, 70, "dprf:verify", 0), ev(62, 64, "dprf:oracle", 0),
            ev(62.5, 63.5, "bench:oracle"),
            ev(70, 100, "dprf:complete", 0), ev(97, 100, "bench:complete")]
    other = [ev(5, 70, "dprf:submit", 9)]       # another thread's
    return {"modules": {"0": [[8 * MS, 45 * MS, "jit_super_step(1)"],
                              [75 * MS, 100 * MS, "jit_super_step(1)"]],
                        "1": [[8 * MS, 40 * MS, "jit_super_step(1)"],
                              [75 * MS, 100 * MS, "jit_super_step(1)"]]},
            "host": [{"line": "warmup", "events": other},
                     {"line": "python", "events": sorted(loop)}]}


def read(name, reduction):
    reader = importlib.import_module("metrics." + name)
    return reader.read({"chip_spans": reduction})


def test_self_and_inclusive_time():
    r = chip_spans.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.100)
    self_ms = {k: round(v * 1e3, 6) for k, v in r["self_s"].items()}
    assert self_ms["dprf:reprobe_wait"] == 20
    assert self_ms["dprf:oracle"] == 5          # 18 less the proxy's 13
    assert self_ms["bench:oracle"] == 13
    assert self_ms["dprf:decode"] == 8
    assert sum(r["self_s"].values()) == pytest.approx(r["window_s"])
    assert r["inclusive_s"]["dprf:oracle"] == pytest.approx(0.018)
    assert r["inclusive_s"]["dprf:decode"] == pytest.approx(0.048)


def test_each_chip_s_idle_under_the_station_open_at_the_time():
    r = chip_spans.reduce(synthetic())
    ms = lambda chip: {k: round(v * 1e3, 6)
                       for k, v in r["idle_by_chip_s"][chip].items()}
    # chip 0 is busy to 45: of the oracle's ten milliseconds after the
    # re-probes it shows five, chip 1 all ten
    assert ms("0") == {"unnamed": 1.0, "lease": 1, "submit": 6,
                       "oracle": 7, "decode": 8, "resolve": 2,
                       "verify": 8, "complete": 5}
    assert ms("1") == dict(ms("0"), oracle=12)
    assert chip_spans.verify_idle_s(r) == pytest.approx([0.023, 0.028])


def test_the_three_readers():
    r = chip_spans.reduce(synthetic())
    assert read("reprobe_wait_pct", r) == pytest.approx(20.0)
    assert read("verify_idle_pct", r) == pytest.approx(25.5)   # 23, 28
    assert read("oracle_span_pct", r) == pytest.approx(18.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_without_a_trace(name):
    reader = importlib.import_module("metrics." + name)
    assert reader.read({"trace_dir": None}) is None
    assert reader.read({}) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_where_the_program_lacks_the_stations(name):
    """The parent commit's trace: `decode` and `verify` undivided."""
    t = synthetic()
    inner = {"dprf:readback", "dprf:reprobe", "dprf:reprobe_wait",
             "dprf:oracle"}
    t["host"] = [{"line": ln["line"],
                  "events": [e for e in ln["events"] if e[2] not in inner]}
                 for ln in t["host"]]
    assert chip_spans.reduce(t) is None
    assert read(name, chip_spans.reduce(t)) is None


def test_nothing_to_read_gives_nothing():
    t = synthetic()
    t["modules"] = {}
    assert chip_spans.reduce(t) is None
    t = synthetic()
    t["host"] = t["host"][:1]            # no line holds a bench:lease
    assert chip_spans.reduce(t) is None


def test_the_trace_is_loaded_once_a_run(tmp_path, monkeypatch, capsys):
    calls = []
    monkeypatch.setattr(chip_spans, "find_xplane", lambda d: d)
    monkeypatch.setattr(chip_spans, "load",
                        lambda path: calls.append(path) or synthetic())
    obs = {"trace_dir": str(tmp_path)}
    values = [importlib.import_module("metrics." + n).read(obs)
              for n in READERS]
    assert calls == [str(tmp_path)]
    assert values == pytest.approx([20.0, 25.5, 18.0])
    assert "chip_spans: trace loaded in" in capsys.readouterr().err
    # a work directory the trace never reached: nothing, and no raise
    monkeypatch.undo()
    assert chip_spans.chip_spans({"trace_dir": str(tmp_path)}) is None


def test_load_reads_the_host_s_stations(tmp_path):
    """A trace made here, on the CPU: no device plane, so no chip, but
    the host's plane as the chip's trace has it."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:lease"):
            pass
        with jax.profiler.TraceAnnotation("dprf:decode", unit=3):
            with jax.profiler.TraceAnnotation("dprf:oracle", unit=3):
                pass
    finally:
        jax.profiler.stop_trace()
    trace = chip_spans.load(chip_spans.find_xplane(str(tmp_path)))
    assert trace["modules"] == {}
    assert [(e[2], e[3]) for e in chip_spans.loop_events(trace)] == [
        ("bench:lease", None), ("dprf:decode", 3), ("dprf:oracle", 3)]
    assert chip_spans.reduce(trace) is None


def test_a_traced_run_off_the_chip_reads_nothing_and_does_not_raise(tmp_path):
    """`run.measure` on the CPU: the trace holds the stations but no
    device plane, so the three report nothing (never 0)."""
    import jax
    import run
    bench = {"workloads": [{"name": "tiny-md5.crack"}], "end_to_end": [],
             "per_layer": [{"name": n, "unit": "%"}
                           for n in READERS + ("lease_pct",)]}
    r = run.measure("tiny-md5.crack", 2**31 + 41, 1.5, True, jax.devices(),
                    str(tmp_path / "wd"), platform="cpu", interpret=True,
                    bench=bench, data_root=conftest.DATA, reach_chip_s=0.0)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"lease_pct"}
