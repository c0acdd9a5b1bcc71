"""Performance attribution (ISSUE 9): sampled per-phase sweep
accounting, the live busy-fraction gauge, the roofline model, the
bench regression sentinel, and `dprf report`.

Device-engine cases run the XLA md5 pipeline on the CPU backend
(conftest pins jax to cpu); everything is loopback/local.
"""

import hashlib
import json
import time

import pytest

from dprf_tpu import get_engine
from dprf_tpu.cli import main as cli_main
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
from dprf_tpu.runtime.dispatcher import Dispatcher
from dprf_tpu.runtime.worker import CpuWorker
from dprf_tpu.runtime.workunit import WorkUnit
from dprf_tpu.telemetry import perf
from dprf_tpu.telemetry.registry import MetricsRegistry
from dprf_tpu.telemetry.trace import (TraceRecorder, get_tracer,
                                      load_trace, overlap_report,
                                      trace_path)

pytestmark = pytest.mark.smoke

UNMATCHABLE = "ff" * 16


def _recorder(**kw):
    kw.setdefault("enabled", True)
    kw.setdefault("registry", MetricsRegistry())
    return TraceRecorder(**kw)


def _device_worker(mask="?l?l?d", batch=2048):
    eng = get_engine("md5", device="jax")
    oracle = get_engine("md5", device="cpu")
    gen = MaskGenerator(mask)
    targets = [oracle.parse_target(UNMATCHABLE)]
    return eng.make_mask_worker(gen, targets, batch=batch,
                                hit_capacity=16, oracle=oracle), gen


def _local_sweep(mask, unit_size, worker=None, gen=None,
                 registry=None, recorder=None):
    reg = registry or MetricsRegistry()
    rec = recorder or _recorder(registry=reg)
    oracle = get_engine("md5", device="cpu")
    if worker is None:
        gen = MaskGenerator(mask)
        targets = [oracle.parse_target(UNMATCHABLE)]
        worker = CpuWorker(oracle, gen, targets, chunk=8192)
    disp = Dispatcher(gen.keyspace, unit_size, registry=reg,
                      recorder=rec)
    spec = JobSpec(engine="md5", device="cpu", attack="mask",
                   attack_arg=mask, keyspace=gen.keyspace,
                   fingerprint="perftest")
    coord = Coordinator(spec, worker.targets, disp, worker,
                        registry=reg, recorder=rec,
                        oracle=None)
    t0 = time.perf_counter()
    result = coord.run()
    return result, time.perf_counter() - t0, rec, reg


# ---------------------------------------------------------------------------
# probed sweep: phases + hits through the real device worker contract

def test_probe_pending_digit_worker_phases_and_hits(monkeypatch):
    eng = get_engine("md5", device="jax")
    oracle = get_engine("md5", device="cpu")
    gen = MaskGenerator("?l?l?d")
    # planted crack so the probed sweep must decode a REAL hit
    targets = [oracle.parse_target(hashlib.md5(b"zz9").hexdigest())]
    worker = eng.make_mask_worker(gen, targets, batch=2048,
                                  hit_capacity=16, oracle=oracle)
    reg = MetricsRegistry()
    rec = _recorder(registry=reg)
    sampler = perf.PerfSampler(registry=reg, recorder=rec, every=1)
    worker.warmup()
    unit = WorkUnit(7, 0, gen.keyspace)
    p = perf.probe_pending(worker, unit, sampler, trace="t1")
    assert p.resolve() == worker.process(unit)   # identical hits
    assert [h.plaintext for h in p.resolve()] == [b"zz9"]
    for ph in ("generate", "h2d", "device", "d2h"):
        assert p.phases[ph] >= 0.0
    assert p.phases["device"] > 0.0
    # spans: one per phase, parented on the pre-allocated sweep id
    assert {s["attrs"]["phase"] for s in p.phase_spans} == {
        "generate", "h2d", "device", "d2h"}
    assert all(s["parent"] == p.sweep_span for s in p.phase_spans)
    assert all(s["trace"] == "t1" for s in p.phase_spans)
    # histogram observed once per phase
    h = reg.get("dprf_phase_seconds")
    assert h.count(phase="device", engine="md5", job="j0") == 1


def test_probe_pending_coarse_for_custom_process_worker():
    oracle = get_engine("md5", device="cpu")
    gen = MaskGenerator("?l?l")
    targets = [oracle.parse_target(
        hashlib.md5(b"zz").hexdigest())]      # planted at last index
    worker = CpuWorker(oracle, gen, targets)
    reg = MetricsRegistry()
    sampler = perf.PerfSampler(registry=reg, recorder=_recorder(),
                               every=1)
    unit = WorkUnit(0, 0, gen.keyspace)
    p = perf.probe_pending(worker, unit, sampler)
    assert [h.plaintext for h in p.resolve()] == [b"zz"]
    assert set(p.phases) == {"device"}       # coarse: one honest total


# ---------------------------------------------------------------------------
# phase spans sum to ~the sweep span (acceptance criterion)

def test_phase_spans_sum_to_sweep_within_tolerance(monkeypatch):
    monkeypatch.setenv("DPRF_PERF_SAMPLE", "1")
    monkeypatch.setenv("DPRF_PIPELINE_DEPTH", "1")
    worker, gen = _device_worker()
    worker.warmup()
    _, _, rec, _ = _local_sweep("?l?l?d", 2000, worker=worker,
                                gen=gen)
    spans = rec.tail(100000)
    sweeps = {s["span"]: s for s in spans
              if s["name"] == "sweep" and s["attrs"].get("probed")}
    assert len(sweeps) >= 3                  # every unit probed
    by_parent: dict = {}
    for s in spans:
        if s["name"] == "phase":
            by_parent.setdefault(s["parent"], 0.0)
            by_parent[s["parent"]] += s["dur"]
    for sid, sw in sweeps.items():
        total = by_parent.get(sid)
        assert total is not None, "probed sweep lost its phase spans"
        # phases cover the probe work inside the sweep span; the
        # sweep adds only queue/pop overhead at depth 1
        assert total <= sw["dur"] * 1.05 + 0.02
        assert total >= sw["dur"] * 0.5 - 0.02


# ---------------------------------------------------------------------------
# sampling cadence: exactly every Nth unit

def test_sampler_cadence_exact():
    s = perf.PerfSampler(registry=MetricsRegistry(),
                         recorder=_recorder(), every=4)
    takes = [s.take() for _ in range(12)]
    assert takes == [i % 4 == 0 for i in range(12)]
    off = perf.PerfSampler(registry=MetricsRegistry(),
                           recorder=_recorder(), every=0)
    assert not any(off.take() for _ in range(8))


def test_sampled_mode_records_on_configured_cadence(monkeypatch):
    monkeypatch.setenv("DPRF_PERF_SAMPLE", "4")
    _, _, rec, _ = _local_sweep("?l?l?d", 600)   # 6760 -> 12 units
    spans = rec.tail(100000)
    probed = [s for s in spans
              if s["name"] == "sweep" and s["attrs"].get("probed")]
    n_units = len([s for s in spans if s["name"] == "sweep"])
    assert n_units == 12
    assert len(probed) == 3                  # units 1, 5, 9
    # coarse CPU probe: exactly one phase span per probed unit
    assert len([s for s in spans if s["name"] == "phase"]) == 3


def test_sample_zero_disables_probing(monkeypatch):
    monkeypatch.setenv("DPRF_PERF_SAMPLE", "0")
    _, _, rec, reg = _local_sweep("?l?l?d", 600)
    spans = rec.tail(100000)
    assert not [s for s in spans if s["name"] == "phase"]
    assert reg.get("dprf_phase_seconds").count(
        phase="device", engine="md5", job="j0") == 0


# ---------------------------------------------------------------------------
# which units leave the pipelined submit: a count, with no clock in it
# (what a probed unit costs is a chip's to say: PERF.md, PR 31)

@pytest.mark.parametrize("sample, probed_units", [("16", [0, 16]),
                                                  (None, [])],
                         ids=["DPRF_PERF_SAMPLE=16", "default"])
def test_only_the_sampled_units_leave_submit(monkeypatch, sample,
                                             probed_units):
    if sample is None:
        monkeypatch.delenv("DPRF_PERF_SAMPLE", raising=False)
    else:
        monkeypatch.setenv("DPRF_PERF_SAMPLE", sample)
    stations = get_tracer()      # UnitPipeline.submit's: the process's
    before = stations.station_table()
    _, _, rec, reg = _local_sweep("?l?l?d", 212)   # 6760 -> 32 units
    after = stations.station_table()
    sweeps = [s["attrs"] for s in rec.tail(100000)
              if s["name"] == "sweep"]
    assert sorted(a["unit"] for a in sweeps) == list(range(32))
    assert sorted(a["unit"] for a in sweeps
                  if a["probed"]) == probed_units
    passed = {name: after.get(name, (0, 0.0))[0]
              - before.get(name, (0, 0.0))[0]
              for name in ("submit", "probe")}
    assert passed == {"submit": 32 - len(probed_units),
                      "probe": len(probed_units)}
    assert reg.get("dprf_phase_seconds").count(
        phase="device", engine="md5", job="j0") == len(probed_units)


def test_the_cadence_has_one_default_and_it_is_off(monkeypatch):
    """The knob's declared default (utils/env.py) is the only one:
    unset, no unit is ever the sampler's."""
    from dprf_tpu.utils import env as envreg
    monkeypatch.delenv("DPRF_PERF_SAMPLE", raising=False)
    assert envreg.get_int("DPRF_PERF_SAMPLE") == 0
    assert perf.sample_every() == 0
    sampler = perf.PerfSampler(registry=MetricsRegistry(),
                               recorder=_recorder())
    assert sampler.every == 0
    assert not any(sampler.take() for _ in range(64))
    monkeypatch.setenv("DPRF_PERF_SAMPLE", "3")
    assert perf.sample_every() == 3


def test_default_job_observes_verify_alone(monkeypatch):
    """Unset, `dprf_phase_seconds` holds what costs no sync: every
    hit batch's verify, and none of the sweep's phases."""
    monkeypatch.delenv("DPRF_PERF_SAMPLE", raising=False)
    oracle = get_engine("md5", device="cpu")
    gen = MaskGenerator("?l?l?d")
    targets = [oracle.parse_target(hashlib.md5(b"zz9").hexdigest())]
    worker = CpuWorker(oracle, gen, targets, chunk=8192)
    result, _, rec, reg = _local_sweep("?l?l?d", 600, worker=worker,
                                       gen=gen)
    assert result.found == {0: b"zz9"}
    hist = reg.get("dprf_phase_seconds")
    counts = {ph: hist.count(phase=ph, engine="md5", job="j0")
              for ph in perf.PHASES}
    assert counts == {"generate": 0, "h2d": 0, "device": 0, "d2h": 0,
                      "verify": 1}
    assert not [s for s in rec.tail(100000) if s["name"] == "phase"]


# ---------------------------------------------------------------------------
# live busy fraction == tools/trace_overlap.py union math

def test_busy_fraction_gauge_matches_trace_overlap(tmp_path):
    clk = [1000.0]
    reg = MetricsRegistry()
    rec = TraceRecorder(enabled=True, registry=reg,
                        clock=lambda: clk[0])
    stream = str(tmp_path / "s.session.trace.jsonl")
    rec.attach_file(stream, max_bytes=0)
    # worker A: two sweeps with a 2 s hole; worker B: overlapping
    # pipelined sweeps, no hole
    plan = {"wA": [(1000.0, 3.0), (1005.0, 3.0)],
            "wB": [(1000.0, 4.0), (1003.0, 4.0)]}
    for proc, sweeps in plan.items():
        for ts, dur in sweeps:
            clk[0] = ts + dur
            rec.record("sweep", dur=dur, ts=ts, proc=proc,
                       unit=1, length=100)
    clk[0] = 1008.0          # == global last end
    live = rec.busy_fractions()
    rec.detach_file()
    rep = overlap_report(load_trace(stream))
    for proc in plan:
        sweeps = plan[proc]
        t0 = min(ts for ts, _ in sweeps)
        t1 = max(ts + dur for ts, dur in sweeps)
        covered = (t1 - t0) - rep["workers"][proc]["idle_s"]
        expected = covered / (1008.0 - t0)
        assert live[proc] == pytest.approx(expected, abs=1e-3), proc
    assert live["wA"] == pytest.approx(6.0 / 8.0, abs=1e-3)
    assert live["wB"] == pytest.approx(7.0 / 8.0, abs=1e-3)
    # the gauge carries the same values
    g = reg.get("dprf_device_busy_fraction")
    assert g.value(worker="wA") == pytest.approx(6.0 / 8.0, abs=1e-3)


def test_busy_fraction_prunes_outside_window():
    clk = [0.0]
    rec = TraceRecorder(enabled=True, registry=MetricsRegistry(),
                        clock=lambda: clk[0])
    clk[0] = 10.0
    rec.record("sweep", dur=10.0, ts=0.0, proc="w")
    assert rec.busy_fractions()["w"] == pytest.approx(1.0)
    # 100% idle for a window's length: the old interval falls out
    from dprf_tpu.telemetry.trace import BUSY_WINDOW_S
    clk[0] = 10.0 + BUSY_WINDOW_S + 1
    assert rec.busy_fractions()["w"] == 0.0


# ---------------------------------------------------------------------------
# roofline model + gauges

def _no_analyzed_model(monkeypatch):
    """Pin the HAND-model fallback: earlier tests in the session may
    have warmed real workers, landing XLA-derived records in the
    process-global program registry (ISSUE 13) -- these tests assert
    the hand table's band, so the analyzed model must read absent."""
    from dprf_tpu.telemetry import programs
    monkeypatch.setattr(programs, "analyzed_ops_per_candidate",
                        lambda engine, programs=None: None)


V5E = "TPU v5 lite"


def test_roofline_band_and_fraction(monkeypatch):
    _no_analyzed_model(monkeypatch)
    lo, hi = perf.roofline_band_hs("md5", V5E)
    assert (lo, hi) == (4.0e9, 8.0e9)        # documented band
    assert perf.roofline_fraction("md5", 4.0e9,
                                  V5E) == pytest.approx(0.5)
    assert perf.roofline_band_hs("sha1", V5E) == pytest.approx(
        (3.0e12 / 1000, 6.0e12 / 1000))
    assert perf.roofline_band_hs("bcrypt", V5E) is None   # no model
    assert perf.roofline_fraction("bcrypt", 1e9, V5E) is None


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", None])
def test_roofline_is_keyed_by_device_kind(monkeypatch, kind):
    """The band is one chip kind's: a kind that is not in the table
    gets no band, no fraction and NO gauge -- never v5e's numbers."""
    _no_analyzed_model(monkeypatch)
    assert kind not in perf.CHIP_INT_OPS_BANDS
    reg = MetricsRegistry()
    assert perf.roofline_band_hs("md5", kind) is None
    assert perf.roofline_fraction("md5", 4.0e9, kind) is None
    assert perf.analyzed_roofline_fraction("md5", 4.0e9, kind) is None
    assert perf.publish_roofline("md5", 4.0e9, kind,
                                 registry=reg) is None
    assert perf.roofline_snapshot(reg) == {}


def test_measured_cost_band_needs_a_known_kind(monkeypatch):
    """An engine with only a profiler-measured cost: the measured
    device time per candidate is the ceiling's reciprocal."""
    _no_analyzed_model(monkeypatch)
    monkeypatch.setitem(perf._MEASURED_SPC, "no-model-engine", 1e-9)
    lo, hi = perf.roofline_band_hs("no-model-engine", V5E)
    assert hi == pytest.approx(1e9) and lo == pytest.approx(0.5e9)
    assert perf.roofline_fraction("no-model-engine", 5e8,
                                  V5E) == pytest.approx(0.5)
    assert perf.roofline_band_hs("no-model-engine", "cpu") is None


def test_roofline_prefers_analyzed_model(monkeypatch):
    """ISSUE 13: an analyzed program's flops/candidate beats the hand
    table, and covers engines the table never listed."""
    from dprf_tpu.telemetry import programs
    monkeypatch.setattr(programs, "analyzed_ops_per_candidate",
                        lambda engine, programs=None: 1500.0)
    assert perf.ops_per_candidate("sha512") == 1500.0
    assert perf.roofline_band_hs("sha512", V5E) == pytest.approx(
        (3.0e12 / 1500, 6.0e12 / 1500))
    # md5's documented hand band yields to the derived one too
    assert perf.roofline_band_hs("md5", V5E) == pytest.approx(
        (3.0e12 / 1500, 6.0e12 / 1500))
    assert perf.analyzed_roofline_fraction(
        "md5", 2.0e9, V5E) == pytest.approx(2.0e9 / (6.0e12 / 1500))


def test_publish_roofline_smooths_and_snapshots(monkeypatch):
    _no_analyzed_model(monkeypatch)
    reg = MetricsRegistry()
    f1 = perf.publish_roofline("md5", 4.0e9, V5E, registry=reg)
    assert f1 == pytest.approx(0.5)          # first sample unsmoothed
    f2 = perf.publish_roofline("md5", 8.0e9, V5E, registry=reg)
    assert 0.5 < f2 < 1.0                    # EWMA toward 1.0
    snap = perf.roofline_snapshot(reg)
    assert snap["md5"] == pytest.approx(f2)
    assert perf.publish_roofline("bcrypt", 1e9, V5E,
                                 registry=reg) is None


def test_scaling_gauges_published():
    reg = MetricsRegistry()
    perf.publish_scaling("md5", 2.0e9, 0.85, 8, V5E, registry=reg)
    assert reg.get("dprf_per_chip_rate_hs").value(
        engine="md5") == 2.0e9
    assert reg.get("dprf_scaling_efficiency").value(
        engine="md5") == pytest.approx(0.85)


# ---------------------------------------------------------------------------
# bench JSON carries phases

def test_run_bench_cpu_reports_phases():
    from dprf_tpu.bench import run_bench
    res = run_bench(engine="md5", device="cpu", mask="?l?l?l?l",
                    batch=2048, seconds=0.2)
    assert set(res["phases"]) == {"generate", "device"}
    assert all(v >= 0 for v in res["phases"].values())


def test_run_config_reports_phases():
    from dprf_tpu.bench import run_config
    res = run_config(1, device="jax", seconds=0.2, batch=4096)
    ph = res["phases"]
    assert ph["device"] > 0
    assert {"generate", "h2d", "device", "d2h"} <= set(ph)


# ---------------------------------------------------------------------------
# bench regression sentinel

def _plant_bench(tmp_path, values, device="tpu", start_round=1):
    for i, v in enumerate(values):
        line = json.dumps({"metric": "md5 candidates/sec/chip",
                           "value": v, "unit": "H/s",
                           "device": device, "engine": "md5"})
        (tmp_path / f"BENCH_r{start_round + i:02d}.json").write_text(
            json.dumps({"n": start_round + i, "rc": 0,
                        "tail": "noise line\n" + line + "\n"}))


def test_bench_compare_passes_and_fails_planted_trajectories(tmp_path):
    from dprf_tpu.perfreport import compare
    _plant_bench(tmp_path, [5.0e9, 5.1e9, 4.9e9, 5.05e9])
    base = compare.load_bench_records(str(tmp_path))
    assert [r["round"] for r in base] == [1, 2, 3, 4]
    cur = {"value": 4.9e9, "device": "tpu", "engine": "md5"}
    assert compare.gate(cur, base)["verdict"] == "pass"
    bad = {"value": 3.0e9, "device": "tpu", "engine": "md5"}
    v = compare.gate(bad, base)
    assert v["verdict"] == "regression" and v["ratio"] < 0.7
    # a CPU-fallback run must not regress against a TPU baseline
    cpu = {"value": 3.0e6, "device": "cpu", "engine": "md5"}
    assert compare.gate(cpu, base)["verdict"] == "no-baseline"
    # noisy trajectories widen their own tolerance
    noisy = [{"value": x, "device": "tpu", "engine": "md5"}
             for x in (4.0e9, 6.0e9, 5.0e9)]
    dip = {"value": 4.2e9, "device": "tpu", "engine": "md5"}
    v = compare.gate(dip, noisy)
    assert v["verdict"] == "pass" and v["tolerance"] >= 0.4


def test_bench_compare_dry_mode_and_tool_exit_codes(tmp_path):
    import importlib.util
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_compare_tool", os.path.join(repo, "tools",
                                           "bench_compare.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    _plant_bench(tmp_path, [5.0e9, 5.1e9, 4.9e9, 2.0e9])
    assert tool.main(["--dry", "--dir", str(tmp_path), "-q"]) == 1
    _plant_bench(tmp_path, [5.0e9], start_round=5)
    assert tool.main(["--dry", "--dir", str(tmp_path), "-q"]) == 0
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps({"value": 1.0e9, "device": "tpu",
                               "engine": "md5"}))
    assert tool.main(["--current", str(cur), "--dir", str(tmp_path),
                      "-q"]) == 1


def test_bench_gate_dry_cli(tmp_path, capsys):
    _plant_bench(tmp_path, [5.0e9, 5.1e9, 4.9e9, 5.0e9])
    rc = cli_main(["bench", "--gate-dry", "--baseline-dir",
                   str(tmp_path), "--quiet"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["gate"]["verdict"] == "pass"
    _plant_bench(tmp_path, [1.0e9], start_round=5)
    rc = cli_main(["bench", "--gate-dry", "--baseline-dir",
                   str(tmp_path), "--quiet"])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert out["gate"]["verdict"] == "regression"


# ---------------------------------------------------------------------------
# dprf report: the whole post-mortem from session artifacts alone

def test_report_from_session_artifacts(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("DPRF_PERF_SAMPLE", "2")
    monkeypatch.setenv("DPRF_TELEMETRY_INTERVAL", "0.25")
    hashes = tmp_path / "h.txt"
    hashes.write_text(hashlib.md5(b"zz9").hexdigest() + "\n")
    session = str(tmp_path / "job.session")
    rc = cli_main(["crack", "--engine", "md5", "--device", "cpu",
                   "-a", "mask", "?l?l?d", str(hashes),
                   "--session", session, "--unit-size", "600",
                   "--no-potfile", "--quiet"])
    assert rc == 0
    capsys.readouterr()
    from dprf_tpu.perfreport import build_report, render_report
    doc = build_report(session)
    assert doc["engine"] == "md5"
    assert doc["units"] >= 1 and doc["probed_units"] >= 1
    assert doc["phases"]["device"]["count"] >= 1
    assert doc["throughput"]["hs"] and doc["throughput"]["hs"] > 0
    assert doc["busy"] and all(0 <= v <= 1
                               for v in doc["busy"].values())
    assert doc["fair_share"] and doc["fair_share"][0]["job"] == "j0"
    text = render_report(doc)
    assert "phase breakdown" in text and "device busy fraction" in text
    # the CLI renders the same report; --json round-trips
    assert cli_main(["report", session, "--quiet"]) == 0
    assert "throughput" in capsys.readouterr().out
    assert cli_main(["report", session, "--json", "--quiet"]) == 0
    doc2 = json.loads(capsys.readouterr().out)
    assert doc2["units"] == doc["units"]
    # no artifacts at all -> rc 2
    assert cli_main(["report", str(tmp_path / "nope.session"),
                     "--quiet"]) == 2


# ---------------------------------------------------------------------------
# top header carries busy/roofline; status ships them over the RPC

def test_render_top_header_busy_and_roofline():
    from dprf_tpu.telemetry.trace import render_top
    resp = {"status": {"done": 5, "total": 10, "found": 0,
                       "targets": 1, "parked": 0, "stop": False,
                       "elapsed": 3.0, "now": time.time(),
                       "busy": {"w1": 0.9, "w2": 0.7},
                       "roofline": {"md5": 0.62}},
            "spans": [], "leases": [
                {"worker": "w1", "unit": 3, "start": 0,
                 "length": 100, "job": "j1", "deadline_s": 10.0},
                {"worker": "w2", "unit": 4, "start": 100,
                 "length": 100, "job": "j0", "deadline_s": 10.0}]}
    text = render_top(resp)
    assert "busy 80%" in text
    assert "roofline md5:0.62" in text
    # per-job grouping: the j0 worker row sorts before the j1 row
    lines = text.splitlines()
    w1 = next(i for i, ln in enumerate(lines) if ln.startswith("w1"))
    w2 = next(i for i, ln in enumerate(lines) if ln.startswith("w2"))
    assert w2 < w1                            # grouped by job id


def test_probe_pending_wordlist_worker_phases_and_hits():
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    oracle = get_engine("md5", device="cpu")
    words = [b"alpha", b"bravo", b"zulu9", b"kilo", b"tango", b"echo"]
    gen = WordlistRulesGenerator(words, None, max_len=16)
    # planted at the LAST word so the probe sweeps the whole range
    targets = [oracle.parse_target(hashlib.md5(b"echo").hexdigest())]
    worker = get_engine("md5", device="jax").make_wordlist_worker(
        gen, targets, batch=4, hit_capacity=8, oracle=oracle)
    worker.warmup()
    reg = MetricsRegistry()
    sampler = perf.PerfSampler(registry=reg, recorder=_recorder(),
                               every=1)
    unit = WorkUnit(0, 0, gen.keyspace)
    p = perf.probe_pending(worker, unit, sampler)
    assert p.resolve() == worker.process(unit)
    assert [h.plaintext for h in p.resolve()] == [b"echo"]
    # wordlist contract: generation happens ON device, so the split
    # is h2d (scalars) / device / d2h
    assert p.phases["device"] > 0.0
    assert {"h2d", "device", "d2h"} <= set(p.phases)


def test_phase_share_scales_sampled_against_unsampled_verify():
    """1 probed unit in 16 contributes sampled phase durations that
    stand for ~16 units of fleet time; verify spans are per-hit-batch
    and unsampled -- the share must not let verify inflate by the
    sampling factor."""
    from dprf_tpu.perfreport.report import _phase_stats
    spans = ([{"name": "phase", "dur": 1.0, "ts": 0.0,
               "attrs": {"phase": "device"}}]
             + [{"name": "hit_verify", "dur": 1.0, "ts": 0.0}] * 4)
    st = _phase_stats(spans, sample_scale=16.0)
    assert st["device"]["share"] == pytest.approx(16 / 20)
    assert st["verify"]["share"] == pytest.approx(4 / 20)
    assert st["device"]["total_s"] == 1.0      # observed, not scaled
    # unscaled: verify would wrongly dominate
    raw = _phase_stats(spans, sample_scale=1.0)
    assert raw["verify"]["share"] == pytest.approx(0.8)


def test_probe_drains_device_backlog_before_measuring():
    """A sampled probe submitted behind queued pipelined units must
    wait for THEIR device work first, so its synced phase boundaries
    attribute only the probed unit (code-review finding)."""
    calls = []

    class _Flag:
        def block_until_ready(self):
            calls.append("blocked")

    class _Pending:
        flag = _Flag()

        def resolve(self):
            return []

    queue = [(None, _Pending(), 0.0, None),
             (None, object(), 0.0, None)]   # flag-less: skipped
    perf.drain_backlog(queue)
    assert calls == ["blocked"]
