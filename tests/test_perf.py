"""Performance attribution (ISSUE 9): the verify phase's histogram,
the live busy-fraction gauge, the roofline model, the
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
from dprf_tpu.telemetry import perf
from dprf_tpu.telemetry.registry import MetricsRegistry
from dprf_tpu.telemetry.trace import (TraceRecorder, load_trace,
                                      overlap_report)

pytestmark = pytest.mark.smoke


# ---------------------------------------------------------------------------
# dprf_phase_seconds: what every job observes

def test_default_job_observes_verify_alone():
    """`dprf_phase_seconds` holds what costs no sync: every hit
    batch's verify, the one phase there is."""
    reg = MetricsRegistry()
    rec = TraceRecorder(enabled=True, registry=reg)
    oracle = get_engine("md5", device="cpu")
    gen = MaskGenerator("?l?l?d")
    targets = [oracle.parse_target(hashlib.md5(b"zz9").hexdigest())]
    disp = Dispatcher(gen.keyspace, 600, registry=reg, recorder=rec)
    spec = JobSpec(engine="md5", device="cpu", attack="mask",
                   attack_arg="?l?l?d", keyspace=gen.keyspace,
                   fingerprint="perftest")
    result = Coordinator(spec, targets, disp,
                         CpuWorker(oracle, gen, targets, chunk=8192),
                         registry=reg, recorder=rec, oracle=None).run()
    assert result.found == {0: b"zz9"}
    hist = reg.get("dprf_phase_seconds")
    assert perf.PHASES == ("verify",)
    assert [(v["labels"], v["count"]) for v in hist.snapshot_values()] \
        == [({"phase": "verify", "engine": "md5", "job": "j0"}, 1)]
    assert {s["name"] for s in rec.tail(100000)} <= {
        "lease", "sweep", "hit_verify", "complete"}


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
# bench JSON carries its own step's phases (bench._step_phases)

def test_run_bench_cpu_reports_phases():
    from dprf_tpu.bench import run_bench
    res = run_bench(engine="md5", device="cpu", mask="?l?l?l?l",
                    batch=2048, seconds=0.2)
    assert set(res["phases"]) == {"generate", "device"}
    assert all(v >= 0 for v in res["phases"].values())


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
    assert doc["units"] >= 1
    # the plant's unit is the one hit batch the host verified
    assert doc["verify"]["count"] == 1
    assert doc["verify"]["total_s"] >= doc["verify"]["p95_s"] > 0
    assert doc["throughput"]["hs"] and doc["throughput"]["hs"] > 0
    assert doc["busy"] and all(0 <= v <= 1
                               for v in doc["busy"].values())
    assert doc["fair_share"] and doc["fair_share"][0]["job"] == "j0"
    text = render_report(doc)
    assert "host verify" in text and "device busy fraction" in text
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
