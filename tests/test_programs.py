"""Device introspection plane (ISSUE 13): compiled-program registry,
XLA-derived rooflines, HBM accounting, the op_programs surface, the
peak-memory regression gate, and the report memory section.

Runs entirely on the CPU backend: ``compiled.cost_analysis()`` /
``memory_analysis()`` work there, while ``device.memory_stats()``
returns None -- exactly the graceful-degrade half the tests pin.
"""

import hashlib
import json

import pytest

# device-pipeline compiles: full suite / tier-1, excluded from the
# <5-min smoke tier (tools/check_markers.py enforces a tier decision)
pytestmark = pytest.mark.compileheavy

from dprf_tpu import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.telemetry import DEFAULT as METRICS
from dprf_tpu.telemetry import devstats
from dprf_tpu.telemetry import perf as perf_mod
from dprf_tpu.telemetry import programs as programs_mod
from dprf_tpu.telemetry.programs import ProgramRegistry


def _warm_worker(engine: str, mask: str = "?l?l?l?l",
                 batch: int = 1 << 12):
    dev = get_engine(engine, device="jax")
    oracle = get_engine(engine, device="cpu")
    gen = MaskGenerator(mask)
    w = dev.make_mask_worker(
        gen, [oracle.parse_target("ff" * oracle.digest_size)],
        batch=batch, hit_capacity=16, oracle=oracle)
    if not getattr(w, "_warmed", False):
        w.warmup()
    return w


# ---------------------------------------------------------------------------
# registry round-trip

def test_registry_roundtrip_keyed_by_fingerprint():
    w = _warm_worker("md5")
    # warmup registered the site; analysis is deferred until asked
    n = programs_mod.analyze_pending()
    recs = [r for r in programs_mod.get_programs().snapshot()
            if r["engine"] == "md5" and r["attack"] == "mask"
            and r["batch"] == w.stride]
    assert recs, "warmup did not land a program record"
    rec = recs[-1]
    for key in ("key", "fingerprint", "engine", "attack", "batch",
                "flops", "flops_per_candidate", "peak_bytes",
                "argument_bytes", "output_bytes", "total_peak_bytes"):
        assert key in rec
    assert rec["flops"] and rec["flops"] > 0
    assert rec["total_peak_bytes"] and rec["total_peak_bytes"] > 0
    # re-registering the SAME step re-analyzes to the SAME fingerprint:
    # the registry stays deduped (round-trip keyed by the fingerprint)
    before = len(programs_mod.get_programs().snapshot())
    programs_mod.register_program("md5", "mask", w.stride,
                                  step=w.step, args=w.warmup_args())
    programs_mod.analyze_pending()
    assert len(programs_mod.get_programs().snapshot()) == before
    assert n >= 0


def test_wire_roundtrip_ingest_sanitizes():
    reg = ProgramRegistry()
    rec = {"fingerprint": "abc123", "engine": "md5", "attack": "mask",
           "batch": 4096, "flops": 4096 * 900.0,
           "peak_bytes": 1 << 20, "junk": "dropped",
           "key": "x" * 500}
    assert reg.ingest([rec], proc="w0") == 1
    got = reg.snapshot()[0]
    assert "junk" not in got
    assert len(got["key"]) <= 128
    assert got["proc"] == "w0"
    assert reg.analyzed_ops_per_candidate("md5") == pytest.approx(900.0)
    # duplicate fingerprints and junk entries drop silently
    assert reg.ingest([rec, "nope", {"engine": "md5"}], proc="w1") == 0


# ---------------------------------------------------------------------------
# analyzed roofline + hand-model cross-check

def test_md5_analyzed_within_2x_of_hand_model(monkeypatch):
    # a registry of its own: the process-wide one keeps the md5
    # programs of every test file this xdist worker ran before, and
    # the smallest record wins -- a program sharded over the 8 virtual
    # devices records its per-device flops over the global batch, an
    # eighth of the per-candidate cost
    monkeypatch.setattr(programs_mod, "DEFAULT", ProgramRegistry())
    _warm_worker("md5")
    programs_mod.analyze_pending()
    analyzed = programs_mod.analyzed_ops_per_candidate("md5")
    hand = perf_mod.OPS_PER_CANDIDATE["md5"]
    assert analyzed is not None
    ratio = max(analyzed, hand) / min(analyzed, hand)
    assert ratio < perf_mod.MODEL_DIVERGENCE_MAX, (
        f"analyzed {analyzed:.0f} vs hand {hand} ops/candidate "
        f"diverge {ratio:.2f}x")
    # the cross-check gauge carries the ratio
    assert perf_mod.ops_per_candidate("md5") == analyzed
    g = METRICS.get("dprf_roofline_model_divergence")
    assert g is not None
    assert 1.0 <= g.value(engine="md5") < perf_mod.MODEL_DIVERGENCE_MAX


#: one engine per family shape, including engines the hand table never
#: covered (sha512, lm, mysql41's nested sha1(sha1)): the silent
#: no-roofline path is gone -- compiling a step is enough to publish
ROOFLINE_ENGINES = ["md5", "ntlm", "sha512", "lm", "mysql41"]


@pytest.mark.parametrize("engine", ROOFLINE_ENGINES)
def test_every_engine_family_publishes_roofline(engine):
    _warm_worker(engine, mask="?l?l?l", batch=1 << 10)
    programs_mod.analyze_pending()
    assert programs_mod.analyzed_ops_per_candidate(engine) is not None
    frac = perf_mod.publish_roofline(engine, 1.0e9, "TPU v5 lite")
    assert frac is not None and frac > 0
    g = METRICS.get("dprf_roofline_frac")
    assert g.value(engine=engine) > 0


def test_no_silent_skip_for_any_registered_engine_with_a_record():
    """Every registered device engine's roofline publishes once a
    program record exists -- the registry itself has no per-engine
    skip list (synthetic records on a FRESH registry, so the real
    DEFAULT registry's analyzed values stay untouched)."""
    from dprf_tpu import engine_names
    reg = ProgramRegistry(registry=None)
    names = sorted(engine_names("jax"))
    reg.ingest([{"fingerprint": f"fp-{n}", "engine": n,
                 "attack": "mask", "batch": 1024,
                 "flops": 1024 * 500.0} for n in names],
               limit=len(names))
    for n in names:
        ops = reg.analyzed_ops_per_candidate(n)
        assert ops is not None, f"engine {n} lost its analyzed model"
        lo, hi = perf_mod.CHIP_INT_OPS_BANDS["TPU v5 lite"]
        assert hi / ops > 0


# ---------------------------------------------------------------------------
# HBM accounting: graceful None on the CPU backend

def test_memory_stats_none_degrade_on_cpu():
    assert devstats.device_memory_stats() == {}
    assert devstats.poll() == {}
    assert devstats.summary() is None
    assert devstats.bytes_free() is None
    assert devstats.headroom_frac() is None
    poller = devstats.DevstatsPoller(interval=0.05).start()
    poller.stop()       # no crash, no gauges
    assert METRICS.get("dprf_hbm_bytes_in_use") is None or \
        not METRICS.get("dprf_hbm_bytes_in_use").snapshot_values()


def test_peak_hbm_falls_back_to_program_analysis():
    _warm_worker("md5")
    programs_mod.analyze_pending()
    peak, source = devstats.peak_hbm_bytes()
    assert source == "program_analysis"
    assert peak and peak > 0


def test_unit_sizer_halves_under_low_headroom():
    from dprf_tpu.telemetry.registry import MetricsRegistry
    from dprf_tpu.tune.unit_sizer import AdaptiveUnitSizer
    full = AdaptiveUnitSizer(1 << 20, registry=MetricsRegistry(),
                             headroom_fn=lambda: 0.5)
    low = AdaptiveUnitSizer(1 << 20, registry=MetricsRegistry(),
                            headroom_fn=lambda: 0.05)
    none = AdaptiveUnitSizer(1 << 20, registry=MetricsRegistry(),
                             headroom_fn=lambda: None)
    assert low.next_size("w") == full.next_size("w") // 2
    assert none.next_size("w") == full.next_size("w")
    # serve plane: per-WORKER headroom from heartbeats, no local fn
    served = AdaptiveUnitSizer(1 << 20, registry=MetricsRegistry())
    served.observe_headroom("w1", 0.05)
    assert served.next_size("w1") == full.next_size("w") // 2
    assert served.next_size("w2") == full.next_size("w")
    served.observe_headroom("w1", None)       # report stopped: clear
    assert served.next_size("w1") == full.next_size("w")


# ---------------------------------------------------------------------------
# serve-plane surface: op_programs / heartbeat shipping / top fields

def _loopback_state():
    from dprf_tpu.runtime.dispatcher import Dispatcher
    from dprf_tpu.runtime.rpc import CoordinatorState
    from dprf_tpu.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    disp = Dispatcher(1000, 100, registry=reg)
    return CoordinatorState({"engine": "md5"}, disp, 1, registry=reg)


def test_op_programs_serves_heartbeat_shipped_records():
    state = _loopback_state()
    rec = {"fingerprint": "deadbeef", "engine": "md5",
           "attack": "mask", "batch": 4096,
           "flops": 4096 * 1000.0, "peak_bytes": 5 << 20,
           "argument_bytes": 128, "output_bytes": 64}
    resp = state.op_heartbeat({
        "worker_id": "w0",
        "payload": {"engine": "md5", "hbm_in_use": 1 << 30,
                    "hbm_limit": 16 << 30, "hbm_peak": 2 << 30},
        "programs": [rec]})
    assert resp["ok"]
    out = state.op_programs({})
    assert out["ok"]
    got = [r for r in out["programs"]
           if r["fingerprint"] == "deadbeef"]
    assert got and got[0]["proc"] == "w0"
    assert got[0]["flops_per_candidate"] == pytest.approx(1000.0)
    # fleet memory view from the heartbeat payload
    assert state.health.mem_by_worker() == {"w0": 1 << 30}
    totals = state.health.hbm_totals()
    assert totals == {"in_use": 1 << 30, "limit": 16 << 30,
                      "workers": 1}
    # ... and the dprf top status carries both
    tail = state.op_trace_tail({"n": 10})
    assert tail["status"]["mem"] == {"w0": 1 << 30}
    assert tail["status"]["hbm"]["limit"] == 16 << 30


def test_programs_cli_json_schema(capsys):
    from dprf_tpu.cli import main as cli_main
    from dprf_tpu.runtime.rpc import CoordinatorServer
    state = _loopback_state()
    state.programs.ingest([{"fingerprint": "f1", "engine": "sha512",
                            "attack": "mask", "batch": 2048,
                            "flops": 2048 * 3000.0,
                            "peak_bytes": 1 << 20}], proc="w1")
    server = CoordinatorServer(state, "127.0.0.1", 0)
    t = server.start_background()
    try:
        host, port = server.address
        rc = cli_main(["programs", "--connect", f"{host}:{port}",
                       "--json", "--quiet"])
        assert rc == 0
        out = capsys.readouterr().out
        records = json.loads(out)
        assert isinstance(records, list)
        mine = [r for r in records if r.get("fingerprint") == "f1"]
        assert mine
        for key in ("engine", "attack", "batch",
                    "flops_per_candidate", "total_peak_bytes"):
            assert key in mine[0]
        # the human rendering works on the same records
        table = programs_mod.render_table(records)
        assert "sha512" in table
    finally:
        server.shutdown()
        t.join(timeout=5)


def test_render_top_shows_mem_column_and_hbm_header():
    from dprf_tpu.telemetry.trace import render_top
    text = render_top({
        "status": {"done": 10, "total": 100, "found": 0,
                   "targets": 1, "parked": 0, "elapsed": 1.0,
                   "mem": {"w0": 3 << 30},
                   "hbm": {"in_use": 3 << 30, "limit": 16 << 30,
                           "workers": 1},
                   "health": {"w0": "healthy"}},
        "spans": [], "leases": []})
    assert "MEM" in text
    assert "hbm 3.0G/16.0G (1w)" in text
    assert "3.0G" in text


# ---------------------------------------------------------------------------
# peak-memory regression gate

def _bench_rec(round_no, value=1.0e9, peak=None):
    rec = {"value": value, "device": "cpu", "engine": "md5",
           "round": round_no}
    if peak is not None:
        rec["peak_hbm_bytes"] = peak
    return rec


def test_memory_gate_fails_planted_peak_regression():
    from dprf_tpu.perfreport import compare
    base = [_bench_rec(i, peak=100 << 20) for i in range(5)]
    # throughput flat, peak +30%: memory regression drives the verdict
    cur = _bench_rec(6, peak=130 << 20)
    out = compare.gate(cur, base)
    assert out["memory"]["verdict"] == "regression"
    assert out["verdict"] == "regression"
    # +5% stays inside the noise floor
    ok = compare.gate(_bench_rec(6, peak=105 << 20), base)
    assert ok["memory"]["verdict"] == "pass"
    assert ok["verdict"] == "pass"


def test_memory_gate_no_baseline_on_legacy_records():
    from dprf_tpu.perfreport import compare
    legacy = [_bench_rec(i) for i in range(5)]          # no memory
    out = compare.gate(_bench_rec(6, peak=100 << 20), legacy)
    assert out["memory"]["verdict"] == "no-baseline"
    assert out["verdict"] == "pass"
    # and a record that itself lacks the field gates clean too
    out2 = compare.gate(_bench_rec(6), legacy)
    assert out2["memory"]["verdict"] == "no-baseline"


def test_gate_dry_passes_committed_history():
    """The committed BENCH_r*.json records predate the memory fields:
    the dry gate must treat them as no-baseline, not crash."""
    from dprf_tpu.perfreport import compare
    out = compare.gate_dry(compare.repo_root())
    assert out["verdict"] in ("pass", "no-baseline")
    assert out["memory"]["verdict"] == "no-baseline"


# ---------------------------------------------------------------------------
# dprf report memory section, from session artifacts alone

def test_report_memory_section_e2e(tmp_path, monkeypatch, capsys):
    from dprf_tpu.cli import main as cli_main
    from dprf_tpu.perfreport import build_report
    monkeypatch.setenv("DPRF_TELEMETRY_INTERVAL", "600")
    monkeypatch.setenv("DPRF_TUNE_DIR", str(tmp_path / "tune"))
    hashfile = tmp_path / "h.txt"
    hashfile.write_text(hashlib.md5(b"zz7").hexdigest() + "\n")
    session = str(tmp_path / "s.session")
    rc = cli_main(["crack", "--engine", "md5", "--device", "tpu",
                   "-a", "mask", "?l?l?d", str(hashfile),
                   "--session", session, "--batch", "4096",
                   "--unit-size", "4096", "--no-potfile", "--quiet"])
    capsys.readouterr()
    assert rc == 0
    doc = build_report(session)
    assert doc is not None
    memory = doc.get("memory")
    assert memory, "report lost the device-memory section"
    progs = memory["programs"]
    assert any(p["engine"] == "md5" and p["peak_bytes"] > 0
               for p in progs)
    # CPU backend: no HBM gauges, the section degrades to programs
    assert memory["devices"] == {}
    from dprf_tpu.perfreport import render_report
    text = render_report(doc)
    assert "device memory & program costs" in text
