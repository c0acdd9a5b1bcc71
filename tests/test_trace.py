"""Distributed tracing & flight recorder (telemetry/trace.py, ISSUE 4):
recorder semantics, trace-context propagation across the RPC boundary,
Chrome-trace export schema, the dprf top live view, crash-history unit
sizing, JSONL rotation, and the declaration lint.
"""

import hashlib
import json
import subprocess
import sys
import time

import pytest

from dprf_tpu.cli import main as cli_main
from dprf_tpu.engines import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
from dprf_tpu.runtime.dispatcher import Dispatcher
from dprf_tpu.runtime.rpc import (CoordinatorClient, CoordinatorServer,
                                  CoordinatorState, worker_loop)
from dprf_tpu.runtime.session import job_fingerprint
from dprf_tpu.runtime.worker import CpuWorker
from dprf_tpu.telemetry.registry import MetricsRegistry
from dprf_tpu.telemetry import trace as trace_mod
from dprf_tpu.telemetry.trace import (TraceRecorder, export_chrome_trace,
                                      lifecycle_report, load_trace,
                                      render_top)

pytestmark = pytest.mark.smoke


def _recorder(**kw):
    kw.setdefault("registry", MetricsRegistry())
    return TraceRecorder(**kw)


# ---------------------------------------------------------------------------
# recorder semantics

def test_ring_is_bounded_and_tail_ordered():
    r = _recorder(capacity=16)
    for i in range(100):
        r.record("sweep", unit=i)
    spans = r.tail(1000)
    assert len(spans) == 16
    assert [s["attrs"]["unit"] for s in spans] == list(range(84, 100))
    assert all(s["name"] == "sweep" for s in spans)
    # span ids unique; tail(n) truncates from the old end
    assert len({s["span"] for s in spans}) == 16
    assert [s["attrs"]["unit"] for s in r.tail(4)] == [96, 97, 98, 99]


def test_disabled_recorder_records_nothing(monkeypatch):
    monkeypatch.setenv("DPRF_TRACE", "0")
    r = _recorder()          # enabled resolved from env at construction
    assert r.record("sweep") is None
    assert r.ingest([{"name": "sweep", "ts": 1.0}]) == 0
    assert r.tail() == []


def test_record_backdates_ts_by_duration():
    r = _recorder(clock=lambda: 100.0)
    s = r.record("sweep", dur=2.5)
    assert s["ts"] == pytest.approx(97.5)
    assert s["dur"] == pytest.approx(2.5)


def test_ingest_sanitizes_client_controlled_spans():
    r = _recorder()
    junk = [
        "not a dict",
        {"name": "not_a_declared_span", "ts": 1.0},
        {"name": "sweep", "ts": "NaN-ish junk"},
        {"name": "sweep", "ts": 1.0, "dur": 0.5, "trace": "t" * 500,
         "proc": "liar", "attrs": {"k": object()}},
        {"name": "rpc", "ts": 2.0, "attrs": {str(i): i
                                             for i in range(50)}},
    ]
    n = r.ingest(junk, proc="w1")
    assert n == 2
    spans = r.tail()
    # proc is forced to the server-known worker id, never trusted
    assert all(s["proc"] == "w1" for s in spans)
    over_long_trace = spans[0]
    assert over_long_trace["trace"] is None        # over MAX_ID_LEN
    assert len(spans[1]["attrs"]) <= trace_mod.MAX_ATTRS


def test_ingest_rebases_skewed_worker_clocks():
    """A worker 30s behind the coordinator must not render its sweep
    before its lease: span timestamps rebase by (coordinator now -
    sender's clock at send time)."""
    r = _recorder(clock=lambda: 1000.0)
    r.ingest([{"name": "sweep", "ts": 965.0, "dur": 2.0}],
             proc="w", sent_at=970.0)       # worker clock 30s behind
    (s,) = r.tail()
    assert s["ts"] == pytest.approx(995.0)  # 965 + (1000 - 970)
    assert s["dur"] == pytest.approx(2.0)   # durations are never scaled
    # no sent_at (old worker / local test harness): ts kept verbatim
    r.ingest([{"name": "rpc", "ts": 965.0}], proc="w")
    assert r.tail()[-1]["ts"] == pytest.approx(965.0)


def test_rotation_target_unusable_still_caps_the_file(tmp_path):
    """An unwritable rotation target must not defeat the size cap: the
    stream truncates in place instead of growing unbounded."""
    import os
    path = str(tmp_path / "s.trace.jsonl")
    os.mkdir(path + ".1")                   # os.replace onto a dir fails
    r = _recorder()
    r.attach_file(path, max_bytes=2000)
    for i in range(500):
        r.record("sweep", unit=i)
    r.detach_file()
    assert os.path.getsize(path) <= 2300    # cap + one span of slack


def test_file_stream_rotates_at_cap(tmp_path):
    path = str(tmp_path / "s.trace.jsonl")
    r = _recorder()
    r.attach_file(path, max_bytes=2000)
    for i in range(200):
        r.record("sweep", unit=i)
    r.detach_file()
    import os
    assert os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 2300     # cap + one span of slack
    assert os.path.getsize(path + ".1") <= 2300
    # load_trace stitches the rotated part back, oldest first
    spans = load_trace(path)
    units = [s["attrs"]["unit"] for s in spans]
    assert units == sorted(units)
    assert units[-1] == 199


def test_snapshotter_rotates_at_cap(tmp_path, monkeypatch):
    from dprf_tpu.telemetry import TelemetrySnapshotter
    monkeypatch.setenv("DPRF_TELEMETRY_MAX_BYTES", "400")
    reg = MetricsRegistry()
    reg.counter("dprf_hits_total", "x").inc()
    path = str(tmp_path / "t.telemetry.jsonl")
    snap = TelemetrySnapshotter(path, reg, interval=60.0)
    for _ in range(20):
        snap.write_once()
    import os
    assert os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 600
    # the snapshot stream still loads (torn-tail tolerant)
    from dprf_tpu.telemetry import load_snapshots
    assert load_snapshots(path)


# ---------------------------------------------------------------------------
# dispatcher lifecycle spans

def test_dispatcher_spans_cover_the_unit_lifecycle():
    rec = _recorder()
    d = Dispatcher(100, 100, registry=MetricsRegistry(), recorder=rec,
                   max_unit_retries=2)
    u = d.lease("w1")
    tid, lease_sid = d.trace_context(u.unit_id)
    assert tid and lease_sid
    d.fail(u.unit_id)
    assert d.trace_context(u.unit_id) is None
    u2 = d.lease("w2")
    assert u2.unit_id == u.unit_id          # reissued, same trace id
    assert d.trace_context(u.unit_id)[0] == tid
    d.complete(u.unit_id, elapsed=1.5)
    names = [s["name"] for s in rec.tail() if s["trace"] == tid]
    assert names == ["lease", "fail", "reissue", "lease", "complete"]
    rep = lifecycle_report(rec.tail())
    assert rep["orphans"] == 0
    assert rep["details"][tid]["terminal"]
    # second attempt's lease carries the attempt number
    leases = [s for s in rec.tail() if s["name"] == "lease"]
    assert leases[1]["attrs"]["attempt"] == 2


def test_dispatcher_park_span_after_retry_budget():
    rec = _recorder()
    d = Dispatcher(50, 50, registry=MetricsRegistry(), recorder=rec,
                   max_unit_retries=1)
    u = d.lease("w1")
    tid = d.trace_context(u.unit_id)[0]
    d.fail(u.unit_id)
    names = [s["name"] for s in rec.tail() if s["trace"] == tid]
    assert names == ["lease", "fail", "park"]
    assert lifecycle_report(rec.tail())["details"][tid]["terminal"]
    # retry-parked requeues with a reissue span on the same trace
    assert d.retry_parked() == 1
    names = [s["name"] for s in rec.tail() if s["trace"] == tid]
    assert names[-1] == "reissue"


# ---------------------------------------------------------------------------
# trace-context propagation across the RPC boundary (ISSUE 4 satellite:
# a unit that fails on one worker and completes on another yields ONE
# trace holding both workers' spans, no orphans, correct parent links)

def _loopback_job(mask, plants, unit_size, rec, reg, **dispatcher_kw):
    eng = get_engine("md5")
    gen = MaskGenerator(mask)
    targets = [eng.parse_target(hashlib.md5(p).hexdigest())
               for p in plants]
    fp = job_fingerprint("md5", f"mask:{mask}", gen.keyspace,
                         [t.digest for t in targets])
    job = {"engine": "md5", "attack": "mask", "attack_arg": mask,
           "customs": {}, "rules": None, "max_len": None,
           "targets": [t.raw for t in targets],
           "keyspace": gen.keyspace, "unit_size": unit_size,
           "batch": 4096, "hit_cap": 8, "fingerprint": fp}
    disp = Dispatcher(gen.keyspace, unit_size, registry=reg,
                      recorder=rec, **dispatcher_kw)
    state = CoordinatorState(
        job, disp, len(targets), registry=reg, recorder=rec,
        verifier=lambda ti, plain: eng.verify(plain, targets[ti]))
    server = CoordinatorServer(state, "127.0.0.1", 0)
    server.start_background()
    return eng, gen, targets, state, server, disp


class _FailOnce:
    """Worker whose first unit raises; the crash-and-reissue chaos."""

    def __init__(self, inner):
        self.inner = inner
        self.crashed = False

    def process(self, unit):
        if not self.crashed:
            self.crashed = True
            raise RuntimeError("injected chaos crash")
        return self.inner.process(unit)


def test_distributed_reissue_stitches_both_workers_onto_one_trace(tmp_path):
    reg = MetricsRegistry()
    rec = _recorder()
    path = str(tmp_path / "chaos.session.trace.jsonl")
    rec.attach_file(path)
    eng, gen, targets, state, server, disp = _loopback_job(
        "?l?l", [b"zz"], unit_size=26 * 26, rec=rec, reg=reg)
    try:
        c1 = CoordinatorClient(*server.address)
        with pytest.raises(RuntimeError, match="chaos"):
            worker_loop(c1, _FailOnce(CpuWorker(eng, gen, targets)),
                        "wA", idle_sleep=0.01)
        c1.close()
        c2 = CoordinatorClient(*server.address)
        worker_loop(c2, CpuWorker(eng, gen, targets), "wB",
                    idle_sleep=0.01)
        c2.close()
        assert state.found == {0: b"zz"}
    finally:
        server.shutdown()
        rec.detach_file()

    spans = load_trace(path)
    rep = lifecycle_report(spans)
    # ONE trace for the bounced unit, zero orphan spans anywhere
    assert rep["orphans"] == 0
    assert rep["incomplete"] == []
    (tid, detail), = rep["details"].items()
    assert detail["leases"] == 2 and detail["reissues"] == 1
    assert detail["terminal"]
    assert {"coordinator", "wA", "wB"} <= set(detail["procs"])
    # correct parent links: every worker span parents onto a lease
    # span of ITS attempt, and the failed attempt's spans carry wA
    by_id = {s["span"]: s for s in spans if s.get("span")}
    leases = [s for s in spans if s["name"] == "lease"]
    assert len(leases) == 2
    first_lease, second_lease = leases
    for s in spans:
        if s["proc"] == "wA":
            assert s["parent"] == first_lease["span"]
        if s["proc"] == "wB":
            assert s["parent"] == second_lease["span"]
        if s.get("parent"):
            assert s["parent"] in by_id
    crashed = [s for s in spans
               if s["name"] == "sweep" and s["proc"] == "wA"]
    assert crashed and crashed[0]["attrs"]["error"] == "RuntimeError"
    # hit_verify ran on the coordinator, parented to the live attempt
    hv = [s for s in spans if s["name"] == "hit_verify"]
    assert hv and hv[0]["parent"] == second_lease["span"]


def test_trace_export_cli_on_chaos_session(tmp_path):
    """Acceptance: export on a chaos-test distributed session
    reconstructs every lifecycle with zero orphans, and the emitted
    file is schema-valid Chrome-trace JSON."""
    reg = MetricsRegistry()
    rec = _recorder()
    session = str(tmp_path / "chaos.session")
    rec.attach_file(session + ".trace.jsonl")
    eng, gen, targets, state, server, disp = _loopback_job(
        "?l?l", [b"qq", b"zz"], unit_size=200, rec=rec, reg=reg)
    try:
        c1 = CoordinatorClient(*server.address)
        with pytest.raises(RuntimeError, match="chaos"):
            worker_loop(c1, _FailOnce(CpuWorker(eng, gen, targets)),
                        "wA", idle_sleep=0.01)
        c1.close()
        c2 = CoordinatorClient(*server.address)
        worker_loop(c2, CpuWorker(eng, gen, targets), "wB",
                    idle_sleep=0.01)
        c2.close()
    finally:
        server.shutdown()
        rec.detach_file()

    out = str(tmp_path / "chaos.perfetto.json")
    rc = cli_main(["trace", "export", session, "--out", out, "--quiet"])
    assert rc == 0

    spans = load_trace(session + ".trace.jsonl")
    rep = lifecycle_report(spans)
    assert rep["orphans"] == 0 and rep["incomplete"] == []
    # every unit's lifecycle reconstructs lease -> ... -> complete
    # (a worker's rpc span may SORT before its lease: its round trip
    # started before the coordinator recorded the lease, which is the
    # honest timeline)
    for detail in rep["details"].values():
        assert detail["leases"] >= 1
        assert detail["terminal"]
    assert any(d["reissues"] for d in rep["details"].values())

    with open(out, encoding="utf-8") as fh:
        doc = json.load(fh)
    _assert_chrome_trace_schema(doc)


def _assert_chrome_trace_schema(doc):
    assert isinstance(doc, dict)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    seen_x = False
    for e in events:
        assert isinstance(e["name"], str)
        assert e["ph"] in ("X", "M")
        assert isinstance(e["pid"], int)
        assert isinstance(e["tid"], int)
        if e["ph"] == "X":
            seen_x = True
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert isinstance(e["dur"], (int, float)) and e["dur"] > 0
            assert e["name"] in trace_mod.SPAN_NAMES
        else:
            assert e["name"] in ("process_name", "thread_name")
            assert isinstance(e["args"]["name"], str)
    assert seen_x
    # every X event's pid/tid has a metadata name
    named_pids = {e["pid"] for e in events
                  if e["ph"] == "M" and e["name"] == "process_name"}
    assert {e["pid"] for e in events if e["ph"] == "X"} <= named_pids


def test_chrome_export_is_deterministic_for_empty_attrs():
    r = _recorder(clock=lambda: 50.0)
    r.record("lease", trace="t1", proc="coordinator")
    doc = export_chrome_trace(r.tail())
    _assert_chrome_trace_schema(doc)


# ---------------------------------------------------------------------------
# op_trace_tail + dprf top

def test_trace_tail_rpc_and_top_cli(capsys):
    reg = MetricsRegistry()
    rec = _recorder()
    eng, gen, targets, state, server, disp = _loopback_job(
        "?d?d", [b"42"], unit_size=25, rec=rec, reg=reg)
    try:
        client = CoordinatorClient(*server.address)
        worker_loop(client, CpuWorker(eng, gen, targets), "w-tail",
                    idle_sleep=0.01)
        resp = client.call("trace_tail", n=50)
        client.close()
        assert resp["ok"]
        assert resp["status"]["found"] == 1
        assert resp["status"]["stop"] is True
        assert resp["status"]["targets"] == 1
        assert resp["leases"] == []
        procs = {s["proc"] for s in resp["spans"]}
        assert {"coordinator", "w-tail"} <= procs
        # render + the CLI view both carry the worker
        text = render_top(resp)
        assert "w-tail" in text and "FINISHED" in text
        host, port = server.address
        rc = cli_main(["top", "--connect", f"{host}:{port}",
                       "--iterations", "1", "--no-clear", "--quiet"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "w-tail" in out and "WORKER" in out
    finally:
        server.shutdown()


def test_trace_tail_shows_live_lease_countdown():
    reg = MetricsRegistry()
    rec = _recorder()
    eng, gen, targets, state, server, disp = _loopback_job(
        "?d?d?d", [b"999"], unit_size=100, rec=rec, reg=reg)
    try:
        client = CoordinatorClient(*server.address)
        leased = client.call("lease", worker_id="holder")["unit"]
        resp = client.call("trace_tail", n=10)
        client.close()
        (lease,), = (resp["leases"],)
        assert lease["worker"] == "holder"
        assert lease["unit"] == leased["id"]
        assert 0 < lease["deadline_s"] <= 300.0
        assert lease["trace"]
        text = render_top(resp)
        assert "holder" in text
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# local coordinator path: cli crack --session writes the trace stream

def test_local_crack_session_writes_trace_stream(tmp_path, capsys):
    hashes = tmp_path / "h.txt"
    hashes.write_text(hashlib.md5(b"zz9").hexdigest() + "\n")
    session = str(tmp_path / "job.session")
    rc = cli_main(["crack", "--engine", "md5", "--device", "cpu",
                   "-a", "mask", "?l?l?d", str(hashes),
                   "--session", session, "--unit-size", "2000",
                   "--no-potfile", "--quiet"])
    assert rc == 0
    spans = load_trace(session + ".trace.jsonl")
    rep = lifecycle_report(spans)
    assert rep["traces"] >= 1 and rep["orphans"] == 0
    names = {s["name"] for s in spans}
    assert {"lease", "sweep", "hit_verify", "complete"} <= names
    # export round-trips through the cli
    rc = cli_main(["trace", "export", session, "--quiet"])
    assert rc == 0
    with open(session + ".perfetto.json", encoding="utf-8") as fh:
        _assert_chrome_trace_schema(json.load(fh))


# ---------------------------------------------------------------------------
# overhead: tracing on the local sweep hot path <= 2% (bench mode)

def _timed_sweep(trace_on: bool) -> tuple:
    """One local sweep through the real Coordinator/Dispatcher path;
    returns (wall seconds, spans recorded)."""
    reg = MetricsRegistry()
    rec = TraceRecorder(enabled=trace_on, registry=reg)
    eng = get_engine("md5")
    gen = MaskGenerator("?l?l?l?l")          # 456,976 candidates
    targets = [eng.parse_target("ff" * 16)]  # unmatchable: pure sweep
    disp = Dispatcher(gen.keyspace, 1 << 14, registry=reg, recorder=rec)
    worker = CpuWorker(eng, gen, targets, chunk=8192)
    spec = JobSpec(engine="md5", device="cpu", attack="mask",
                   attack_arg="?l?l?l?l", keyspace=gen.keyspace,
                   fingerprint="bench")
    coord = Coordinator(spec, targets, disp, worker, registry=reg,
                        recorder=rec)
    t0 = time.perf_counter()
    result = coord.run()
    elapsed = time.perf_counter() - t0
    assert result.exhausted
    return elapsed, len(rec.tail(100000))


def test_tracing_overhead_on_sweep_hot_path_within_2_percent():
    # interleaved min-of-N wall clocks, recorder on vs off
    offs, ons = [], []
    for _ in range(2):
        offs.append(_timed_sweep(False)[0])
        ons.append(_timed_sweep(True)[0])
    t_off, t_on = min(offs), min(ons)
    # primary, noise-free bound: the spans the traced run actually
    # recorded, costed at a measured per-record price, must be <= 2%
    # of the sweep
    _, n_spans = _timed_sweep(True)
    assert n_spans > 0
    r = _recorder()
    reps = 5000
    t0 = time.perf_counter()
    for i in range(reps):
        r.record("sweep", unit=i, length=1 << 14, hits=0)
    per_span = (time.perf_counter() - t0) / reps
    overhead = per_span * n_spans
    assert overhead <= 0.02 * t_on, (
        f"{n_spans} spans x {per_span * 1e6:.1f}us = {overhead:.4f}s "
        f"> 2% of the {t_on:.3f}s sweep")
    # sanity wall-clock guard (generous: catches a gross regression
    # like an fsync per span without flaking on a loaded 2-core box)
    assert t_on <= t_off * 1.25 + 0.1, (t_on, t_off)


# ---------------------------------------------------------------------------
# crash history -> unit sizing (ROADMAP item satellite)

def test_sizer_shrinks_units_for_crashy_workers_and_recovers():
    from dprf_tpu.tune import AdaptiveUnitSizer
    s = AdaptiveUnitSizer(1 << 20, target_seconds=10.0,
                          min_unit=1 << 8, registry=MetricsRegistry())
    s.observe("w", 1 << 20, 10.0)            # rate -> exactly target
    base = s.next_size("w")
    assert base == 1 << 20
    s.observe_failure("w")
    assert s.next_size("w") == base // 2
    s.observe_failure("w")
    s.observe_failure("w")
    assert s.next_size("w") == base // 8
    # penalty is capped
    for _ in range(20):
        s.observe_failure("w")
    assert s.next_size("w") == base // (1 << s.MAX_PENALTY_BITS)
    assert s.failures("w") == s.MAX_FAILURES
    # clean completions at the same rate earn the size back
    for _ in range(s.MAX_FAILURES):
        s.observe("w", 1 << 18, 2.5)         # same rate, no poisoning
    assert s.failures("w") == 0
    assert s.next_size("w") == base
    # other workers are unaffected throughout
    assert s.next_size("other") == 1 << 20


def test_dispatcher_reports_failures_and_expiries_to_sizer():
    from dprf_tpu.tune import AdaptiveUnitSizer

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    sizer = AdaptiveUnitSizer(100, target_seconds=10.0, min_unit=1,
                              registry=MetricsRegistry())
    d = Dispatcher(10000, 100, lease_timeout=10.0, clock=clk,
                   registry=MetricsRegistry(), sizer=sizer,
                   recorder=_recorder())
    u = d.lease("crashy")
    d.fail(u.unit_id)
    assert sizer.failures("crashy") == 1
    d.lease("crashy")
    clk.t += 60.0                            # lease expires
    d.reap_expired()
    assert sizer.failures("crashy") == 2
    # the reissued unit keeps its geometry (resizing it would tear the
    # ledger); completing it decays one failure and seeds the rate
    u3 = d.lease("crashy")
    assert u3.unit_id == u.unit_id and u3.length == 100
    d.complete(u3.unit_id, elapsed=10.0)     # rate 10/s -> 100 target
    assert sizer.failures("crashy") == 1
    # the next LAZILY-GENERATED unit carries the crash penalty: halved
    assert d.lease("crashy").length == 50


# ---------------------------------------------------------------------------
# declaration lint (tools/check_metrics.py)

def _run_lint(*args):
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "check_metrics.py")
    return subprocess.run([sys.executable, tool, *args],
                          capture_output=True, text=True)


def test_check_metrics_passes_on_the_real_package():
    proc = _run_lint()
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_check_metrics_flags_duplicate_declaration(tmp_path):
    pkg = tmp_path / "pkg"
    (pkg / "telemetry").mkdir(parents=True)
    (pkg / "telemetry" / "trace.py").write_text(
        'SPAN_NAMES = ("lease", "sweep")\n')
    (pkg / "a.py").write_text(
        'def f(m):\n    m.counter("dprf_dup_total", "x")\n')
    (pkg / "b.py").write_text(
        'def g(m):\n    m.counter("dprf_dup_total", "x")\n')
    proc = _run_lint(str(pkg))
    assert proc.returncode == 1
    assert "dprf_dup_total" in proc.stdout


@pytest.mark.parametrize("name", ["made_up_span", "phase"])
def test_check_metrics_flags_undeclared_span_name(tmp_path, name):
    """Held to the package's own declaration, which has no `phase`:
    a `sweep` span has no children, a unit is swept in one piece."""
    from dprf_tpu.telemetry.trace import SPAN_NAMES
    assert name not in SPAN_NAMES
    pkg = tmp_path / "pkg"
    (pkg / "telemetry").mkdir(parents=True)
    (pkg / "telemetry" / "trace.py").write_text(
        "SPAN_NAMES = %r\n" % (SPAN_NAMES,))
    (pkg / "a.py").write_text(
        'def f(tracer):\n    tracer.record("sweep")\n'
        '    tracer.record("%s")\n' % name)
    proc = _run_lint(str(pkg))
    assert proc.returncode == 1
    assert "'%s'" % name in proc.stdout
    assert "'sweep'" not in proc.stdout
