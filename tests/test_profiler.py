"""Kernel-level profiling plane (ISSUE 15): the single-flight
ProfileCapture, the dependency-free perfetto analyzer pinned against
the committed TPU-shaped fixture (exact op-class fractions +
generate/hash/compare phase mapping), capture-dir retention caps, the
op_profile / op_profile_push RPC flow through a real worker_loop, the
alert-triggered auto-capture chaos path (exactly one request, cooldown
enforced, journaled, rendered by `dprf report`), the exact
compile-cache classifier, and the disabled-path overhead guard.
"""

import gzip
import json
import logging
import os
import time

import pytest

from dprf_tpu.cli import main as cli_main
from dprf_tpu.engines import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.runtime.dispatcher import Dispatcher
from dprf_tpu.runtime.rpc import (CoordinatorClient, CoordinatorServer,
                                  CoordinatorState, worker_loop)
from dprf_tpu.runtime.session import SessionJournal
from dprf_tpu.runtime.worker import CpuWorker
from dprf_tpu.telemetry import profiler as profiler_mod
from dprf_tpu.telemetry.alerts import AlertEngine, AlertRule
from dprf_tpu.telemetry.profiler import (ProfileCapture, analyze_trace,
                                         classify_op, enforce_caps,
                                         render_summary,
                                         sanitize_summary)
from dprf_tpu.telemetry.registry import MetricsRegistry
from dprf_tpu.telemetry.trace import TraceRecorder

pytestmark = [pytest.mark.smoke, pytest.mark.profiler]

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_profile_trace.json.gz")


# ---------------------------------------------------------------------------
# the analyzer against the committed TPU-shaped fixture (exact pins)

def test_fixture_exact_class_fractions_and_phase_mapping():
    """Acceptance pin: the committed fixture's op-class fractions and
    generate/hash/compare mapping are EXACT -- any analyzer change
    that moves them is a deliberate, reviewed change."""
    s = analyze_trace(FIXTURE)
    assert s["schema"] == 1 and not s.get("error")
    assert s["seconds"] == {"fusion": 0.008, "op": 0.0,
                            "custom_call": 0.0005, "collective": 0.001,
                            "copy": 0.0005, "compile": 0.003,
                            "host": 0.02, "infra": 0.0}
    assert s["device_s"] == 0.01
    assert s["fractions"] == {"compute": 0.85, "collective": 0.1,
                              "copy": 0.05}
    assert s["phases"] == {"generate": 0.001, "hash": 0.0065,
                           "compare": 0.001, "other": 0.0015}
    top = s["top_ops"][0]
    assert (top["name"], top["class"], top["self_s"], top["count"]) \
        == ("md5_fusion.1", "fusion", 0.006, 1)
    # the XLA Modules wrapper lane must NOT double-count device time
    names = {o["name"] for o in s["top_ops"]}
    assert "jit_crack_step_module" not in names


def test_fixture_candidates_turn_on_per_candidate_cost():
    reg = MetricsRegistry()
    s = analyze_trace(FIXTURE, candidates=1000, registry=reg)
    assert s["candidates"] == 1000
    assert s["device_s_per_cand"] == pytest.approx(0.01 / 1000)
    # no analyzed program for engine=None: divergence stays None
    assert s["divergence"] is None


def test_render_summary_shows_fractions_and_top_ops():
    text = render_summary(analyze_trace(FIXTURE))
    assert "compute 85.0%" in text
    assert "collective 10.0%" in text
    assert "md5_fusion.1" in text
    assert "compile 0.0030s" in text


def test_classify_op_table():
    assert classify_op("my_big_fusion.12", "device") == "fusion"
    assert classify_op("all-gather.1", "device") == "collective"
    assert classify_op("reduce-scatter.3", "device") == "collective"
    assert classify_op("copy.1", "device") == "copy"
    assert classify_op("convert.9", "device") == "copy"
    assert classify_op("custom-call.2", "device") == "custom_call"
    assert classify_op("reduce-window", "device") == "op"
    assert classify_op("ThunkExecutor::Execute", "device") == "infra"
    assert classify_op("$cli.py:1 main", "host") == "host"
    assert classify_op("anything", "compile") == "compile"


def test_self_time_subtracts_children(tmp_path):
    """A parent frame's self time loses every nested child's dur --
    the host lane would otherwise read as N x wall."""
    evs = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
         "args": {"name": "python"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "$a.py:1 outer"},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 10, "dur": 30,
         "name": "$b.py:2 inner"},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 50, "dur": 20,
         "name": "$b.py:2 inner"},
    ]
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": evs}))
    s = analyze_trace(str(p))
    # outer self = 100 - 30 - 20 = 50us; total host = 100us
    assert s["seconds"]["host"] == pytest.approx(100e-6)


def test_analyze_bad_paths(tmp_path):
    assert "error" in analyze_trace(str(tmp_path))   # no trace under it
    bad = tmp_path / "perfetto_trace.json.gz"
    with gzip.open(bad, "wt") as fh:
        fh.write("{not json")
    assert "unparsable" in analyze_trace(str(bad))["error"]


def test_sanitize_summary_bounds_and_known_keys():
    dirty = {"schema": 1, "junk": object(), "path": "x" * 9999,
             "device_s": "0.5", "fractions": {"compute": "0.5"},
             "top_ops": ([{"name": "n" * 999, "class": "fusion",
                           "self_s": 0.25, "count": 2}] * 99
                         + [{"name": "bad", "self_s": "nope"}])}
    s = sanitize_summary(dirty)
    assert "junk" not in s
    assert len(s["path"]) <= profiler_mod.MAX_SUMMARY_STR
    assert s["fractions"] == {"compute": 0.5}
    assert len(s["top_ops"]) == profiler_mod.TOP_OPS
    assert s["top_ops"][0]["count"] == 2
    assert len(s["top_ops"][0]["name"]) <= profiler_mod.MAX_SUMMARY_STR
    # a row with an unparsable float is skipped entirely
    assert all(isinstance(r["self_s"], float) for r in s["top_ops"])
    assert sanitize_summary("nope") is None
    assert sanitize_summary({}) is None


def test_phase_patterns_merge_engine_declaration():
    """The md5 device engine's PROFILE_PHASES merge OVER the analyzer
    defaults -- the per-engine declaration site."""
    pats = profiler_mod.phase_patterns("md5")
    assert "md5" in pats["hash"]
    assert "fusion" in pats["hash"]          # defaults kept
    assert "decode_batch" in pats["generate"]
    # unknown engine: defaults only, never a crash
    assert profiler_mod.phase_patterns("no-such-engine") \
        == profiler_mod.phase_patterns(None)


def test_cli_profile_local_analyze(capsys):
    rc = cli_main(["profile", FIXTURE, "--json", "--quiet"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["fractions"] == {"compute": 0.85, "collective": 0.1,
                                "copy": 0.05}
    rc = cli_main(["profile", "--quiet"])      # no target, no connect
    assert rc == 2


# ---------------------------------------------------------------------------
# retention caps

def test_enforce_caps_keep_last_n_and_xplane_drop(tmp_path):
    root = str(tmp_path)
    base = tmp_path / "plugins" / "profile"
    for i, name in enumerate(["r1", "r2", "r3"]):
        d = base / name
        d.mkdir(parents=True)
        (d / "perfetto_trace.json.gz").write_bytes(b"x" * 100)
        (d / "host.xplane.pb").write_bytes(b"y" * 1000)
        t = time.time() - 100 + i
        os.utime(d, (t, t))
    enforce_caps(root, keep=2, max_bytes=500)
    left = sorted(p.name for p in base.iterdir())
    assert left == ["r2", "r3"]               # oldest reaped
    for name in left:
        d = base / name
        assert (d / "perfetto_trace.json.gz").exists()
        assert not (d / "host.xplane.pb").exists()   # over the cap
    # keep=0 / max_bytes=0 disable both; a rootless dir is a no-op
    enforce_caps(root, keep=0, max_bytes=0)
    assert sorted(p.name for p in base.iterdir()) == ["r2", "r3"]
    enforce_caps(str(tmp_path / "nope"), keep=1, max_bytes=1)


# ---------------------------------------------------------------------------
# single-flight + the bounded window (live CPU-backend captures)

def test_single_flight_session_blocks_window_and_second_session(
        tmp_path, caplog):
    prof = ProfileCapture(registry=MetricsRegistry())
    with prof.session(str(tmp_path / "a"), owner="cli"):
        assert prof.busy() == "cli"
        # a second starter degrades to a refusal, never an exception
        assert not prof.begin_window(0.5,
                                     directory=str(tmp_path / "b"))
        with prof.session(str(tmp_path / "c"), owner="env"):
            pass                              # no-op, no crash
        assert prof.busy() == "cli"           # still the first owner
    assert prof.busy() is None
    # the slot frees: a window can start now, and abort releases it
    assert prof.begin_window(0.5, directory=str(tmp_path / "b"))
    assert prof.window_active()
    prof.abort_window()
    assert prof.busy() is None and not prof.window_active()


@pytest.mark.compileheavy
def test_live_cpu_capture_attributes_host_and_compile(tmp_path,
                                                      cache_off):
    """Live e2e on the CPU backend: a capture window around COLD jit
    compiles + dispatches attributes nonzero host-python and
    compile-pass time (per-HLO device lanes are TPU-only -- the
    committed fixture covers those), counts candidates through the
    window, and lands in the capture history.  cache_off: every
    iteration builds a new jit, and only with the persistent cache
    off does each one compile INSIDE the window."""
    import jax
    import jax.numpy as jnp
    prof = ProfileCapture(registry=MetricsRegistry())
    n = [0]
    x = jnp.arange(7919, dtype=jnp.uint32)

    def busy():
        f = jax.jit(lambda v, s: ((v * jnp.uint32(2654435761)
                                   + s) ^ (v >> 7)).sum())
        f(x, jnp.uint32(n[0] % 3)).block_until_ready()
        n[0] += x.shape[0]

    s = prof.capture(seconds=1.0, directory=str(tmp_path / "cap"),
                     trigger="manual", engine="md5",
                     counter_fn=lambda: n[0], busy_fn=busy)
    assert s is not None and not s.get("error")
    assert s["seconds"]["host"] > 0
    assert s["seconds"]["compile"] > 0
    assert s["candidates"] and s["candidates"] >= 7919
    assert s["trigger"] == "manual" and s["window_s"] == 1.0
    assert os.path.isdir(s["path"])
    assert prof.last_summary() is s
    assert prof.last_capture_ts("manual") is not None
    # single-flight released: the next window starts cleanly
    assert prof.begin_window(0.5, directory=str(tmp_path / "cap"))
    prof.abort_window()


def _stub_traces(monkeypatch, stop=None):
    """Instant fake jax trace + analyzer: window state-machine tests
    must not pay real captures."""
    import jax
    monkeypatch.setitem(profiler_mod._deps, "state", "ready")
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        stop or (lambda: None))
    monkeypatch.setattr(
        profiler_mod, "analyze_trace",
        lambda path, **k: {"schema": 1, "path": path})


def _drive(prof, deadline_s=5.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        s = prof.poll()
        if s is not None:
            return s
        time.sleep(0.01)
    raise AssertionError("window never finished")


def test_new_window_never_clobbers_finishing_summary(
        tmp_path, monkeypatch):
    """A second capture armed while the first is still finishing on
    its background thread must not discard the first summary: both
    reach poll(), in order."""
    _stub_traces(monkeypatch)
    prof = ProfileCapture(registry=MetricsRegistry())
    assert prof.begin_window(0.5, directory=str(tmp_path / "a"),
                             request_id=1)
    assert prof.poll() is None               # trace started
    time.sleep(0.55)
    assert prof.poll() is None               # finishing (background)
    for _ in range(500):                     # slot frees post-stop
        if prof.busy() is None:
            break
        time.sleep(0.01)
    assert prof.begin_window(0.5, directory=str(tmp_path / "b"),
                             request_id=2)
    s1 = _drive(prof)                        # A's summary first
    assert s1["request_id"] == 1
    time.sleep(0.55)
    s2 = _drive(prof)
    assert s2["request_id"] == 2
    assert prof.busy() is None


def test_abort_leaves_finishing_window_to_its_thread(
        tmp_path, monkeypatch):
    """abort_window during the FINISHING state must not release the
    single-flight slot out from under the background thread (a
    successor owner's slot would be freed mid-capture); the thread
    still delivers the summary."""
    import threading
    gate = threading.Event()
    _stub_traces(monkeypatch, stop=lambda: gate.wait(5))
    prof = ProfileCapture(registry=MetricsRegistry())
    assert prof.begin_window(0.5, directory=str(tmp_path / "c"),
                             request_id=3)
    assert prof.poll() is None
    time.sleep(0.55)                         # window min is 0.5 s
    assert prof.poll() is None               # finishing; stop blocked
    prof.abort_window()
    assert prof.busy() is not None           # NOT released by abort
    gate.set()
    s = _drive(prof)
    assert s["request_id"] == 3
    assert prof.busy() is None


def test_profile_request_table_ttl_and_cap(monkeypatch):
    """Pending requests are client-fed: stale entries expire by TTL
    (a dead worker can't block its own future auto-captures) and the
    table is bounded like the other worker-keyed tables."""
    from dprf_tpu.runtime import rpc as rpc_mod
    eng, gen, targets, job = _mask_job()
    state, server, reg = _serve(job, gen, targets)
    try:
        now = time.monotonic()
        with state.lock:
            state._profile_requests["dead"] = {
                "id": 1, "seconds": 1.0, "trigger": "straggler",
                "queued_at": now - rpc_mod.PROFILE_REQUEST_TTL_S - 1}
            state._profile_requests["fresh"] = {
                "id": 2, "seconds": 1.0, "trigger": "manual",
                "queued_at": now}
            state._prune_profile_requests(now)
            assert list(state._profile_requests) == ["fresh"]
        # the cap: a request flood with throwaway worker ids errors
        # out instead of growing the table without bound
        with state.lock:
            for i in range(state.MAX_WORKER_LABELS):
                state._profile_requests[f"w{i}"] = {
                    "id": i, "seconds": 1.0, "trigger": "manual",
                    "queued_at": now}
        c = CoordinatorClient(*server.address)
        from dprf_tpu.runtime.rpc import RpcError
        with pytest.raises(RpcError, match="too many pending"):
            c.call("profile", action="request", worker="one-more")
        # re-requesting an ALREADY-pending worker shares the queued
        # request's id (a second operator must not orphan the first
        # requester's poll), and neither the delivered request nor
        # the pending table on the wire carries the coordinator-clock
        # bookkeeping
        resp = c.call("profile", action="request", worker="w0")
        assert resp["worker"] == "w0" and resp["pending"] is True
        assert resp["request_id"] == 0          # the queued one's id
        st = c.call("profile")
        assert all("queued_at" not in r
                   for r in st["pending"].values())
        c.close()
        with state.lock:
            req = state._profile_request_for("w0")
            assert req is not None and "queued_at" not in req
            # delivery moved it to the inflight ledger
            assert 0 in state._profile_inflight
    finally:
        server.shutdown()


def test_disabled_path_overhead_negligible():
    """PR 4/9-style guard: with no capture active, the per-iteration
    work the worker loop gained (one poll probe + one lease-response
    dict read) must be microseconds -- <= 2% of even a 20 ms unit."""
    prof = ProfileCapture()
    resp = {"unit": None, "stop": False, "pull": 0}
    t0 = time.perf_counter()
    n = 10_000
    for _ in range(n):
        prof.poll()
        resp.get("profile")
    per_iter = (time.perf_counter() - t0) / n
    assert per_iter < 400e-6, \
        f"disabled-path probe {per_iter * 1e6:.1f}us/iter"


# ---------------------------------------------------------------------------
# RPC flow: op_profile request -> worker_loop capture -> push -> fetch

class SlowCpuWorker(CpuWorker):
    """CpuWorker with a per-unit floor so the loop outlasts a capture
    window (the md5 sweep alone finishes in milliseconds)."""

    def process(self, unit):
        time.sleep(0.05)
        return super().process(unit)


def _mask_job(keyspace_digits=4, unit=100):
    import hashlib
    eng = get_engine("md5")
    gen = MaskGenerator("?d" * keyspace_digits)
    plain = b"9" * keyspace_digits      # plant at the LAST index
    targets = [eng.parse_target(hashlib.md5(plain).hexdigest())]
    job = {"engine": "md5", "attack": "mask",
           "attack_arg": "?d" * keyspace_digits, "targets":
           [t.raw for t in targets], "keyspace": gen.keyspace,
           "unit_size": unit, "batch": 256, "hit_cap": 8,
           "fingerprint": "fp"}
    return eng, gen, targets, job


def _serve(job, gen, targets, lease_timeout=300.0):
    reg = MetricsRegistry()
    rec = TraceRecorder(registry=reg)
    eng = get_engine(job["engine"])
    disp = Dispatcher(gen.keyspace, job["unit_size"], registry=reg,
                      recorder=rec, job_id="j0",
                      lease_timeout=lease_timeout)
    state = CoordinatorState(
        job, disp, len(targets), registry=reg, recorder=rec,
        verifier=lambda ti, p: eng.verify(p, targets[ti]))
    server = CoordinatorServer(state, "127.0.0.1", 0)
    server.start_background()
    return state, server, reg


def test_op_profile_request_rides_lease_and_push_round_trips(
        tmp_path):
    """The fleet path end-to-end with a REAL capture: op_profile
    request -> the worker's next lease carries the window -> the
    worker sweeps through it, analyzes locally, pushes the summary ->
    op_profile serves it (raw trace stays on the worker host, path
    included) -> the journal hook fired."""
    eng, gen, targets, job = _mask_job()
    state, server, reg = _serve(job, gen, targets)
    journaled = []
    state.on_profile = lambda w, s: journaled.append((w, s))
    try:
        c = CoordinatorClient(*server.address)
        # no live worker yet: auto-pick must refuse loudly
        from dprf_tpu.runtime.rpc import RpcError
        with pytest.raises(RpcError, match="no live worker"):
            c.call("profile", action="request")
        # target w1 explicitly; the request waits for its first lease
        resp = c.call("profile", action="request", worker="w1",
                      seconds=0.6)
        rid = resp["request_id"]
        assert resp["worker"] == "w1"
        with state.lock:
            assert state._profile_requests["w1"]["id"] == rid

        os.environ["DPRF_PROFILE_DIR"] = str(tmp_path / "wcap")
        try:
            w = CoordinatorClient(*server.address)
            done = worker_loop(
                w, SlowCpuWorker(eng, gen, targets), "w1",
                idle_sleep=0.01, depth=1,
                registry=MetricsRegistry(),
                recorder=TraceRecorder(registry=MetricsRegistry()))
            w.close()
        finally:
            os.environ.pop("DPRF_PROFILE_DIR", None)
        assert done == gen.keyspace // job["unit_size"]

        resp = c.call("profile")
        c.close()
        summaries = resp["summaries"]["w1"]
        assert summaries and summaries[0]["request_id"] == rid
        s = summaries[0]
        assert not s.get("error")
        assert s["trigger"] == "manual" and s["window_s"] == 0.6
        # the CpuWorker hashes on host: candidates still counted
        # through the window, and the raw path names the worker dir
        assert s["candidates"] and s["candidates"] > 0
        assert str(tmp_path / "wcap") in s["path"]
        assert journaled and journaled[0][0] == "w1"
        assert journaled[0][1]["request_id"] == rid
        # the request table drained; top sees the capture meta
        with state.lock:
            assert "w1" not in state._profile_requests
        c2 = CoordinatorClient(*server.address)
        status = c2.call("trace_tail", n=10)["status"]
        c2.close()
        assert status["profiles"]["w1"]["trigger"] == "manual"
        # the found crack is untouched by all the profiling traffic
        with state.lock:
            assert state.scheduler.get("j0").found
    finally:
        profiler_mod.DEFAULT.abort_window()
        server.shutdown()


def test_window_outlasting_job_still_pushes_cut_short(
        tmp_path, monkeypatch):
    """A capture window longer than the job's remaining work: the
    loop's clean-stop grace cuts the window short and still pushes
    the (real, shorter) summary instead of silently aborting it."""
    monkeypatch.setitem(profiler_mod._deps, "state", "ready")
    eng, gen, targets, job = _mask_job(keyspace_digits=3, unit=100)
    state, server, reg = _serve(job, gen, targets)
    try:
        c = CoordinatorClient(*server.address)
        # 30 s window vs ~1 s of job: can only land via the grace
        resp = c.call("profile", action="request", worker="w1",
                      seconds=30.0)
        rid = resp["request_id"]
        os.environ["DPRF_PROFILE_DIR"] = str(tmp_path / "wcap")
        try:
            w = CoordinatorClient(*server.address)
            worker_loop(
                w, SlowCpuWorker(eng, gen, targets), "w1",
                idle_sleep=0.01, depth=1,
                registry=MetricsRegistry(),
                recorder=TraceRecorder(registry=MetricsRegistry()))
            w.close()
        finally:
            os.environ.pop("DPRF_PROFILE_DIR", None)
        s = c.call("profile")["summaries"]["w1"][0]
        c.close()
        assert s["request_id"] == rid
        assert not s.get("error")
        assert s["window_s"] == 30.0      # asked; delivered early
        # the push cleared the inflight ledger: serve's drain loop
        # (which waits on profile_pending) is free to exit
        with state.lock:
            assert state._profile_inflight == {}
        assert not state.profile_pending()
    finally:
        profiler_mod.DEFAULT.abort_window()
        server.shutdown()


def test_summary_read_grace_and_worker_filtered_read():
    """A landed summary holds the serve drain (profile_pending) until
    somebody reads it -- the requester polls every ~0.5 s, and without
    the grace the drain could close the socket between the worker's
    push and the poller's next read.  A poll naming its worker ships
    that bucket alone and clears only that worker's grace."""
    from dprf_tpu.runtime import rpc as rpc_mod
    eng, gen, targets, job = _mask_job()
    state, server, reg = _serve(job, gen, targets)
    try:
        c = CoordinatorClient(*server.address)
        for wid in ("wa", "wb"):
            c.call("profile_push", worker_id=wid,
                   summary={"schema": 1, "ts": 1.0,
                            "trigger": "manual"})
        assert state.profile_pending()        # unread: drain held
        st = c.call("profile", worker="wa")
        assert list(st["summaries"]) == ["wa"]    # filtered read
        assert state.profile_pending()        # wb still unread
        c.call("profile")                     # unfiltered read: all
        assert not state.profile_pending()
        # an unread grace a crashed requester never collects expires
        # on its own instead of pinning the drain table
        c.call("profile_push", worker_id="wa",
               summary={"schema": 1, "ts": 2.0, "trigger": "manual"})
        with state.lock:
            state._profile_unread["wa"] -= \
                rpc_mod.PROFILE_READ_GRACE_S + 1
        assert not state.profile_pending()
        with state.lock:
            assert state._profile_unread == {}
        c.close()
    finally:
        server.shutdown()


def test_connect_poll_tolerates_coordinator_exit(monkeypatch):
    """The serve session can legitimately end while `dprf profile
    --connect` is polling (short job, drained past the read-grace):
    the poll's ConnectionError means "no summary in time" (rc 1, the
    miss path), not the generic rc-2 error exit."""
    from dprf_tpu import cli as cli_mod
    eng, gen, targets, job = _mask_job()
    state, server, reg = _serve(job, gen, targets)
    try:
        real = cli_mod._jobs_client

        def dying_client(args, log):
            # the request lands; every subsequent summary poll sees
            # the closed socket, as after a coordinator process exit
            client = real(args, log)
            orig = client.call

            def call(op, **kw):
                if op == "profile" and kw.get("action") != "request":
                    raise ConnectionError(
                        "coordinator closed the connection")
                return orig(op, **kw)

            client.call = call
            return client

        monkeypatch.setattr(cli_mod, "_jobs_client", dying_client)
        rc = cli_main(["profile", "--connect",
                       "%s:%d" % server.address, "--worker", "wz",
                       "--wait", "5", "--quiet"])
        assert rc == 1
    finally:
        server.shutdown()


def test_profile_push_sanitizes_and_bounds(tmp_path):
    eng, gen, targets, job = _mask_job()
    state, server, reg = _serve(job, gen, targets)
    try:
        c = CoordinatorClient(*server.address)
        # junk summary: rejected without a crash
        assert c.call("profile_push", worker_id="w9",
                      summary="junk")["ok"] is False
        for i in range(6):
            c.call("profile_push", worker_id="w9",
                   summary={"schema": 1, "ts": float(i),
                            "trigger": "manual", "junk": "dropped"})
        resp = c.call("profile")
        c.close()
        bucket = resp["summaries"]["w9"]
        from dprf_tpu.runtime.rpc import PROFILE_SUMMARIES_PER_WORKER
        assert len(bucket) == PROFILE_SUMMARIES_PER_WORKER
        assert bucket[0]["ts"] == 5.0           # newest first
        assert all("junk" not in s for s in bucket)
    finally:
        server.shutdown()


def test_render_top_prof_column_age_and_trigger():
    """`dprf top` shows each worker's last-capture age + trigger rule
    from the status profiles table (pushed summaries, with the
    heartbeat payload as the env-local fallback)."""
    from dprf_tpu.telemetry.trace import render_top
    now = time.time()
    text = render_top({
        "status": {"done": 10, "total": 100, "found": 0,
                   "targets": 1, "parked": 0, "elapsed": 1.0,
                   "now": now,
                   "profiles": {"w0": {"ts": now - 90,
                                       "trigger": "straggler"}},
                   "health": {"w0": "healthy", "w1": "healthy"}},
        "spans": [], "leases": []})
    assert "PROF" in text
    assert "90s/straggle" in text
    w1 = [ln for ln in text.splitlines() if ln.startswith("w1")][0]
    assert "straggle" not in w1          # no capture yet: just a dash


# ---------------------------------------------------------------------------
# alert-triggered auto-capture (the chaos acceptance path)

def _straggler_state(tmp_path, session=None):
    """A serve state with 3 live workers (w3 far under the fleet
    median) and a fast straggler rule."""
    eng, gen, targets, job = _mask_job()
    state, server, reg = _serve(job, gen, targets)
    state.alerts = AlertEngine(
        rules=[AlertRule(name="straggler",
                         metric="dprf_worker_straggler",
                         op=">=", threshold=1, for_s=0.0,
                         severity="warning")],
        registry=reg)
    for wid, rate in (("w1", 100.0), ("w2", 100.0), ("w3", 10.0)):
        state.health.observe(wid, rate_hs=rate)
    return state, server, reg


def test_chaos_straggler_alert_yields_exactly_one_auto_capture(
        tmp_path, monkeypatch):
    """Acceptance: the planted straggler fires -> the health tick
    queues EXACTLY ONE capture request for the implicated worker;
    re-fires inside the cooldown are swallowed; the pushed summary is
    journaled as {"type": "profile"} and `dprf report` renders it."""
    monkeypatch.setenv("DPRF_PROFILE_COOLDOWN_S", "600")
    state, server, reg = _straggler_state(tmp_path)
    path = str(tmp_path / "auto.session")
    session = SessionJournal(path, snapshot_every=1)
    session.open(state.job, default_job="j0")
    state.on_profile = \
        lambda w, s: session.record_profile(w, s)
    try:
        state.health_tick()
        with state.lock:
            reqs = dict(state._profile_requests)
        assert list(reqs) == ["w3"]
        assert reqs["w3"]["trigger"] == "straggler"
        rid = reqs["w3"]["id"]

        # the SAME firing produces no second request, and a re-fire
        # within the cooldown is swallowed even after delivery
        state.health_tick()
        with state.lock:
            assert len(state._profile_requests) == 1
            state._profile_requests.clear()     # simulate delivery
        state.alerts = AlertEngine(
            rules=state.alerts.rules, registry=reg)  # fresh lifecycle
        state.health_tick()                          # fires again
        with state.lock:
            assert state._profile_requests == {}     # cooldown held

        # cooldown elapsed (0 = always): the next firing captures
        monkeypatch.setenv("DPRF_PROFILE_COOLDOWN_S", "0")
        state.alerts = AlertEngine(
            rules=state.alerts.rules, registry=reg)
        state.health_tick()
        with state.lock:
            assert list(state._profile_requests) == ["w3"]
            state._profile_requests.clear()

        # the worker's pushed summary is journaled and reportable
        c = CoordinatorClient(*server.address)
        c.call("profile_push", worker_id="w3",
               summary={"schema": 1, "ts": time.time(),
                        "trigger": "straggler", "request_id": rid,
                        "engine": "md5", "device_s": 0.01,
                        "fractions": {"compute": 0.85,
                                      "collective": 0.1,
                                      "copy": 0.05}})
        # retrievable via the same surface dprf profile --connect polls
        fetched = c.call("profile")["summaries"]["w3"][0]
        assert fetched["trigger"] == "straggler"
        c.close()
        session.close()

        loaded = SessionJournal.load(path)
        assert len(loaded.profiles) == 1
        assert loaded.profiles[0]["worker"] == "w3"
        assert loaded.profiles[0]["summary"]["trigger"] == "straggler"
        from dprf_tpu.perfreport.report import (build_report,
                                                render_report)
        doc = build_report(path)
        assert doc["profiles"][0]["worker"] == "w3"
        assert doc["profiles"][0]["trigger"] == "straggler"
        text = render_report(doc)
        assert "kernel profile" in text
        assert "straggler" in text
    finally:
        server.shutdown()


def test_autoprofile_disabled_and_job_stalled_picks_slowest(
        tmp_path, monkeypatch):
    state, server, reg = _straggler_state(tmp_path)
    try:
        # kill switch: no request queued no matter what fires
        monkeypatch.setenv("DPRF_AUTOPROFILE", "0")
        state.health_tick()
        with state.lock:
            assert state._profile_requests == {}
        monkeypatch.delenv("DPRF_AUTOPROFILE")
        monkeypatch.setenv("DPRF_PROFILE_COOLDOWN_S", "0")
        # a job_stalled firing names no worker: the slowest live
        # worker is implicated
        state._maybe_autoprofile([
            {"state": "firing", "rule": "job_stalled",
             "labels": {"job": "j0"}}])
        with state.lock:
            assert list(state._profile_requests) == ["w3"]
            assert state._profile_requests["w3"]["trigger"] \
                == "job_stalled"
        # unrelated rules never trigger captures
        with state.lock:
            state._profile_requests.clear()
        state._maybe_autoprofile([
            {"state": "firing", "rule": "trace_drops", "labels": {}}])
        with state.lock:
            assert state._profile_requests == {}
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# exact compile-cache classifier (ISSUE 15 satellite)

def test_compile_classifier_exact_from_cache_log_lines(fresh_cache):
    """The observer classifies from the compiler's own
    persistent-cache log lines: a cold compile is
    an exact miss, a same-key recompile served from disk an exact hit
    -- no wall-clock floor involved."""
    import jax
    import jax.numpy as jnp

    from dprf_tpu import compilecache
    reg = MetricsRegistry()
    x = jnp.arange(4093, dtype=jnp.uint32)   # unique prime shape
    with compilecache.compile_observer("md5", registry=reg) as o1:
        jax.jit(lambda v: (v ^ jnp.uint32(41)).sum())(
            x).block_until_ready()
    assert o1.cache == "miss"
    # a FRESH jit of the same computation: jax's in-memory cache
    # cannot serve it, the persistent cache does -> exact hit
    with compilecache.compile_observer("md5", registry=reg) as o2:
        jax.jit(lambda v: (v ^ jnp.uint32(41)).sum())(
            x).block_until_ready()
    assert o2.cache == "hit"
    assert reg.get("dprf_compile_cache_hits_total").value(
        engine="md5") == 1
    assert reg.get("dprf_compile_cache_misses_total").value(
        engine="md5") == 1
    # the observers' watches are gone; the one left is the process
    # watch that counts every compile while the cache is on
    from dprf_tpu.compilecache import _watch_state
    assert len(_watch_state["watches"]) == 1
    counts = compilecache.process_cache_counts()
    assert counts["cache_hits"] >= 1 and counts["cache_misses"] >= 1
    # while the process watch is on, the compiler's logger still
    # propagates: its WARNING records reach the operator's handlers,
    # its DEBUG records (the lines the watch counts) do not
    jlog = logging.getLogger("jax._src.compiler")
    assert jlog.propagate
    seen = []

    class Seen(logging.Handler):
        def emit(self, record):
            seen.append(record.getMessage())

    root_handler = Seen(level=logging.DEBUG)
    logging.getLogger().addHandler(root_handler)
    try:
        jlog.warning("compiler says careful")
        jlog.debug("compiler chatter")
    finally:
        logging.getLogger().removeHandler(root_handler)
    assert seen == ["compiler says careful"]
    # switching the cache off restores the logger's level
    level = _watch_state["saved"]
    compilecache.disable()
    assert not _watch_state["watches"] and not jlog.filters
    assert jlog.propagate and jlog.level == level
    assert compilecache.process_cache_counts() == {
        "cache_hits": 0, "cache_misses": 0}


def test_observer_window_counts_beside_the_process_watch(fresh_cache,
                                                         monkeypatch):
    """A logger stops at the first filter that rejects a record, and
    the hit line is a DEBUG record: the observer's window must still
    see it while the process watch is installed.  With the wall floor
    at zero the fallback heuristic would call this window a miss."""
    from dprf_tpu import compilecache
    monkeypatch.setenv("DPRF_COMPILE_COLD_FLOOR_S", "0")
    jlog = logging.getLogger("jax._src.compiler")
    before = compilecache.process_cache_counts()["cache_hits"]
    with compilecache.compile_observer("md5", publish=False) as obs:
        jlog.debug("Persistent compilation cache hit for 'jit_step'")
    assert obs.cache == "hit"
    assert compilecache.process_cache_counts()["cache_hits"] == before + 1


def test_compile_classifier_falls_back_when_watch_sees_nothing():
    """A window whose executable was already live in jax's in-memory
    cache logs nothing: classification falls back to the entry-delta
    + wall-floor heuristic (fast re-dispatch reads as a hit)."""
    import jax
    import jax.numpy as jnp

    from dprf_tpu import compilecache
    if not compilecache.enabled():
        compilecache.enable()
    f = jax.jit(lambda v: (v + jnp.uint32(5)).sum())
    x = jnp.arange(61, dtype=jnp.uint32)
    f(x).block_until_ready()                  # compile outside
    with compilecache.compile_observer("md5", publish=False) as obs:
        f(x).block_until_ready()              # pure dispatch
    assert obs.cache == "hit"
