"""Telemetry subsystem: registry semantics, /metrics over the RPC
port, JSONL snapshot round-trip, and a planted-crack integration test
asserting the scraped counters match coordinator state."""

import json
import threading

import pytest

from dprf_tpu.telemetry import (MetricsRegistry, TelemetrySnapshotter,
                                load_snapshots, scrape_metrics,
                                telemetry_path)

pytestmark = pytest.mark.smoke


# ---------------------------------------------------------------------------
# registry semantics

def test_counter_labels_and_values():
    r = MetricsRegistry()
    c = r.counter("dprf_test_total", "a counter", labelnames=("engine",))
    c.inc(engine="md5")
    c.inc(41, engine="md5")
    c.inc(7, engine="sha1")
    assert c.value(engine="md5") == 42
    assert c.value(engine="sha1") == 7
    with pytest.raises(ValueError):
        c.inc(-1, engine="md5")          # counters only go up
    with pytest.raises(ValueError):
        c.inc(1, wrong="label")          # undeclared label set
    # get-or-create: same declaration returns the same metric
    assert r.counter("dprf_test_total", "x", labelnames=("engine",)) is c
    # conflicting re-declaration is an error, not silent shadowing
    with pytest.raises(ValueError):
        r.counter("dprf_test_total", "x", labelnames=("other",))
    with pytest.raises(ValueError):
        r.gauge("dprf_test_total", "x", labelnames=("engine",))


def test_histogram_bucket_redeclaration_conflicts():
    r = MetricsRegistry()
    h = r.histogram("dprf_rb_seconds", "x", buckets=(1, 10))
    assert r.histogram("dprf_rb_seconds", "x", buckets=(10, 1)) is h
    with pytest.raises(ValueError):
        r.histogram("dprf_rb_seconds", "x", buckets=(2, 20))


def test_worker_liveness_label_cap():
    """worker_id is client-controlled; id churn past the cap shares
    one overflow child instead of growing the registry forever."""
    from dprf_tpu.runtime.dispatcher import Dispatcher
    from dprf_tpu.runtime.rpc import CoordinatorState

    reg = MetricsRegistry()
    state = CoordinatorState({}, Dispatcher(10, 5, registry=reg), 1,
                             registry=reg)
    state.MAX_WORKER_LABELS = 4
    for i in range(10):
        state._touch_worker(f"w{i}")
    g = reg.get("dprf_worker_last_seen_timestamp")
    assert g.child_count() == 5         # 4 real ids + _overflow
    assert g.has_labels(worker="_overflow")
    assert not g.has_labels(worker="w9")


def test_gauge_set_inc_dec():
    r = MetricsRegistry()
    g = r.gauge("dprf_g", "a gauge")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value() == 6


def test_histogram_buckets_sum_count_and_timer():
    r = MetricsRegistry()
    h = r.histogram("dprf_h_seconds", "latency", buckets=(0.1, 1, 10))
    for v in (0.05, 0.5, 0.5, 5, 100):
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(106.05)
    text = r.render()
    # cumulative bucket counts in the exposition
    assert 'dprf_h_seconds_bucket{le="0.1"} 1' in text
    assert 'dprf_h_seconds_bucket{le="1"} 3' in text
    assert 'dprf_h_seconds_bucket{le="10"} 4' in text
    assert 'dprf_h_seconds_bucket{le="+Inf"} 5' in text
    assert "dprf_h_seconds_count 5" in text
    with h.time():
        pass
    assert h.count() == 6


def test_render_prometheus_shape():
    r = MetricsRegistry()
    r.counter("b_total", "second").inc(2)
    r.counter("a_total", "first", labelnames=("x",)).inc(x='we"ird\n')
    text = r.render()
    # HELP/TYPE headers precede samples; label values are escaped
    lines = text.splitlines()
    assert lines[0] == "# HELP a_total first"
    assert lines[1] == "# TYPE a_total counter"
    assert lines[2] == 'a_total{x="we\\"ird\\n"} 1'
    assert "b_total 2" in lines
    # snapshot is JSON-serializable and value-faithful
    snap = json.loads(r.snapshot_json())
    assert snap["b_total"]["kind"] == "counter"
    assert snap["b_total"]["values"][0]["value"] == 2


def test_registry_thread_safety():
    """Exact totals under the RPC server's handler-thread concurrency
    (and the worker's async submit): no lost increments."""
    r = MetricsRegistry()
    c = r.counter("dprf_t_total", "t", labelnames=("w",))
    h = r.histogram("dprf_t_seconds", "t")

    def work(i):
        for _ in range(5000):
            c.inc(w=f"w{i % 2}")
            h.observe(0.01)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value(w="w0") + c.value(w="w1") == 40000
    assert h.count() == 40000


# ---------------------------------------------------------------------------
# snapshot JSONL round-trip

def test_snapshot_jsonl_roundtrip(tmp_path):
    r = MetricsRegistry()
    r.counter("dprf_hits_total", "hits").inc(3)
    path = telemetry_path(str(tmp_path / "job.session"))
    snap = TelemetrySnapshotter(path, r, interval=60.0)
    snap.write_once()
    r.counter("dprf_hits_total", "hits").inc(2)
    snap.write_once()
    docs = load_snapshots(path)
    assert len(docs) == 2
    assert docs[0]["metrics"]["dprf_hits_total"]["values"][0]["value"] == 3
    assert docs[1]["metrics"]["dprf_hits_total"]["values"][0]["value"] == 5
    assert docs[1]["ts"] >= docs[0]["ts"]
    assert docs[1]["elapsed_s"] >= docs[0]["elapsed_s"]
    # torn tail line (killed run) is skipped, not fatal
    with open(path, "a") as fh:
        fh.write('{"ts": 1, "metr')
    assert len(load_snapshots(path)) == 2


def test_snapshotter_background_thread(tmp_path):
    r = MetricsRegistry()
    g = r.gauge("dprf_live", "liveness")
    g.set(1)
    path = str(tmp_path / "t.jsonl")
    snap = TelemetrySnapshotter(path, r, interval=0.3).start()
    import time
    time.sleep(1.0)
    snap.stop()                  # final line always written
    docs = load_snapshots(path)
    assert len(docs) >= 2
    assert docs[-1]["metrics"]["dprf_live"]["values"][0]["value"] == 1


# ---------------------------------------------------------------------------
# /metrics endpoint on the RPC port + planted-crack integration

def _planted_job(mask, plants, unit_size, registry):
    import hashlib

    from dprf_tpu.engines import get_engine
    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.runtime.dispatcher import Dispatcher
    from dprf_tpu.runtime.rpc import CoordinatorServer, CoordinatorState
    from dprf_tpu.runtime.session import job_fingerprint

    eng = get_engine("md5")
    gen = MaskGenerator(mask)
    targets = [eng.parse_target(hashlib.md5(p).hexdigest())
               for p in plants]
    fp = job_fingerprint("md5", f"mask:{mask}", gen.keyspace,
                         [t.digest for t in targets])
    job = {"engine": "md5", "attack": "mask", "attack_arg": mask,
           "customs": {}, "rules": None, "max_len": None,
           "targets": [t.raw for t in targets], "keyspace": gen.keyspace,
           "unit_size": unit_size, "batch": 4096, "hit_cap": 8,
           "fingerprint": fp}
    dispatcher = Dispatcher(gen.keyspace, unit_size, registry=registry)
    state = CoordinatorState(job, dispatcher, len(targets),
                             registry=registry)
    server = CoordinatorServer(state, "127.0.0.1", 0)
    server.start_background()
    return eng, gen, targets, state, server, dispatcher


def test_metrics_endpoint_and_counters_match_state():
    """Two workers crack a planted job; the scraped /metrics endpoint
    must agree with coordinator state: hits, units, candidates, and
    coverage (the ISSUE 1 acceptance criterion)."""
    from dprf_tpu.runtime.rpc import CoordinatorClient, worker_loop
    from dprf_tpu.runtime.worker import CpuWorker

    reg = MetricsRegistry()
    # "zz" is the LAST candidate, so no early stop: every unit runs
    eng, gen, targets, state, server, dispatcher = _planted_job(
        "?l?l", [b"ca", b"zz"], unit_size=100, registry=reg)
    try:
        def run_worker(wid):
            client = CoordinatorClient(*server.address)
            w = CpuWorker(eng, gen, targets)
            worker_loop(client, w, wid, idle_sleep=0.01, registry=reg)
            client.close()

        ts = [threading.Thread(target=run_worker, args=(f"w{i}",))
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert state.finished()
        assert state.found == {0: b"ca", 1: b"zz"}

        n_units = -(-gen.keyspace // 100)
        assert reg.get("dprf_hits_total").value() == len(state.found)
        assert reg.get("dprf_units_completed_total").value(job="j0") == n_units
        assert reg.get("dprf_units_leased_total").value(job="j0") == n_units
        assert reg.get("dprf_keyspace_covered").value(job="j0") == gen.keyspace
        cands = reg.get("dprf_candidates_hashed_total")
        assert cands.value(engine="md5", device="cpu") == gen.keyspace
        # the coordinator ALSO attributes completed units (its registry
        # is the scrapeable one; remote workers hash in other processes)
        assert cands.value(engine="md5", device="remote") == gen.keyspace
        assert reg.get("dprf_targets_found").value() == 2

        # scrape over the SAME port the RPC protocol uses
        text = scrape_metrics(*server.address)
        assert "dprf_hits_total 2" in text
        assert ('dprf_units_completed_total{job="j0"} '
                f"{n_units}") in text
        assert ('dprf_candidates_hashed_total{engine="md5",'
                f'device="cpu"}} {gen.keyspace}') in text
        assert 'dprf_worker_last_seen_timestamp{worker="w0"}' in text
        # op accounting saw the lease/complete traffic
        assert 'dprf_rpc_requests_total{op="lease"}' in text
    finally:
        server.shutdown()


def test_metrics_http_404_and_rpc_op():
    from dprf_tpu.runtime.rpc import CoordinatorClient

    reg = MetricsRegistry()
    *_, state, server, _ = _planted_job("?d", [b"7"], 5, reg)
    try:
        with pytest.raises(ValueError):
            scrape_metrics(*server.address, path="/nope")
        # the authenticated-protocol read of the same registry
        client = CoordinatorClient(*server.address)
        resp = client.call("metrics")
        assert "dprf_units_leased_total" in resp["text"]
        resp = client.call("metrics", format="json")
        assert resp["metrics"]["dprf_keyspace_total"]["values"][0][
            "value"] == 10
        client.close()
    finally:
        server.shutdown()


def test_metrics_endpoint_served_with_token_auth():
    """Read-only scrape needs no shared secret even when the RPC
    protocol is token-authenticated (it exposes counts, never the job
    or hits); the JSON protocol still challenges."""
    import hashlib

    from dprf_tpu.engines import get_engine
    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.runtime.dispatcher import Dispatcher
    from dprf_tpu.runtime.rpc import (CoordinatorClient, CoordinatorServer,
                                      CoordinatorState, RpcError)

    reg = MetricsRegistry()
    eng = get_engine("md5")
    gen = MaskGenerator("?d")
    targets = [eng.parse_target(hashlib.md5(b"3").hexdigest())]
    job = {"engine": "md5"}
    state = CoordinatorState(job, Dispatcher(gen.keyspace, 5,
                                             registry=reg),
                             len(targets), token="s3cret", registry=reg)
    server = CoordinatorServer(state, "127.0.0.1", 0)
    server.start_background()
    try:
        text = scrape_metrics(*server.address)
        assert 'dprf_keyspace_total{job="j0"} 10' in text
        client = CoordinatorClient(*server.address)   # no token
        with pytest.raises(RpcError):
            client.hello()
        client.close()
    finally:
        server.shutdown()


def test_local_coordinator_publishes(tmp_path):
    """The in-process Coordinator path publishes the same metric names
    the distributed path does (one dashboard for both)."""
    import hashlib

    from dprf_tpu.engines import get_engine
    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
    from dprf_tpu.runtime.dispatcher import Dispatcher
    from dprf_tpu.runtime.worker import CpuWorker

    reg = MetricsRegistry()
    eng = get_engine("md5")
    gen = MaskGenerator("?l?l")
    targets = [eng.parse_target(hashlib.md5(b"zz").hexdigest())]
    spec = JobSpec(engine="md5", device="cpu", attack="mask",
                   attack_arg="?l?l", keyspace=gen.keyspace,
                   fingerprint="t")
    coord = Coordinator(spec, targets,
                        Dispatcher(gen.keyspace, 100, registry=reg),
                        CpuWorker(eng, gen, targets), registry=reg)
    result = coord.run()
    assert result.found == {0: b"zz"}
    assert reg.get("dprf_hits_total").value() == 1
    assert reg.get("dprf_candidates_hashed_total").value(
        engine="md5", device="cpu") == result.tested
    assert reg.get("dprf_unit_seconds").count() == \
        reg.get("dprf_units_completed_total").value(job="j0")
    assert reg.get("dprf_targets_found").value() == 1


# ---------------------------------------------------------------------------
# bench freshness contract (driver bench.py)

def test_bench_publishes_to_registry():
    """dprf_tpu.bench runs report through the shared registry."""
    from dprf_tpu.bench import run_bench
    from dprf_tpu.telemetry import DEFAULT

    res = run_bench(engine="md5", device="cpu", mask="?l?l?l?l",
                    batch=1024, seconds=0.1)
    g = DEFAULT.get("dprf_bench_rate_hs")
    assert g is not None
    assert g.value(engine="md5", impl="xla",
                   device="cpu", mode="bench") == res["value"]
    assert DEFAULT.get("dprf_bench_runs_total").value(mode="bench") >= 1
