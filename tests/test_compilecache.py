"""Compile-cost elimination (ISSUE 3): persistent XLA compile cache
wiring (enable/idempotency/degradation), hit/miss classification +
metrics, the >=5x repeated-warmup acceptance case, `dprf prewarm`
populating entries a later worker warmup hits, overlapped (async)
warmup, and the tools/compile_report.py artifact summarizer."""

import json
import os
import threading
import time

import pytest

pytestmark = pytest.mark.smoke

from dprf_tpu import compilecache
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.telemetry import MetricsRegistry


# ---------------------------------------------------------------------------
# enable(): wiring, idempotency, degradation

def test_enable_idempotent_and_entry_count(fresh_cache):
    import jax
    assert compilecache.enabled()
    assert compilecache.cache_dir() == fresh_cache
    # persistence thresholds lowered so step compiles always persist
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert compilecache.enable() == fresh_cache                 # no-op
    assert compilecache.entry_count() == 0                      # empty


def test_enable_kill_switch_and_unwritable_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(compilecache.DISABLE_ENV, "0")
    assert compilecache.enable() is None
    monkeypatch.delenv(compilecache.DISABLE_ENV)
    # an unwritable "dir" (a plain file blocks makedirs) degrades to
    # None -- never an exception, never a half-enabled state
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("x")
    prev = compilecache.cache_dir()
    monkeypatch.setenv(compilecache.CACHE_DIR_ENV, str(blocker))
    assert compilecache.enable() is None
    assert compilecache.cache_dir() == prev     # state untouched


# ---------------------------------------------------------------------------
# placement: the variable decides, else one fixed path in the checkout

def _record_config_updates(monkeypatch):
    import jax
    seen = []
    real = jax.config.update

    def update(name, value):
        seen.append(name)
        real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return seen


def test_variable_set_places_cache_and_no_code_sets_the_dir(
        tmp_path, monkeypatch, cache_off):
    """$JAX_COMPILATION_CACHE_DIR set: the cache is there, and the
    program makes no jax.config.update of the directory -- only the
    on switch and the two persistence thresholds."""
    want = str(tmp_path / "placed")
    monkeypatch.setenv(compilecache.CACHE_DIR_ENV, want)
    assert compilecache.default_cache_dir() == want
    seen = _record_config_updates(monkeypatch)
    assert compilecache.enable() == want
    assert "jax_compilation_cache_dir" not in seen
    assert sorted(seen) == [
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes"]
    assert os.path.isdir(want)


def test_variable_unset_uses_the_fixed_checkout_path(monkeypatch,
                                                     cache_off):
    """Unset: ONE fixed path inside the checkout -- not under $HOME,
    and not a name made from a temp name, a pid or the time."""
    import jax
    monkeypatch.delenv(compilecache.CACHE_DIR_ENV, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".cache", "xla")
    assert compilecache.default_cache_dir() == want
    assert compilecache.CHECKOUT_CACHE_DIR == want
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compilecache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        compilecache.disable()
        jax.config.update("jax_compilation_cache_dir", prev)


def test_disable_switches_off_without_moving_the_dir(fresh_cache):
    import jax
    compilecache.disable()
    assert not compilecache.enabled()
    assert jax.config.jax_enable_compilation_cache is False
    assert jax.config.jax_compilation_cache_dir == fresh_cache
    assert compilecache.enable() == fresh_cache
    assert jax.config.jax_enable_compilation_cache is True


# ---------------------------------------------------------------------------
# hit/miss classification + metric surface

def test_classify_compile_rules(fresh_cache, monkeypatch):
    # new cache entries appeared -> miss, regardless of wall time
    assert compilecache.classify_compile(0.01, 3, 5) == "miss"
    # nothing new + under the cold floor -> hit
    assert compilecache.classify_compile(0.5, 5, 5) == "hit"
    # nothing new but OVER the floor -> still a miss (a backend whose
    # compiles cannot persist must not report eternal hits)
    assert compilecache.classify_compile(10.0, 5, 5) == "miss"
    monkeypatch.setenv(compilecache.COLD_FLOOR_ENV, "20")
    assert compilecache.classify_compile(10.0, 5, 5) == "hit"


def test_classify_off_when_disabled(cache_off):
    assert compilecache.classify_compile(9.0, 0, 5) == "off"


def test_observe_compile_metrics():
    m = MetricsRegistry()
    compilecache.observe_compile("md5", 3.0, "miss", registry=m)
    compilecache.observe_compile("md5", 0.2, "hit", registry=m)
    compilecache.observe_compile("md5", 0.2, "off", registry=m)
    assert m.counter("dprf_compile_cache_misses_total",
                     labelnames=("engine",)).value(engine="md5") == 1
    assert m.counter("dprf_compile_cache_hits_total",
                     labelnames=("engine",)).value(engine="md5") == 1
    h = compilecache.compile_histogram(m)
    assert h.count(engine="md5", cache="miss") == 1
    assert h.count(engine="md5", cache="hit") == 1
    assert h.count(engine="md5", cache="off") == 1   # off: observed,
    # not counted as cache behavior


# ---------------------------------------------------------------------------
# the acceptance case: repeated same-shape warmup >= 5x faster

def _make_worker(engine_name, mask, batch):
    from dprf_tpu import get_engine
    oracle = get_engine(engine_name, device="cpu")
    dev = get_engine(engine_name, device="jax")
    gen = MaskGenerator(mask)
    target = oracle.parse_target("ff" * oracle.digest_size)
    return dev.make_mask_worker(gen, [target], batch=batch,
                                hit_capacity=64, oracle=oracle)


@pytest.mark.compileheavy
def test_repeated_warmup_5x_faster_with_cache(fresh_cache):
    """Acceptance (ISSUE 3): with the persistent cache on, a
    repeated identically-shaped warmup's XLA compile is >= 5x faster
    than the cold compile -- the cache serves the executable instead
    of re-running XLA (measured ~10x for sha512 on this CPU backend;
    trace/lower time is host Python the cache can never touch, so the
    compile is compared to the compile).  Each build creates a NEW
    jit function, so nothing here can hit jax's in-memory trace
    cache; the end-to-end warmup must improve too."""
    w1 = _make_worker("sha512", "?l?l?l?d?d?d", 4096)
    w1.aot_compile()
    assert w1.compile_cache == "miss"
    assert compilecache.entry_count() > 0       # compile persisted
    warm = []
    for _ in range(2):
        w = _make_worker("sha512", "?l?l?l?d?d?d", 4096)
        w.aot_compile()
        assert w.compile_cache == "hit"
        warm.append(w.xla_compile_seconds)
    assert w1.xla_compile_seconds >= 5 * min(warm), (
        f"cold compile {w1.xla_compile_seconds:.2f}s vs cached "
        f"{min(warm):.2f}s")
    # the full dispatching warmup path hits and beats the cold total
    w3 = _make_worker("sha512", "?l?l?l?d?d?d", 4096)
    w3.warmup()
    assert w3.compile_cache == "hit"
    assert w3.compile_seconds < w1.compile_seconds
    # the metric surface saw one miss then the cache hits
    from dprf_tpu.telemetry import DEFAULT
    assert DEFAULT.get("dprf_compile_cache_hits_total").value(
        engine="sha512") >= 2


# ---------------------------------------------------------------------------
# dprf prewarm: AOT population a later worker warmup hits

def test_prewarm_populates_cache_for_subsequent_warmup(fresh_cache):
    from dprf_tpu.compilecache.prewarm import PrewarmSpec, run_prewarm

    spec = PrewarmSpec(engine="md5", attack="mask", batch=2048,
                       mask="?l?l?d?d")
    (res,) = run_prewarm([spec])
    assert res.error is None and res.cache == "miss"
    assert res.compile_s > 0 and compilecache.entry_count() > 0
    # a job-side worker of the SAME shape now warms from the cache
    w = _make_worker("md5", "?l?l?d?d", 2048)
    w.warmup()
    assert w.compile_cache == "hit"
    # prewarm is idempotent: a second pass is all hits
    (res2,) = run_prewarm([spec])
    assert res2.error is None and res2.cache == "hit"


def test_prewarm_wordlist_needs_the_real_wordlist(fresh_cache,
                                                  tmp_path):
    """The wordlist program embeds the packed word table (content is
    part of the cache key), so prewarm refuses to compile a wordlist
    shape without the job's file -- and with it, a job-side worker
    over the SAME file hits."""
    from dprf_tpu import get_engine
    from dprf_tpu.cli import _wordlist_max_len
    from dprf_tpu.compilecache.prewarm import PrewarmSpec, run_prewarm
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator

    (res,) = run_prewarm([PrewarmSpec(engine="md5", attack="wordlist",
                                      batch=1024)])
    assert res.error is not None and "--wordlist" in res.error

    wl = tmp_path / "words.txt"
    wl.write_text("".join(f"word{i:04d}\n" for i in range(512)))
    (res,) = run_prewarm([PrewarmSpec(engine="md5", attack="wordlist",
                                      batch=1024, wordlist=str(wl))])
    assert res.error is None and res.cache == "miss"
    oracle = get_engine("md5", device="cpu")
    gen = WordlistRulesGenerator.from_files(
        str(wl), None, max_len=_wordlist_max_len("md5", oracle, "jax"))
    w = get_engine("md5", device="jax").make_wordlist_worker(
        gen, [oracle.parse_target("ff" * 16)], batch=1024,
        hit_capacity=64, oracle=oracle)
    w.warmup()
    assert w.compile_cache == "hit"


def test_prewarm_cli_json_and_error_rows(fresh_cache, capsys):
    """The CLI prints a machine-checkable JSON line; a spec whose
    engine needs salted targets is reported as an error row, not a
    crashed prewarm (a fleet image bake must not die on one engine)."""
    from dprf_tpu.cli import main as cli_main

    rc = cli_main(["prewarm", "--engines", "md5,wpa2-pmkid",
                   "--attacks", "mask", "--mask", "?l?d?d",
                   "--batch", "2048", "-q"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["specs"] == 2 and doc["compiled"] == 1
    assert doc["errors"] == 1 and doc["cache_dir"] == fresh_cache
    rows = {r["engine"]: r for r in doc["results"]}
    assert "error" in rows["wpa2-pmkid"]      # unparseable fake target
    assert rows["md5"]["cache"] in ("hit", "miss")


def test_prewarm_seeds_from_tune_cache(fresh_cache, tmp_path,
                                       monkeypatch):
    """Without --engines, prewarm compiles exactly the shapes the
    tuning cache recorded for the jax device."""
    from dprf_tpu import tune
    from dprf_tpu.compilecache.prewarm import tune_seeded_specs

    monkeypatch.setenv("DPRF_TUNE_DIR", str(tmp_path / "tune"))
    env = tune.env_fingerprint("md5", "jax")
    tune.default_cache().put(
        tune.make_key("md5", attack="mask", device="jax", hit_cap=64),
        {"batch": 4096}, env)
    tune.default_cache().put(       # other device: filtered out
        tune.make_key("md5", attack="mask", device="cpu", hit_cap=64),
        {"batch": 512}, env)
    tune.default_cache().put(       # wordlist entry: needs --wordlist
        tune.make_key("sha256", attack="wordlist", device="jax",
                      hit_cap=64, rules_n=64),
        {"batch": 8192}, tune.env_fingerprint("sha256", "jax"))
    tune.default_cache().put(       # stale env: must NOT seed a spec
        tune.make_key("sha1", attack="mask", device="jax", hit_cap=64),
        {"batch": 2048}, dict(env, jax="0.0.0"))
    specs = tune_seeded_specs("jax")
    assert [(s.engine, s.attack, s.batch, s.hit_cap)
            for s in specs] == [("md5", "mask", 4096, 64)]
    # with the real wordlist supplied, the wordlist entry seeds too
    specs = tune_seeded_specs("jax", wordlist="words.txt",
                              rules="best64")
    assert ("sha256", "wordlist", 8192) in [
        (s.engine, s.attack, s.batch) for s in specs]
    assert [s for s in specs if s.attack == "wordlist"][0].wordlist \
        == "words.txt"


def test_prewarm_cli_refuses_without_cache(monkeypatch, capsys):
    from dprf_tpu.cli import main as cli_main
    monkeypatch.setenv(compilecache.DISABLE_ENV, "0")
    rc = cli_main(["prewarm", "--engines", "md5", "-q"])
    assert rc == 2


# ---------------------------------------------------------------------------
# overlapped warmup

class _RecordingWorker:
    """Minimal duck-typed worker borrowing MaskWorkerBase's async
    warmup machinery: records which thread ran warmup and whether a
    dispatch ever ran cold."""

    from dprf_tpu.runtime.worker import MaskWorkerBase as _B
    warmup_async = _B.warmup_async
    ensure_warm = _B.ensure_warm

    def __init__(self, fail=False, delay=0.05):
        self.fail = fail
        self.delay = delay
        self.warm_thread = None
        self.processed_cold = False
        self._warmed = False

    def warmup(self):
        self.warm_thread = threading.current_thread()
        time.sleep(self.delay)
        if self.fail:
            raise RuntimeError("mosaic exploded")
        self._warmed = True

    def process(self, unit):
        if not self._warmed:
            self.processed_cold = True
        return []


def test_warmup_async_runs_in_background_and_joins():
    w = _RecordingWorker()
    assert w.warmup_async() is w
    w.ensure_warm()
    assert w._warmed
    assert w.warm_thread is not threading.current_thread()
    w.ensure_warm()                    # idempotent after join
    # an already-warm worker never restarts a thread
    t = w.warm_thread
    w.warmup_async()
    w.ensure_warm()
    assert w.warm_thread is t


def test_warmup_async_error_surfaces_in_ensure_warm():
    w = _RecordingWorker(fail=True)
    w.warmup_async()
    with pytest.raises(RuntimeError, match="mosaic exploded"):
        w.ensure_warm()
    w.ensure_warm()                    # error consumed; no re-raise


def test_warmup_async_sync_fallback_env(monkeypatch):
    monkeypatch.setenv("DPRF_ASYNC_WARMUP", "0")
    w = _RecordingWorker()
    w.warmup_async()
    assert w._warmed                   # ran synchronously...
    assert w.warm_thread is threading.current_thread()


def test_coordinator_overlaps_warmup_before_first_dispatch():
    """Coordinator.run() kicks warmup_async at entry and joins it
    before the first submit: the step never dispatches cold, and the
    compile ran off the caller's thread."""
    from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
    from dprf_tpu.runtime.dispatcher import Dispatcher

    w = _RecordingWorker(delay=0.1)
    spec = JobSpec(engine="fake", device="jax", attack="mask",
                   attack_arg="?l", keyspace=256, fingerprint="f")
    coord = Coordinator(spec, [object()], Dispatcher(256, 64), w,
                        registry=MetricsRegistry())
    result = coord.run()
    assert result.exhausted
    assert w._warmed and not w.processed_cold
    assert w.warm_thread is not threading.current_thread()


def test_worker_loop_joins_async_warmup_before_processing():
    """The distributed path: worker_loop must ensure_warm before the
    first unit (cli.cmd_worker starts the compile before the loop)."""
    from dprf_tpu.runtime.dispatcher import Dispatcher
    from dprf_tpu.runtime.rpc import (CoordinatorClient,
                                      CoordinatorServer,
                                      CoordinatorState, worker_loop)

    m = MetricsRegistry()
    d = Dispatcher(keyspace=128, unit_size=64, registry=m)
    state = CoordinatorState({"engine": "md5"}, d, n_targets=1,
                             registry=m)
    server = CoordinatorServer(state, "127.0.0.1", 0)
    server.start_background()
    try:
        w = _RecordingWorker()
        w.warmup_async()
        client = CoordinatorClient(*server.address)
        done = worker_loop(client, w, "w0", idle_sleep=0.01,
                           registry=m)
        client.close()
        assert done == 2
        assert w._warmed and not w.processed_cold
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# bench JSON: compile_cache + cold/warm compile fields

@pytest.mark.compileheavy
def test_bench_reports_compile_cache_fields(fresh_cache):
    """Acceptance: bench JSON carries compile_cache plus cold/warm
    compile seconds.  First run on an empty cache dir is a miss that
    measures BOTH (warm via an AOT rebuild); a rerun is a hit."""
    from dprf_tpu.bench import run_bench
    from dprf_tpu.telemetry import DEFAULT

    misses = DEFAULT.counter("dprf_compile_cache_misses_total",
                             labelnames=("engine",))
    before = misses.value(engine="md5")
    res = run_bench(engine="md5", device="jax", mask="?l?l?l?l?l",
                    batch=2048, seconds=0.2, impl="xla")
    assert res["compile_cache"] == "miss"
    assert res["compile_cold_s"] > 0
    # ONE cold compile -> ONE miss observation (the compile site
    # publishes; _publish must not re-observe and double the counters
    # tools/compile_report.py sums)
    assert misses.value(engine="md5") == before + 1
    assert res["compile_warm_s"] is not None
    assert res["compile_warm_s"] < res["compile_cold_s"]
    res2 = run_bench(engine="md5", device="jax", mask="?l?l?l?l?l",
                     batch=2048, seconds=0.2, impl="xla")
    assert res2["compile_cache"] == "hit"
    assert res2["compile_cold_s"] is None
    assert res2["compile_warm_s"] is not None


@pytest.mark.compileheavy
def test_run_config_reports_compile_cache(fresh_cache):
    from dprf_tpu.bench import run_config

    res = run_config(1, device="jax", seconds=0.2, batch=2048)
    assert res["compile_cache"] == "miss"
    assert res["compile_cold_s"] > 0
    res2 = run_config(1, device="jax", seconds=0.2, batch=2048)
    assert res2["compile_cache"] == "hit"
    assert res2["compile_warm_s"] > 0


@pytest.mark.compileheavy
def test_tune_sweep_records_rung_cache(fresh_cache):
    """A cache-hit rung's fixed cost ~ 0: the sweep classifies each
    rung so the tune JSON shows which rungs paid a cold compile."""
    from dprf_tpu import get_engine
    from dprf_tpu.runtime.worker import CpuWorker
    from dprf_tpu.tune import sweep

    oracle = get_engine("md5", device="cpu")
    gen = MaskGenerator("?l?l?l?l")
    targets = [oracle.parse_target("ff" * 16)]

    def make_worker(batch):
        from dprf_tpu import get_engine as ge
        dev = ge("md5", device="jax")
        return dev.make_mask_worker(gen, targets, batch=batch,
                                    hit_capacity=64, oracle=oracle)

    res1 = sweep(make_worker, gen.keyspace, ladder=[2048],
                 probe_seconds=0.1)
    assert res1.swept[0].cache == "miss"
    res2 = sweep(make_worker, gen.keyspace, ladder=[2048],
                 probe_seconds=0.1)
    assert res2.swept[0].cache == "hit"
    assert "cache" in res2.swept[0].as_dict()
    # CpuWorker rungs compile nothing: still classified, never crash
    res3 = sweep(lambda b: CpuWorker(oracle, gen, targets, chunk=b),
                 gen.keyspace, ladder=[512], probe_seconds=0.05)
    assert res3.swept[0].cache in ("hit", "miss", "off")


# ---------------------------------------------------------------------------
# tools/compile_report.py: compile cost from snapshot artifacts

def test_compile_report_tool_summarizes_snapshots(tmp_path):
    import subprocess
    import sys

    from dprf_tpu.telemetry import TelemetrySnapshotter

    m = MetricsRegistry()
    for s, cache in ((4.0, "miss"), (6.0, "miss"), (0.3, "hit"),
                     (0.4, "hit"), (0.5, "hit")):
        compilecache.observe_compile("krb5aes", s, cache, registry=m)
    compilecache.observe_compile("md5", 1.2, "miss", registry=m)
    path = str(tmp_path / "job.session.telemetry.jsonl")
    TelemetrySnapshotter(path, m, interval=3600).write_once()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "compile_report.py")
    proc = subprocess.run([sys.executable, tool, path, "--json"],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["cache_hits"] == 3 and doc["cache_misses"] == 3
    rows = {(r["engine"], r["cache"]): r for r in doc["compiles"]}
    miss = rows[("krb5aes", "miss")]
    assert miss["count"] == 2 and miss["total_s"] == 10.0
    # bucket-interpolated percentiles land inside the observed band
    assert 2.5 < miss["p50_s"] <= 10.0
    assert miss["p95_s"] >= miss["p50_s"]
    hit = rows[("krb5aes", "hit")]
    assert hit["count"] == 3 and hit["p95_s"] <= 1.0
    # human rendering works too (smoke: table + hit ratio line)
    proc = subprocess.run([sys.executable, tool, path],
                          capture_output=True, text=True)
    assert proc.returncode == 0
    assert "hit ratio 50%" in proc.stdout
    # an empty/missing file is rc 1 ("no data"), not a crash
    proc = subprocess.run(
        [sys.executable, tool, str(tmp_path / "nope.jsonl")],
        capture_output=True, text=True)
    assert proc.returncode == 1


# ---------------------------------------------------------------------------
# prewarm spec kinds: combinator/hybrid + sharded (ISSUE 5 satellite)

def test_prewarm_combinator_needs_real_files_and_skip_is_cheap(
        fresh_cache):
    """Combinator prewarm refuses stand-ins (both word tables are
    embedded in the program), and a sharded spec on a host with too
    few devices is SKIPPED -- reported, never an error, and never
    compiled."""
    from dprf_tpu.compilecache.prewarm import (PrewarmSpec,
                                               explicit_specs,
                                               run_prewarm)

    (res,) = run_prewarm([PrewarmSpec(engine="md5",
                                      attack="combinator",
                                      batch=512)])
    assert res.error is not None and "--combinator" in res.error
    (res,) = run_prewarm([PrewarmSpec(engine="md5",
                                      attack="hybrid-wm", batch=512)])
    assert res.error is not None and "--wordlist" in res.error
    # sharded shape on a host with fewer devices: graceful skip
    (res,) = run_prewarm([PrewarmSpec(engine="md5", attack="mask",
                                      batch=512, devices=999)])
    assert res.error is None and res.skipped
    assert res.cache == "skip" and res.devices == 999
    # explicit_specs threads the new fields through
    (spec,) = explicit_specs(["md5"], ["combinator"], batch=512,
                             combinator="l.txt,r.txt", devices=2)
    assert spec.combinator == "l.txt,r.txt" and spec.devices == 2
    (spec,) = explicit_specs(["md5"], ["hybrid-mw"], batch=512,
                             wordlist="w.txt")
    assert spec.wordlist == "w.txt" and spec.combinator is None


@pytest.mark.compileheavy
def test_prewarm_combinator_and_hybrid_shapes_warm_the_job(
        fresh_cache, tmp_path):
    """A combinator prewarm over the job's REAL files populates the
    cache the job-side DeviceCombinatorWorker warms from; the hybrid
    shape synthesizes its mask side exactly like a job."""
    from dprf_tpu import get_engine
    from dprf_tpu.compilecache.prewarm import (PrewarmSpec,
                                               run_prewarm)
    from dprf_tpu.generators.combinator import CombinatorGenerator
    from dprf_tpu.generators.wordlist import load_words

    lp, rp = tmp_path / "l.txt", tmp_path / "r.txt"
    lp.write_text("".join(f"left{i}\n" for i in range(64)))
    rp.write_text("".join(f"right{i}\n" for i in range(64)))
    (res,) = run_prewarm([PrewarmSpec(
        engine="md5", attack="combinator", batch=512,
        combinator=f"{lp},{rp}")])
    assert res.error is None and res.cache == "miss", res.as_dict()
    # the job path (same files, same batch) hits
    oracle = get_engine("md5", device="cpu")
    gen = CombinatorGenerator(load_words(str(lp), 55)[0],
                              load_words(str(rp), 55)[0], max_len=55)
    w = get_engine("md5", device="jax").make_combinator_worker(
        gen, [oracle.parse_target("ff" * 16)], batch=512,
        hit_capacity=64, oracle=oracle)
    w.warmup()
    assert w.compile_cache == "hit"
    # hybrid word+mask compiles too (its own program: different table)
    (res,) = run_prewarm([PrewarmSpec(
        engine="md5", attack="hybrid-wm", batch=512,
        wordlist=str(lp), mask="?d?d")])
    assert res.error is None and res.cache in ("miss", "hit")


@pytest.mark.compileheavy
def test_prewarm_sharded_shape_warms_the_sharded_job(fresh_cache,
                                                     capsys):
    """devices=N prewarms the SHARDED step through the same factory a
    `--devices N` job selects (the hermetic suite fakes 8 CPU chips);
    a later sharded worker of the same shape warms from the cache, and
    the CLI JSON reports skip counts separately from errors."""
    from dprf_tpu import get_engine
    from dprf_tpu.cli import main as cli_main
    from dprf_tpu.compilecache.prewarm import (PrewarmSpec,
                                               run_prewarm)
    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.parallel.mesh import make_mesh

    (res,) = run_prewarm([PrewarmSpec(engine="md5", attack="mask",
                                      batch=512, mask="?l?d?d",
                                      devices=2)])
    assert res.error is None and res.cache == "miss", res.as_dict()
    assert res.devices == 2
    oracle = get_engine("md5", device="cpu")
    w = get_engine("md5", device="jax").make_sharded_mask_worker(
        MaskGenerator("?l?d?d"), [oracle.parse_target("ff" * 16)],
        make_mesh(2), 512, hit_capacity=64, oracle=oracle)
    w.warmup()
    assert w.compile_cache == "hit"
    # CLI: one compiled sharded spec + one skipped (too many devices)
    rc = cli_main(["prewarm", "--engines", "md5", "--attacks", "mask",
                   "--mask", "?l?d?d", "--batch", "512",
                   "--devices", "2", "-q"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["compiled"] == 1 and doc["skipped"] == 0
    rc = cli_main(["prewarm", "--engines", "md5", "--attacks", "mask",
                   "--batch", "512", "--devices", "64", "-q"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["skipped"] == 1 and doc["errors"] == 0
    assert doc["results"][0]["cache"] == "skip"
