"""`dprf check` analyzer tests: every analyzer against fixture trees
with planted violations (each must be caught at the planted line) and
clean twins (no false positives), the suppression framework, the CLI,
and the real repo staying clean inside its budget.

Fixture trees are written under tmp_path with the same shape the
AnalysisContext walks (dprf_tpu/, tests/, tools/, README.md); the
analyzers are pure AST so nothing in a fixture is ever imported
(except the env registry, which is exec'd standalone by design).
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from dprf_tpu import analysis

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_repo(tmp_path, files):
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))
    return str(tmp_path)


def check(root, only):
    findings, _ = analysis.run(root, only=[only])
    return findings


def bad(findings):
    return analysis.unsuppressed(findings)


# ---------------------------------------------------------------------------
# locks: guarded-by discipline

LOCKS_DECL = """\
    import threading
    import time

    GUARDED_BY = {
        "State": {"lock": ("found", "count")},
    }

    class State:
        def __init__(self):
            self.lock = threading.Lock()
            self.found = {}
            self.count = 0
"""


def test_locks_unguarded_write_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/state.py": LOCKS_DECL + """\

        def racy(self):
            self.found["x"] = 1
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1
    assert "guarded by" in f[0].message and "found" in f[0].message
    assert f[0].path.endswith("state.py")


def test_locks_unguarded_read_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/state.py": LOCKS_DECL + """\

        def racy_read(self):
            return len(self.found)
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1 and "found" in f[0].message


def test_locks_blocking_call_under_lock_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/state.py": LOCKS_DECL + """\

        def slow(self):
            with self.lock:
                self.count += 1
                time.sleep(1)
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1
    assert "blocking call time.sleep" in f[0].message


def test_locks_order_inversion_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/pair.py": """\
        import threading

        GUARDED_BY = {"Pair": {"l1": ("x",), "l2": ("y",)}}

        class Pair:
            def __init__(self):
                self.l1 = threading.Lock()
                self.l2 = threading.Lock()
                self.x = 0
                self.y = 0

            def fwd(self):
                with self.l1:
                    with self.l2:
                        self.x = 1
                        self.y = 1

            def rev(self):
                with self.l2:
                    with self.l1:
                        self.x = 2
                        self.y = 2
"""})
    f = bad(check(root, "locks"))
    assert any("lock-order cycle" in x.message for x in f), \
        [x.message for x in f]
    # the guarded accesses themselves are all inside both locks: the
    # cycle must be the ONLY finding
    assert all("lock-order cycle" in x.message for x in f)


def test_locks_inversion_through_method_call_cycle(tmp_path):
    # m1 <-> m2 call each other; an early query while holding l1 must
    # not poison the transitive-acquires cache for m2 (a cached
    # mid-cycle placeholder would hide m1's l1 from b(), dropping the
    # l2->l1 edge and missing the inversion against inv())
    root = make_repo(tmp_path, {"dprf_tpu/cyc.py": """\
        import threading

        GUARDED_BY = {"S": {"l1": ("x",), "l2": ("y",)}}

        class S:
            def __init__(self):
                self.l1 = threading.Lock()
                self.l2 = threading.Lock()
                self.x = 0
                self.y = 0

            def m1(self, depth):
                with self.l1:
                    self.x = 1
                self.m2(depth)

            def m2(self, depth):
                if depth:
                    self.m1(depth - 1)

            def a(self):
                with self.l1:
                    self.m1(1)

            def b(self):
                with self.l2:
                    self.m2(1)

            def inv(self):
                with self.l1:
                    with self.l2:
                        pass
"""})
    f = bad(check(root, "locks"))
    assert any("lock-order cycle" in x.message for x in f), \
        [x.message for x in f]


def test_locks_clean_fixture_no_false_positives(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/state.py": LOCKS_DECL + """\

        def good(self):
            with self.lock:
                self.count += 1
                self.found["x"] = self.count

        def _peek(self):
            return len(self.found)
        _peek._holds_lock = "lock"

        def slow_ok(self):
            with self.lock:
                n = self.count
            time.sleep(n)
"""})
    assert bad(check(root, "locks")) == []


def test_locks_atomic_multi_writer_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/flag.py": """\
        GUARDED_BY = {"Flag": {"<atomic>": ("error",)}}

        class Flag:
            def __init__(self):
                self.error = None

            def latch(self, e):
                self.error = e

            def second_writer(self):
                self.error = None
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1 and "single-writer" in f[0].message


def test_locks_extern_acquiring_lock_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/ext.py": LOCKS_DECL + """\

    GUARDED_BY_MORE = None   # (decl for Helper lives in the real table)

    class Helper:
        def __init__(self, state: "State"):
            self.state = state

        def sneaky(self):
            with self.state.lock:
                pass
""", "dprf_tpu/decl.py": """\
    GUARDED_BY = {"Helper": {"<extern>": ()}}
"""})
    f = bad(check(root, "locks"))
    assert any("<extern>" in x.message and "acquires" in x.message
               for x in f), [x.message for x in f]


def test_locks_undeclared_class_in_table_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/empty.py": """\
        GUARDED_BY = {"Ghost": {"lock": ("x",)}}
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1 and "unknown class" in f[0].message


def test_locks_lock_never_assigned_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/state.py": """\
        GUARDED_BY = {"State": {"lock": ("found",)}}

        class State:
            def __init__(self):
                self.found = {}
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1 and "never assigned in __init__" in f[0].message


# ---------------------------------------------------------------------------
# protocol: RPC contract

def test_protocol_one_sided_keys_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_lease(self, msg):
                wid = msg["worker_id"]
                count = msg.get("count")
                return {"unit": wid}

        class Client:
            def call(self, op, **kw):
                return {}

            def go(self):
                resp = self.call("lease", worker_id=3, extra=1)
                u = resp["unit"]
                t = resp["trace"]
                self.call("nosuch")
                return u, t
"""})
    msgs = [x.message for x in bad(check(root, "protocol"))]
    assert len(msgs) == 4, msgs
    assert any("reads request key 'count'" in m for m in msgs)
    assert any("sends key 'extra'" in m for m in msgs)
    assert any("response read of key 'trace'" in m for m in msgs)
    assert any("no op_nosuch handler" in m for m in msgs)


def test_protocol_clean_fixture_no_false_positives(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_lease(self, msg):
                wid = msg["worker_id"]
                return {"unit": wid, "nested": {"trace": 1}}

        class Client:
            def call(self, op, **kw):
                return {}

            def go(self):
                resp = self.call("lease", worker_id=3)
                # transport keys are free; nested payload keys count
                # as returned (over-approximation, documented)
                if "error" in resp:
                    return None
                return resp["unit"], resp.get("trace")
"""})
    assert bad(check(root, "protocol")) == []


def test_protocol_scope_isolation(tmp_path):
    # two functions each call a different op and read "their" key;
    # a flat module-wide pass would cross-attribute the reads
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_a(self, msg):
                return {"akey": 1}

            def op_b(self, msg):
                return {"bkey": 2}

        class Client:
            def call(self, op, **kw):
                return {}

            def ga(self):
                resp = self.call("a")
                return resp["akey"]

            def gb(self):
                resp = self.call("b")
                return resp["bkey"]
"""})
    assert bad(check(root, "protocol")) == []


def test_protocol_nested_def_scope_isolation(tmp_path):
    # a nested def reusing the parent's response-variable name must
    # not cross-attribute its reads to the parent's op (or vice versa)
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_status(self, msg):
                return {"parked": 0}

            def op_lease(self, msg):
                return {"unit": 1}

        class Client:
            def call(self, op, **kw):
                return {}

            def outer(self):
                resp = self.call("status")
                n = resp["parked"]

                def inner():
                    resp = self.call("lease")
                    return resp["unit"]
                return n, inner
"""})
    assert bad(check(root, "protocol")) == []


# ---------------------------------------------------------------------------
# env-knobs: registry lint

ENV_REGISTRY = """\
    KNOBS = {}

    def _declare(name, default, type, doc):
        KNOBS[name] = (default, type, doc)

    _declare("DPRF_FIX_USED", 1, "int", "a knob somebody reads")
"""

ENV_READER = """\
    from dprf_tpu.utils import env

    def f():
        return env.get_int("DPRF_FIX_USED")
"""


def test_envknobs_raw_read_caught(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/utils/env.py": ENV_REGISTRY,
        "dprf_tpu/reader.py": ENV_READER,
        "dprf_tpu/rogue.py": """\
            import os

            A = os.environ.get("DPRF_FIX_USED")

            def g():
                return os.getenv("DPRF_FIX_USED")

            def h():
                return os.environ["DPRF_FIX_USED"]
"""})
    f = bad(check(root, "env-knobs"))
    assert len(f) == 3, [x.message for x in f]
    assert all("raw environment read" in x.message for x in f)
    assert all(x.path.endswith("rogue.py") for x in f)


def test_envknobs_unauditable_read_caught(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/utils/env.py": ENV_REGISTRY,
        "dprf_tpu/reader.py": ENV_READER,
        "dprf_tpu/sneaky.py": """\
            import os

            def h(name):
                return os.environ[name]
"""})
    f = bad(check(root, "env-knobs"))
    assert len(f) == 1 and "cannot resolve" in f[0].message


def test_envknobs_undeclared_getter_and_stale_knob_caught(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/utils/env.py": ENV_REGISTRY + """\
    _declare("DPRF_FIX_STALE", 0, "int", "nobody reads this")
""",
        "dprf_tpu/reader.py": ENV_READER + """\

    def g():
        return env.get_str("DPRF_FIX_MISSING")
"""})
    msgs = [x.message for x in bad(check(root, "env-knobs"))]
    assert len(msgs) == 2, msgs
    assert any("undeclared knob 'DPRF_FIX_MISSING'" in m for m in msgs)
    assert any("'DPRF_FIX_STALE' is declared but never read" in m
               for m in msgs)


def test_envknobs_module_constant_resolution(tmp_path):
    # the `ENABLE_ENV = "DPRF_X"` idiom: raw reads through a
    # module-level constant are still caught
    root = make_repo(tmp_path, {
        "dprf_tpu/utils/env.py": ENV_REGISTRY,
        "dprf_tpu/reader.py": ENV_READER,
        "dprf_tpu/alias.py": """\
            import os

            KNOB = "DPRF_FIX_USED"

            def g():
                return os.environ.get(KNOB)
"""})
    f = bad(check(root, "env-knobs"))
    assert len(f) == 1 and "DPRF_FIX_USED" in f[0].message


def test_envknobs_aliased_os_import_caught(tmp_path):
    # `import os as _os` / `from os import environ, getenv` must not
    # make a raw read invisible (the hole that let an unmigrated
    # engines/device read survive the first migration pass)
    root = make_repo(tmp_path, {
        "dprf_tpu/utils/env.py": ENV_REGISTRY,
        "dprf_tpu/reader.py": ENV_READER,
        "dprf_tpu/rogue.py": """\
            import os as _os
            from os import environ as _environ
            from os import getenv as _getenv

            def a():
                return _os.environ.get("DPRF_FIX_USED", "1")

            def b():
                return _os.getenv("DPRF_FIX_USED")

            def c():
                return _environ["DPRF_FIX_USED"]

            def d():
                return _getenv("DPRF_FIX_USED")
"""})
    f = bad(check(root, "env-knobs"))
    assert len(f) == 4, [x.message for x in f]
    assert all("raw environment read" in x.message for x in f)


def test_envknobs_clean_fixture_no_false_positives(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/utils/env.py": ENV_REGISTRY,
        "dprf_tpu/reader.py": ENV_READER + """\

    def non_knob():
        import os
        return os.environ.get("HOME")   # non-DPRF reads stay legal

    def writes_are_legal():
        import os
        os.environ["DPRF_FIX_USED"] = "2"
"""})
    assert bad(check(root, "env-knobs")) == []


# ---------------------------------------------------------------------------
# markers / metrics / worker-contract (absorbed conftest lints)

def test_markers_unmarked_device_test_caught(tmp_path):
    root = make_repo(tmp_path, {
        "tests/test_fixture_device.py": """\
            from dprf_tpu.ops import pallas_mask

            def test_x():
                assert pallas_mask is not None
""",
        "tests/test_fixture_marked.py": """\
            import pytest
            from dprf_tpu.ops import pallas_mask

            pytestmark = pytest.mark.compileheavy

            def test_y():
                assert pallas_mask is not None
""",
        "dprf_tpu/__init__.py": ""})
    f = bad(check(root, "markers"))
    assert len(f) == 1
    assert f[0].path.endswith("test_fixture_device.py")


def test_metrics_duplicate_declaration_caught(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/a.py": """\
            def declare(reg):
                return reg.counter("dprf_fix_total", "doc")
""",
        "dprf_tpu/b.py": """\
            def declare_again(reg):
                return reg.counter("dprf_fix_total", "doc")
"""})
    f = bad(check(root, "metrics"))
    assert len(f) == 1 and "declared at 2 sites" in f[0].message


def test_metrics_undeclared_span_caught(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/telemetry/trace.py": """\
            SPAN_NAMES = ("lease", "sweep")
""",
        "dprf_tpu/user.py": """\
            def f(tracer, t0, t1):
                tracer.record("lease", t0, t1)
                tracer.record("bogus", t0, t1)
"""})
    f = bad(check(root, "metrics"))
    assert len(f) == 1
    assert "span 'bogus' not declared" in f[0].message


def test_metrics_profiler_call_outside_owner_caught(tmp_path):
    """Rule 4 (ISSUE 15): jax.profiler trace calls outside
    telemetry/profiler.py are findings -- jax allows ONE active
    trace, so every starter must share ProfileCapture's slot."""
    root = make_repo(tmp_path, {
        "dprf_tpu/telemetry/profiler.py": """\
            def owner(directory):
                import jax
                jax.profiler.start_trace(directory)
                jax.profiler.stop_trace()
""",
        "dprf_tpu/rogue.py": """\
            def rogue(directory):
                import jax
                jax.profiler.start_trace(directory)
                with jax.profiler.trace(directory):
                    pass
                jax.profiler.stop_trace()
"""})
    f = bad(check(root, "metrics"))
    assert len(f) == 3
    assert all(x.path.endswith("rogue.py") for x in f)
    assert {x.line for x in f} == {3, 4, 6}


def test_metrics_profiler_unrelated_trace_calls_clean(tmp_path):
    """A clean twin: ``.trace(`` on anything NOT named profiler (span
    recorders, loggers) never matches rule 4."""
    root = make_repo(tmp_path, {
        "dprf_tpu/spans.py": """\
            def fine(recorder, directory):
                with recorder.trace(directory):
                    pass
"""})
    assert bad(check(root, "metrics")) == []


def test_worker_contract_violations_caught(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/w.py": """\
            class Unmarked:
                def process(self, units):
                    return None

            class BadSubmit:
                def process(self, units):
                    return None
                process._submit_based = True

            class GoodSerial:
                def process(self, units):
                    return None
                process._serial_only = True

            class GoodSubmit:
                def submit(self, unit):
                    pass

                def process(self, units):
                    return None
                process._submit_based = True
"""})
    msgs = [x.message for x in bad(check(root, "worker-contract"))]
    assert len(msgs) == 2, msgs
    assert any("Unmarked" in m and "pipelining stance" in m
               for m in msgs)
    assert any("BadSubmit" in m and "no submit()" in m for m in msgs)


# ---------------------------------------------------------------------------
# suppressions

SUPPRESSIBLE = {
    "dprf_tpu/utils/env.py": ENV_REGISTRY,
    "dprf_tpu/reader.py": ENV_READER,
}


def test_suppression_with_reason_silences(tmp_path):
    root = make_repo(tmp_path, dict(SUPPRESSIBLE, **{
        "dprf_tpu/rogue.py": """\
            import os

            A = os.environ.get("DPRF_FIX_USED")  # dprf: disable=env-knobs -- fixture: documents the raw idiom
"""}))
    findings, _ = analysis.run(root, only=["env-knobs"])
    assert bad(findings) == []
    sup = [f for f in findings if f.suppressed]
    assert len(sup) == 1
    assert sup[0].reason == "fixture: documents the raw idiom"


def test_suppression_standalone_comment_covers_next_line(tmp_path):
    root = make_repo(tmp_path, dict(SUPPRESSIBLE, **{
        "dprf_tpu/rogue.py": """\
            import os

            # dprf: disable=env-knobs -- fixture: standalone form
            A = os.environ.get("DPRF_FIX_USED")
"""}))
    findings, _ = analysis.run(root, only=["env-knobs"])
    assert bad(findings) == []


def test_suppression_without_reason_is_a_finding(tmp_path):
    root = make_repo(tmp_path, dict(SUPPRESSIBLE, **{
        "dprf_tpu/rogue.py": """\
            import os

            A = os.environ.get("DPRF_FIX_USED")  # dprf: disable=env-knobs
"""}))
    findings, _ = analysis.run(root, only=["env-knobs"])
    msgs = [f.message for f in bad(findings)]
    # the raw read is NOT silenced, and the reasonless suppression is
    # itself flagged
    assert len(msgs) == 2, msgs
    assert any("without a reason" in m for m in msgs)
    assert any("raw environment read" in m for m in msgs)


def test_unused_suppression_is_a_finding(tmp_path):
    root = make_repo(tmp_path, dict(SUPPRESSIBLE, **{
        "dprf_tpu/fine.py": """\
            X = 1   # dprf: disable=env-knobs -- nothing here anymore
"""}))
    findings, _ = analysis.run(root, only=["env-knobs"])
    msgs = [f.message for f in bad(findings)]
    assert len(msgs) == 1 and "unused suppression" in msgs[0]


def test_unused_suppression_ignored_when_check_skipped(tmp_path):
    # a locks suppression is not "unused" on an env-knobs-only run:
    # the check it names never ran
    root = make_repo(tmp_path, dict(SUPPRESSIBLE, **{
        "dprf_tpu/fine.py": """\
            X = 1   # dprf: disable=locks -- for a run that skips locks
"""}))
    findings, _ = analysis.run(root, only=["env-knobs"])
    assert bad(findings) == []


# ---------------------------------------------------------------------------
# runner / CLI / real repo

def test_parse_failure_is_a_finding(tmp_path):
    # the broken file must contain an analyzer's needle: files the
    # source prefilters rule out are (intentionally) never parsed
    root = make_repo(tmp_path, {
        "dprf_tpu/broken.py": 'def f(:\n    os.getenv("DPRF_X")\n'})
    findings, _ = analysis.run(root)
    msgs = [f.message for f in bad(findings)]
    assert any("does not parse" in m for m in msgs)


def test_unknown_check_rejected(tmp_path):
    with pytest.raises(ValueError, match="unknown checks"):
        analysis.run(str(tmp_path), only=["nosuch"])


def test_run_only_and_skip(tmp_path):
    make_repo(tmp_path, {"dprf_tpu/x.py": "X = 1\n"})
    _, ran = analysis.run(str(tmp_path), only=["locks", "metrics"])
    assert ran == {"locks", "metrics"}
    _, ran = analysis.run(str(tmp_path), skip=["locks"])
    assert "locks" not in ran and "metrics" in ran


def test_cli_json_and_exit_codes(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/w.py": """\
            class Unmarked:
                def process(self, units):
                    return None
"""})
    proc = subprocess.run(
        [sys.executable, "-m", "dprf_tpu.analysis", "--root", root,
         "--only", "worker-contract,metrics", "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["total"] == 1
    assert sorted(payload["checks"]) == ["metrics", "worker-contract"]
    assert payload["findings"][0]["check"] == "worker-contract"
    assert payload["findings"][0]["line"] == 1
    # per-analyzer wall time for the CI artifact (ISSUE 8 satellite)
    assert sorted(payload["timings_s"]) == ["metrics",
                                            "worker-contract"]
    assert all(isinstance(v, float) and v >= 0
               for v in payload["timings_s"].values())

    proc = subprocess.run(
        [sys.executable, "-m", "dprf_tpu.analysis", "--root", root,
         "--only", "metrics"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0

    proc = subprocess.run(
        [sys.executable, "-m", "dprf_tpu.analysis", "--only", "nosuch"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2


# ---------------------------------------------------------------------------
# coverage-events: event-site manifest discipline (ISSUE 19)

COVERAGE_DECL = """\
    EVENT_NAMES = ("split", "complete", "redrive")

    COVERAGE_EVENT_SITES = (
        ("dprf_tpu/disp.py", "complete"),
        ("dprf_tpu/disp.py", "fail"),
    )
"""


def test_coverage_events_violations_caught(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/telemetry/coverage.py": COVERAGE_DECL,
        "dprf_tpu/disp.py": """\
            from dprf_tpu.telemetry import coverage

            class D:
                def complete(self, s, e):
                    # undeclared event literal
                    self.coverage.event("explode", s, e)

                def fail(self, s, e):
                    # declared site that never calls the API
                    return (s, e)

                def reissue(self, s, e):
                    # caller missing from the manifest
                    self.coverage.event("split", s, e)

                def redrive(self, s, e, name):
                    # computed name: statically unauditable
                    coverage.note(name, s, e)
"""})
    # the computed-name call draws two findings: unauditable literal
    # AND an undeclared calling site
    msgs = [x.message for x in bad(check(root, "coverage-events"))]
    assert len(msgs) == 5, msgs
    assert any("'explode' not declared" in m for m in msgs)
    assert any("never calls" in m for m in msgs)
    assert any("'reissue'" in m and "not declared in" in m
               for m in msgs)
    assert any("'redrive'" in m and "not declared in" in m
               for m in msgs)
    assert any("string literal" in m for m in msgs)


def test_coverage_events_clean_twin(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/telemetry/coverage.py": COVERAGE_DECL,
        "dprf_tpu/disp.py": """\
            class D:
                def complete(self, s, e):
                    self.coverage.event("complete", s, e)

                def fail(self, s, e):
                    self.coverage.event("split", s, e)
"""})
    assert bad(check(root, "coverage-events")) == []


def test_coverage_events_stale_manifest_entry(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/telemetry/coverage.py": COVERAGE_DECL,
        "dprf_tpu/disp.py": """\
            class D:
                def complete(self, s, e):
                    self.coverage.event("complete", s, e)
"""})
    f = bad(check(root, "coverage-events"))
    assert len(f) == 1 and "no such function" in f[0].message


def test_run_for_conftest_formats_failures(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/w.py": """\
            class Unmarked:
                def process(self, units):
                    return None
"""})
    msg = analysis.run_for_conftest(root)
    assert msg is not None and "1 violation" in msg
    assert "w.py:1" in msg


def test_real_repo_is_clean_and_fast():
    """The acceptance criterion: all nine analyzers over the whole
    package, zero unsuppressed findings, comfortably inside the 5 s
    CLI budget on the 2-core box."""
    t0 = time.monotonic()
    findings, ran = analysis.run(REPO)
    elapsed = time.monotonic() - t0
    assert ran == {"markers", "metrics", "worker-contract", "locks",
                   "protocol", "env-knobs", "threads", "retrace",
                   "coverage-events"}
    assert bad(findings) == [], "\n".join(
        f.render() for f in bad(findings))
    # every suppression carries a reason (reasonless ones would be
    # unsuppressed findings above); budget check last
    assert elapsed < 5.0, f"analysis took {elapsed:.2f}s"


def test_readme_knob_table_roundtrip(tmp_path):
    from dprf_tpu.utils import env

    readme = tmp_path / "README.md"
    readme.write_text("# x\n\n%s\n%s\n\ntail\n"
                      % (env.README_BEGIN, env.README_END))
    assert env.readme_sync_error(str(readme)) is not None
    assert env.write_readme_table(str(readme)) is True
    assert env.readme_sync_error(str(readme)) is None
    # idempotent
    assert env.write_readme_table(str(readme)) is False
    # drift is detected
    readme.write_text(readme.read_text().replace(
        "DPRF_PIPELINE_DEPTH", "DPRF_GONE"))
    assert env.readme_sync_error(str(readme)) is not None


def test_registry_typed_getters(monkeypatch):
    from dprf_tpu.utils import env

    monkeypatch.setenv("DPRF_PIPELINE_DEPTH", "junk")
    assert env.get_int("DPRF_PIPELINE_DEPTH") == 2   # junk -> default
    monkeypatch.setenv("DPRF_PIPELINE_DEPTH", "7")
    assert env.get_int("DPRF_PIPELINE_DEPTH") == 7
    monkeypatch.setenv("DPRF_TRACE", "0")
    assert env.get_bool("DPRF_TRACE") is False
    monkeypatch.setenv("DPRF_TRACE", "yes")
    assert env.get_bool("DPRF_TRACE") is True
    monkeypatch.delenv("DPRF_TRACE")
    assert env.get_bool("DPRF_TRACE") is True        # declared default
    with pytest.raises(KeyError, match="undeclared env knob"):
        # dprf: disable=env-knobs -- asserts the registry rejects undeclared names
        env.get_str("DPRF_NOT_A_KNOB")


def test_the_sampler_knob_is_gone_from_registry_and_readme():
    from dprf_tpu.utils import env

    # in two halves: the name is to be found nowhere in the tree
    gone = "DPRF_PERF" + "_SAMPLE"
    with pytest.raises(KeyError, match="undeclared env knob"):
        env.get_int(gone)
    with open(os.path.join(REPO, "README.md")) as fh:
        text = fh.read()
    table = text[text.index(env.README_BEGIN):text.index(env.README_END)]
    rows = [ln for ln in table.splitlines() if ln.startswith("| `DPRF_")]
    assert len(rows) == len(env.KNOBS) == 53
    assert gone not in text
