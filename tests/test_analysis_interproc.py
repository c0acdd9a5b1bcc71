"""Interprocedural `dprf check` tests (ISSUE 7): the call-graph core,
the locks/protocol analyzers following facts through helpers, and the
two new analyzers (threads, retrace) -- each against planted-violation
fixtures caught at the planted line, with clean twins pinning the
no-false-positive behavior.

Same fixture idiom as test_analysis.py: trees under tmp_path with the
shape the AnalysisContext walks; nothing in a fixture is imported.
"""

import os
import textwrap

import pytest

from dprf_tpu import analysis
from dprf_tpu.analysis import callgraph as cg

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


def graph_for(root):
    ctx = analysis.AnalysisContext(root)
    return cg.get(ctx), ctx


# ---------------------------------------------------------------------------
# call-graph core

def test_callgraph_resolves_cross_module_function(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/a.py": """\
            from dprf_tpu.b import helper

            def entry():
                return helper(1)
        """,
        "dprf_tpu/b.py": """\
            def helper(x):
                return x
        """,
    })
    g, ctx = graph_for(root)
    mod = g.load_file(os.path.join(root, "dprf_tpu", "a.py"))
    s = g.summary(mod.functions["entry"])
    callees = [fi.qualname for fi, _ in s.callees.values()]
    assert callees == ["helper"]


def test_callgraph_resolves_method_via_annotation(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/w.py": """\
            class Worker:
                def go(self):
                    return 1

            def drive(w: Worker):
                return w.go()
        """,
    })
    g, ctx = graph_for(root)
    mod = g.load_file(os.path.join(root, "dprf_tpu", "w.py"))
    s = g.summary(mod.functions["drive"])
    assert [fi.qualname for fi, _ in s.callees.values()] == ["Worker.go"]


def test_callgraph_factory_return_annotation_types_result(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/w.py": """\
            class Worker:
                def go(self):
                    return 1

            def make() -> Worker:
                return Worker()

            def drive():
                w = make()
                return w.go()
        """,
    })
    g, ctx = graph_for(root)
    mod = g.load_file(os.path.join(root, "dprf_tpu", "w.py"))
    s = g.summary(mod.functions["drive"])
    names = {fi.qualname for fi, _ in s.callees.values()}
    assert "Worker.go" in names


def test_callgraph_closure_blocking_through_chain(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/c.py": """\
            import time

            def a():
                b()

            def b():
                c()

            def c():
                time.sleep(1)
        """,
    })
    g, ctx = graph_for(root)
    mod = g.load_file(os.path.join(root, "dprf_tpu", "c.py"))
    cl = g.closure(mod.functions["a"])
    assert any(reason == "time.sleep" for reason, _via, _ln in cl.blocking)
    # the via-qualname names the function holding the blocking call
    assert any(via == "c" for _r, via, _ln in cl.blocking)


def test_callgraph_closure_cycle_terminates(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/c.py": """\
            import time

            def ping(n):
                time.sleep(1)
                pong(n)

            def pong(n):
                ping(n)
        """,
    })
    g, ctx = graph_for(root)
    mod = g.load_file(os.path.join(root, "dprf_tpu", "c.py"))
    cl = g.closure(mod.functions["pong"])
    assert any(r == "time.sleep" for r, _v, _ln in cl.blocking)


def test_callgraph_param_key_reads_summarized(tmp_path):
    root = make_repo(tmp_path, {
        "dprf_tpu/h.py": """\
            def handle(msg):
                a = msg["worker_id"]
                b = msg.get("ahead")
                if "trace" in msg:
                    pass
                msg["seen"] = 1
                return a, b
        """,
    })
    g, ctx = graph_for(root)
    mod = g.load_file(os.path.join(root, "dprf_tpu", "h.py"))
    s = g.summary(mod.functions["handle"])
    assert set(s.param_reads["msg"]) == {"worker_id", "ahead", "trace"}
    assert set(s.param_writes["msg"]) == {"seen"}


# ---------------------------------------------------------------------------
# locks: interprocedural upgrades

LOCKED_STATE = """\
    import threading
    import time

    GUARDED_BY = {
        "State": {"lock": ("count",)},
    }

    class State:
        def __init__(self):
            self.lock = threading.Lock()
            self.count = 0
"""


def test_locks_blocking_through_helper_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/state.py": LOCKED_STATE + """\

        def bump(self):
            with self.lock:
                self.count += 1
                self._log()

        def _log(self):
            time.sleep(0.1)
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1
    assert "reached via State._log()" in f[0].message


def test_locks_blocking_through_module_function_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/state.py": LOCKED_STATE + """\

        def bump(self):
            with self.lock:
                self.count += 1
                pause()

    def pause():
        time.sleep(0.1)
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1 and "reached via pause()" in f[0].message, \
        [x.message for x in f]


def test_locks_helper_chain_clean_when_not_blocking(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/state.py": LOCKED_STATE + """\

        def bump(self):
            with self.lock:
                self.count += 1
                self._note()

        def _note(self):
            return self.count

        _note._holds_lock = "lock"
"""})
    assert bad(check(root, "locks")) == []


def test_locks_module_global_unlocked_read_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/cachestate.py": """\
        import threading

        GUARDED_BY = {"<module>": {"_lock": ("_state",)}}

        _lock = threading.Lock()
        _state = {"dir": None}

        def bad_read():
            return _state["dir"]

        def good_read():
            with _lock:
                return _state["dir"]
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1
    assert "module global '_state'" in f[0].message
    assert f[0].line == 9


def test_locks_rlock_reentrant_not_a_deadlock(tmp_path):
    base = """\
        import threading

        GUARDED_BY = {"R": {"lock": ("v",)}}

        class R:
            def __init__(self):
                self.lock = threading.{KIND}()
                self.v = 0

            def outer(self):
                with self.lock:
                    self.v += 1
                    self.inner()

            def inner(self):
                with self.lock:
                    self.v += 2
    """
    root = make_repo(tmp_path, {
        "dprf_tpu/r.py": base.replace("{KIND}", "RLock")})
    assert bad(check(root, "locks")) == []
    root2 = make_repo(tmp_path / "plain", {
        "dprf_tpu/r.py": base.replace("{KIND}", "Lock")})
    f = bad(check(root2, "locks"))
    assert len(f) == 1 and "re-acquiring" in f[0].message, \
        [x.message for x in f]
    assert "via R.inner()" in f[0].message


# ---------------------------------------------------------------------------
# protocol: keys followed through helper functions

def test_protocol_helper_laundered_request_key_caught(tmp_path):
    # the handler hands msg to a helper; the helper reads a key no
    # client ever sends -- the PR 6 blind spot
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_lease(self, msg):
                return handle(msg)

        def handle(msg):
            wid = msg["worker_id"]
            n = msg.get("ahead")
            return {"unit": wid, "n": n}

        class Client:
            def call(self, op, **kw):
                return {}

            def go(self):
                resp = self.call("lease", worker_id=3)
                return resp["unit"]
"""})
    msgs = [x.message for x in bad(check(root, "protocol"))]
    assert len(msgs) == 1, msgs
    assert "reads request key 'ahead'" in msgs[0]


def test_protocol_helper_built_response_keys_clean(tmp_path):
    # response keys built by a helper the handler returns are visible
    # to the client-side read check
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_lease(self, msg):
                return build(msg["worker_id"])

        def build(wid):
            return {"unit": wid, "trace": None}

        class Client:
            def call(self, op, **kw):
                return {}

            def go(self):
                resp = self.call("lease", worker_id=3)
                return resp["unit"], resp.get("trace")
"""})
    assert bad(check(root, "protocol")) == []


def test_protocol_client_helper_response_read_caught(tmp_path):
    # the client hands the response to a helper that reads a key the
    # handler never returns
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_lease(self, msg):
                wid = msg["worker_id"]
                return {"unit": wid}

        def pick(resp):
            return resp["unit"], resp["missing"]

        class Client:
            def call(self, op, **kw):
                return {}

            def go(self):
                resp = self.call("lease", worker_id=3)
                return pick(resp)
"""})
    msgs = [x.message for x in bad(check(root, "protocol"))]
    assert len(msgs) == 1, msgs
    assert "'missing'" in msgs[0]


# ---------------------------------------------------------------------------
# *args/**kwargs forwarding (ISSUE 8 satellite: the PR 7 gap --
# positional names only -- closed by callgraph slots)

def test_callgraph_forwarded_slots_map_star_and_keyword(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/f.py": """\
        def wrapper(*args, **kwargs):
            return inner(*args, **kwargs)

        def inner(msg, extra=None):
            return msg
"""})
    g, ctx = graph_for(root)
    mod = g.load_file(os.path.join(root, "dprf_tpu", "f.py"))
    wrapper = mod.functions["wrapper"]
    inner = mod.functions["inner"]
    # a positional arg past wrapper's (empty) param list lands in *args
    assert cg.slot_at(wrapper, 0) == ("*", "args", 0)
    # a keyword with no matching param lands in **kwargs
    assert cg.slot_for_keyword(wrapper, "msg") == ("**", "kwargs",
                                                   "msg")
    s = g.summary(wrapper)
    (callee, argspec, kwspec, _line), = s.calls
    assert callee is inner
    # *args element 0 forwarded through wrapper reaches inner's "msg"
    assert cg.forwarded_slots(callee, argspec, kwspec,
                              ("*", "args", 0)) == ["msg"]
    # **kwargs entry "extra" reaches inner's keyword param
    assert cg.forwarded_slots(callee, argspec, kwspec,
                              ("**", "kwargs", "extra")) == ["extra"]
    # an unknown kwargs entry resolves to nothing, not a guess
    assert cg.forwarded_slots(callee, argspec, kwspec,
                              ("**", "kwargs", "nope")) == []


def test_protocol_star_forwarding_wrapper_key_caught(tmp_path):
    # the handler launders msg through a *args/**kwargs wrapper; the
    # eventual reader's undeclared key must still surface
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_lease(self, msg):
                return fwd(msg)

        def fwd(*args, **kwargs):
            return handle(*args, **kwargs)

        def handle(msg):
            return {"unit": msg["worker_id"], "n": msg.get("ahead")}

        class Client:
            def call(self, op, **kw):
                return {}

            def go(self):
                resp = self.call("lease", worker_id=3)
                return resp["unit"]
"""})
    msgs = [x.message for x in bad(check(root, "protocol"))]
    assert len(msgs) == 1, msgs
    assert "reads request key 'ahead'" in msgs[0]


def test_protocol_keyword_passed_dict_followed(tmp_path):
    # msg handed on BY KEYWORD (helper(req=msg)) -- dropped entirely
    # by the positional-names-only dataflow
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_lease(self, msg):
                return handle(req=msg)

        def handle(req=None):
            return {"unit": req["worker_id"], "n": req["ahead"]}

        class Client:
            def call(self, op, **kw):
                return {}

            def go(self):
                resp = self.call("lease", worker_id=3)
                return resp["unit"]
"""})
    msgs = [x.message for x in bad(check(root, "protocol"))]
    assert len(msgs) == 1, msgs
    assert "reads request key 'ahead'" in msgs[0]


def test_protocol_star_forwarding_clean_when_keys_sent(tmp_path):
    # clean twin: every key the forwarded reader touches is sent
    root = make_repo(tmp_path, {"dprf_tpu/rpc.py": """\
        class Server:
            def op_lease(self, msg):
                return fwd(msg)

        def fwd(*args, **kwargs):
            return handle(*args, **kwargs)

        def handle(msg):
            return {"unit": msg["worker_id"], "n": msg.get("ahead")}

        class Client:
            def call(self, op, **kw):
                return {}

            def go(self):
                resp = self.call("lease", worker_id=3, ahead=2)
                return resp["unit"]
"""})
    assert bad(check(root, "protocol")) == []


def test_locks_blocking_through_star_forwarding_wrapper_caught(
        tmp_path):
    # blocking facts survive a *args/**kwargs forwarding wrapper
    root = make_repo(tmp_path, {"dprf_tpu/state.py": LOCKED_STATE + """\

        def bump(self):
            with self.lock:
                self.count += 1
                self._fwd(1, 2)

        def _fwd(self, *args, **kwargs):
            return self._slow(*args, **kwargs)

        def _slow(self, a, b):
            time.sleep(a + b)
"""})
    f = bad(check(root, "locks"))
    assert len(f) == 1, [x.message for x in f]
    assert "blocking" in f[0].message


# ---------------------------------------------------------------------------
# threads: lifecycle discipline

def test_threads_unjoined_local_thread_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/t.py": """\
        import threading

        def spawn(fn):
            t = threading.Thread(target=fn)
            t.start()
"""})
    f = bad(check(root, "threads"))
    assert len(f) == 1 and "never joined in this function" in f[0].message
    assert f[0].line == 4


def test_threads_joined_or_daemon_clean(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/t.py": """\
        import threading

        def run_sync(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join()

        def run_background(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()

        def run_late_daemon(fn):
            t = threading.Thread(target=fn)
            t.daemon = True
            t.start()

        def handoff(fn):
            t = threading.Thread(target=fn)
            return t
"""})
    assert bad(check(root, "threads")) == []


def test_threads_unbound_thread_start_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/t.py": """\
        import threading

        def fire(fn):
            threading.Thread(target=fn).start()
"""})
    f = bad(check(root, "threads"))
    assert len(f) == 1 and "unbound non-daemon Thread" in f[0].message


def test_threads_attr_thread_unjoined_caught_and_join_clean(tmp_path):
    planted = """\
        import threading

        class S:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                pass
    """
    root = make_repo(tmp_path, {"dprf_tpu/s.py": planted})
    f = bad(check(root, "threads"))
    assert len(f) == 1 and "never joined by any method" in f[0].message
    clean = planted + """\

            def stop(self):
                self._t.join()
    """
    root2 = make_repo(tmp_path / "clean", {"dprf_tpu/s.py": clean})
    assert bad(check(root2, "threads")) == []


def test_threads_resource_closed_on_one_path_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/r.py": """\
        import socket

        def fetch(host, want):
            s = socket.create_connection((host, 1))
            data = s.recv(1)
            if want:
                s.close()
            return data
"""})
    f = bad(check(root, "threads"))
    assert len(f) == 1 and "only some paths" in f[0].message


def test_threads_resource_finally_close_clean(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/r.py": """\
        import socket

        def fetch(host):
            s = socket.create_connection((host, 1))
            try:
                return s.recv(1)
            finally:
                s.close()

        def read(path):
            with open(path) as fh:
                return fh.read()

        def chain(path):
            open(path).close()
"""})
    assert bad(check(root, "threads")) == []


def test_threads_resource_never_released_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/r.py": """\
        def leak(path):
            fh = open(path)
            return fh.read()
"""})
    f = bad(check(root, "threads"))
    assert len(f) == 1 and "never released here" in f[0].message


def test_threads_resource_passed_straight_on_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/r.py": """\
        import json

        def load(path):
            return json.load(open(path))
"""})
    f = bad(check(root, "threads"))
    assert len(f) == 1 and "passed straight on" in f[0].message


def test_threads_self_resource_requires_releases_entry(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/c.py": """\
        class Journal:
            def __init__(self, path):
                self._fh = open(path, "a")
"""})
    f = bad(check(root, "threads"))
    assert len(f) == 1
    assert "not declared in a module-level RELEASES" in f[0].message


def test_threads_releases_declared_and_released_clean(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/c.py": """\
        RELEASES = {"Journal": {"_fh": "close"}}

        class Journal:
            def __init__(self, path):
                self._fh = open(path, "a")

            def close(self):
                self._fh.close()
"""})
    assert bad(check(root, "threads")) == []


def test_threads_stale_releases_declarations_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/c.py": """\
        RELEASES = {
            "Ghost": {"_fh": "close"},
            "NoMeth": {"_fh": "shutdown"},
            "NoClose": {"_fh": "close"},
        }

        class NoMeth:
            def __init__(self, path):
                self._fh = open(path)

        class NoClose:
            def __init__(self, path):
                self._fh = open(path)

            def close(self):
                pass
"""})
    msgs = [x.message for x in bad(check(root, "threads"))]
    assert len(msgs) == 3, msgs
    assert any("unknown class 'Ghost'" in m for m in msgs)
    assert any("no such method" in m for m in msgs)
    assert any("never closes it" in m for m in msgs)


def test_threads_condition_wait_without_while_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/q.py": """\
        import threading

        class Q:
            def __init__(self):
                self.cv = threading.Condition()
                self.items = []

            def get(self):
                with self.cv:
                    if not self.items:
                        self.cv.wait()
                    return self.items.pop()
"""})
    f = bad(check(root, "threads"))
    assert len(f) == 1 and "outside a `while`" in f[0].message


def test_threads_condition_unheld_wait_and_notify_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/q.py": """\
        import threading

        class Q:
            def __init__(self):
                self.cv = threading.Condition()
                self.items = []

            def get(self):
                while not self.items:
                    self.cv.wait()

            def put(self, x):
                self.items.append(x)
                self.cv.notify()
"""})
    msgs = [x.message for x in bad(check(root, "threads"))]
    assert len(msgs) == 2, msgs
    assert all("without holding it" in m for m in msgs)


def test_threads_condition_disciplined_clean(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/q.py": """\
        import threading

        class Q:
            def __init__(self):
                self.cv = threading.Condition()
                self.items = []

            def get(self):
                with self.cv:
                    while not self.items:
                        self.cv.wait()
                    return self.items.pop()

            def get_pred(self):
                with self.cv:
                    self.cv.wait_for(lambda: self.items)
                    return self.items.pop()

            def put(self, x):
                with self.cv:
                    self.items.append(x)
                    self.cv.notify()

            def _drain(self):
                while not self.items:
                    self.cv.wait()

            _drain._holds_lock = "cv"
"""})
    assert bad(check(root, "threads")) == []


def test_threads_lambda_body_is_not_this_functions_code(tmp_path):
    # a lambda CONSTRUCTING a thread hands it to its caller -- the
    # enclosing function must not be charged with the leak (ast.walk
    # without subtree pruning used to flag this)
    root = make_repo(tmp_path, {"dprf_tpu/t.py": """\
        import threading

        def factory():
            make = lambda: threading.Thread(target=print)
            return make
"""})
    assert bad(check(root, "threads")) == []


def test_threads_event_wait_is_not_condition_wait(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/e.py": """\
        import threading

        class W:
            def __init__(self):
                self.done = threading.Event()

            def block(self):
                self.done.wait()
"""})
    assert bad(check(root, "threads")) == []


# ---------------------------------------------------------------------------
# retrace: host syncs + silent recompiles in declared hot paths

RETRACE_HEAD = """\
    import jax
    import numpy as np

    @jax.jit
    def step(xs):
        return xs
"""


def test_retrace_item_in_hot_loop_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def sweep(units):
        out = 0
        for u in units:
            r = step(u)
            out += r.item()
        return out
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and ".item() inside the hot loop" in f[0].message


def test_retrace_sync_after_loop_clean(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def sweep(units):
        flag = None
        for u in units:
            r = step(u)
            flag = r if flag is None else flag + r
        return flag.item()
"""})
    assert bad(check(root, "retrace")) == []


def test_retrace_varying_shape_into_jit_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def sweep(xs):
        n = 1
        r = None
        for _ in range(8):
            n = n + 1
            r = step(xs[:n])
        return r
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "loop-varying shape" in f[0].message


def test_retrace_fixed_shape_jit_clean(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def sweep(xs, stride):
        r = None
        for i in range(8):
            r = step(xs[:stride])
        return r
"""})
    assert bad(check(root, "retrace")) == []


def test_retrace_loop_varying_static_argnum_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": """\
        import jax

        def body(xs, n):
            return xs

        HOT_PATHS = ("sweep",)

        def sweep(xs):
            f = jax.jit(body, static_argnums=(1,))
            for n in range(8):
                r = f(xs, n)
            return r
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "static_argnums position 1" in f[0].message


def test_retrace_implicit_bool_on_device_value_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def sweep(units):
        for u in units:
            r = step(u)
            if r:
                break
        return r
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "implicit bool()" in f[0].message


def test_retrace_np_asarray_on_device_value_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def sweep(units):
        out = []
        for u in units:
            r = step(u)
            out.append(np.asarray(r))
        return out
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "np.asarray()" in f[0].message


def test_retrace_np_asarray_on_host_value_clean(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def sweep(units, gen):
        r = None
        for u in units:
            base = np.asarray(gen.digits(u))
            r = step(base)
        return r
"""})
    assert bad(check(root, "retrace")) == []


def test_retrace_helper_laundered_sync_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def fetch(x):
        return np.asarray(x)

    def sweep(units):
        out = []
        for u in units:
            r = step(u)
            out.append(fetch(r))
        return out
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1
    assert "fetch() syncs the device value" in f[0].message


def test_retrace_factory_assigned_step_resolved(tmp_path):
    # the make_*_step idiom: a factory returning an inner @jax.jit
    # closure, stored on self in __init__, dispatched in the hot loop
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": """\
        import jax

        def make_step():
            @jax.jit
            def step(xs):
                return xs
            return step

        HOT_PATHS = ("W.submit",)

        class W:
            def __init__(self):
                self.step = make_step()

            def submit(self, xs):
                n = 0
                r = None
                for _ in range(4):
                    n = n + 1
                    r = self.step(xs[:n])
                return r
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "loop-varying shape" in f[0].message


def test_retrace_stale_hot_path_declaration_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": """\
        HOT_PATHS = ("nope",)
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "stale declaration" in f[0].message


def test_retrace_lambda_deferring_sync_clean(tmp_path):
    # a lambda built in the loop but invoked after it is deferred
    # work, not an in-loop sync; same for a helper whose only "sync"
    # sits in a nested def it never runs
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)

    def fetch_later(x):
        def inner():
            return np.asarray(x)
        return inner

    def sweep(units):
        out = []
        for u in units:
            r = step(u)
            out.append(lambda v=r: v.item())
            out.append(fetch_later(r))
        return [f() for f in out]
"""})
    assert bad(check(root, "retrace")) == []


def test_retrace_undeclared_module_not_scanned(tmp_path):
    # no HOT_PATHS -> the module's loops are out of scope by design
    root = make_repo(tmp_path, {"dprf_tpu/cold.py": RETRACE_HEAD + """\

    def warmup(units):
        for u in units:
            step(u).item()
"""})
    assert bad(check(root, "retrace")) == []


# ---------------------------------------------------------------------------
# framework: --explain

def test_explain_renders_rules_and_tables(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/c.py": """\
        RELEASES = {"Journal": {"_fh": "close"}}

        class Journal:
            def __init__(self, path):
                self._fh = open(path, "a")

            def close(self):
                self._fh.close()
"""})
    text = analysis.explain(root, "threads")
    assert "RELEASES" in text
    assert "dprf_tpu/c.py:1" in text
    assert '"Journal": {"_fh": "close"}' in text
    with pytest.raises(ValueError):
        analysis.explain(root, "nope")


def test_explain_real_repo_declares_all_tables():
    # the runtime's live declarations render for each table-backed
    # check -- the reference future suppression-writers read
    for name, needle in (("locks", "GUARDED_BY"),
                         ("threads", "RELEASES"),
                         ("retrace", "HOT_PATHS")):
        text = analysis.explain(REPO, name)
        assert "Declarations in this repo:" in text
        assert needle in text


# ---------------------------------------------------------------------------
# retrace: attribute-target taint (ISSUE 9 satellite) -- the device
# value must not launder out of the taint set through `self.attr = ...`

def test_retrace_attribute_target_taint_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": """\
        import jax

        def ident(x):
            return x

        HOT_PATHS = ("W.sweep",)

        class W:
            def __init__(self):
                self.step = jax.jit(ident)

            def sweep(self, units):
                out = 0
                for u in units:
                    self._flag = self.step(u)
                    out += int(self._flag)
                return out
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "int() on a device value" in f[0].message


def test_retrace_attribute_flag_read_after_loop_clean(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": """\
        import jax

        def ident(x):
            return x

        HOT_PATHS = ("W.sweep",)

        class W:
            def __init__(self):
                self.step = jax.jit(ident)
                self._flag = None

            def sweep(self, units):
                for u in units:
                    f = self.step(u)
                    self._flag = f if self._flag is None \
                        else self._flag + f
                return int(self._flag)
"""})
    assert bad(check(root, "retrace")) == []


def test_retrace_attribute_truth_test_caught(tmp_path):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": """\
        import jax

        def ident(x):
            return x

        HOT_PATHS = ("W.sweep",)

        class W:
            def __init__(self):
                self.step = jax.jit(ident)

            def sweep(self, units):
                hits = []
                for u in units:
                    self._flag = self.step(u)
                    if self._flag:
                        hits.append(u)
                return hits
"""})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "implicit bool()" in f[0].message


# ---------------------------------------------------------------------------
# retrace: a helper that syncs inside a hot loop is a finding, and no
# table says who may

#: the table's name, in two halves: it is to be found nowhere in the tree
GONE_TABLE = "PERF" + "_PROBE"


@pytest.mark.parametrize("table", ["", GONE_TABLE + ' = ("grab",)'],
                         ids=["no-table", "exemption-table"])
def test_retrace_undeclared_probe_helper_caught(tmp_path, table):
    root = make_repo(tmp_path, {"dprf_tpu/hot.py": RETRACE_HEAD + """\

    HOT_PATHS = ("sweep",)
    %s

    def grab(r):
        return r.item()

    def sweep(units):
        out = 0
        for u in units:
            r = step(u)
            out += grab(r)
        return out
""" % table})
    f = bad(check(root, "retrace"))
    assert len(f) == 1 and "syncs the device value" in f[0].message


def test_retrace_real_repo_is_clean_under_its_one_table():
    from dprf_tpu.analysis import retrace
    assert retrace.DECL_TABLES == ("HOT_PATHS",)
    assert bad(check(REPO, "retrace")) == []
    assert GONE_TABLE not in analysis.explain(REPO, "retrace")
