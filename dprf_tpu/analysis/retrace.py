"""JAX retrace / host-sync lint over the declared hot paths.

The compile cache (PR 3) and the pipelined loops (PR 5) eliminated
compile cost and device idle -- but neither can see a SILENT
recompile (a jitted step handed a new argument shape every iteration)
or a host-sync stall (``.item()`` mid-sweep serializing the device
stream against the Python interpreter).  Both bug classes live in the
few functions that drive the device per work unit; this analyzer
checks exactly those, declared per module::

    HOT_PATHS = ("Coordinator.run", "worker_loop")

names functions / ``Class.method``s in the declaring module whose
LOOPS are device hot paths.  Stale entries (no such function) are
findings.  Inside any loop of a hot path:

**Host syncs** -- each of these forces the host to wait for the
device stream, turning the pipelined sweep back into lockstep:

  - ``.item()`` / ``.tolist()`` / ``.block_until_ready()`` anywhere
    in the loop (array-only methods: flagged unconditionally);
  - ``bool()`` / ``int()`` / ``float()`` / ``np.asarray()`` /
    ``np.array()`` applied to a DEVICE value -- a name assigned from
    a jitted-entry call (or arithmetic on one) in the same function;
    ``jnp.*`` stays on device and is exempt;
  - an ``if``/``while`` truth-test directly on a device value (the
    implicit ``bool()``); ``x is None``-style comparisons are fine;
  - a call passing a device value into a helper that (transitively,
    over the call graph) performs one of the syncs above -- the
    helper-laundered ``.item()``.

The designed pattern -- accumulate the flag ON DEVICE across the
loop, ``copy_to_host_async()``, read it once per unit AFTER the loop
-- is untouched: only in-loop syncs are findings.

**Silent retraces** -- calls INTO a jitted/AOT entry point inside a
hot loop where:

  - an argument's SHAPE derives from a loop-varying Python value
    (``step(xs[:n])`` with ``n`` reassigned in the loop): every new
    shape is a full retrace+compile mid-sweep.  Pad to a fixed
    ladder, or make the size a static argument with a bounded set of
    values;
  - a loop-varying value lands on a ``static_argnums`` position: one
    retrace per distinct value -- fine for a bounded power-of-two
    ladder, a compile storm for ``range()`` counters; the finding
    asks for the bound.

A "jitted entry" is resolved interprocedurally: a function decorated
``@jax.jit`` (or ``@partial(jax.jit, ...)``); a name or ``self.attr``
assigned from ``jax.jit(...)``; or assigned from a FACTORY whose
return value the call graph resolves to a jit-wrapped closure (the
``make_*_crack_step`` idiom: an inner ``@jax.jit def step`` returned
by the factory).  ``static_argnums`` is read off whichever wrapper
declared it.

**Device taint** flows through plain names AND attribute targets:
``self._flag = self.step(...)`` taints ``self._flag`` exactly like
``flag = self.step(...)`` taints ``flag`` -- a later ``int(self._flag)``
or ``if self._flag:`` in the loop is the same silent sync.

There is no list of helpers that may sync inside a hot loop: a sync
there is a finding, whoever makes it.

Scope: only modules declaring ``HOT_PATHS`` are analyzed, and only
loops inside the named functions -- warmup, decode-after-flag, and
CLI paths sync by design and stay out of the declaration.
"""

from __future__ import annotations

import ast
import re
from typing import Optional

from dprf_tpu.analysis import Finding
from dprf_tpu.analysis import callgraph as cg
from dprf_tpu.analysis.callgraph import (const_str, expr_key, walk_expr,
                                         walk_scope)

NAME = "retrace"
DESCRIPTION = ("silent-recompile and host-sync lint over the declared "
               "HOT_PATHS device loops (jit entries resolved through "
               "the call graph)")
#: declaration tables --explain renders for this check
DECL_TABLES = ("HOT_PATHS",)

#: array-only methods that force a device sync
SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
#: builtins that force a host transfer when fed a device value
HOST_CONVERTERS = {"bool", "int", "float"}
#: host-numpy module aliases whose asarray/array sync a device value
NP_MODULES = {"np", "numpy", "onp"}
NP_SYNC_FUNCS = {"asarray", "array"}

#: helper-chain depth for the transitive sync walk
MAX_SYNC_DEPTH = 16

_PREFILTER_RE = re.compile(r"\bHOT_PATHS\b")


# ---------------------------------------------------------------------------
# jit-entry resolution

def _is_jit_ref(node) -> bool:
    """``jax.jit`` / bare ``jit``."""
    if isinstance(node, ast.Name):
        return node.id == "jit"
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name)
            and node.value.id == "jax")


def _static_from_kwargs(keywords) -> frozenset:
    for kw in keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        if kw.arg == "static_argnames":
            return frozenset()        # name-keyed: positions unknown
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return frozenset((v.value,))
        if isinstance(v, (ast.Tuple, ast.List)):
            out = set()
            for e in v.elts:
                if isinstance(e, ast.Constant) \
                        and isinstance(e.value, int):
                    out.add(e.value)
            return frozenset(out)
    return frozenset()


def _jit_wrapper(node) -> Optional[frozenset]:
    """If ``node`` evaluates to a jit-wrapped callable -- ``jax.jit``
    itself (a decorator ref), ``jax.jit(f, ...)``, or
    ``partial(jax.jit, ...)`` -- the static_argnums set; else None."""
    if _is_jit_ref(node):
        return frozenset()
    if not isinstance(node, ast.Call):
        return None
    if _is_jit_ref(node.func):
        return _static_from_kwargs(node.keywords)
    f = node.func
    is_partial = (isinstance(f, ast.Name) and f.id == "partial") or \
        (isinstance(f, ast.Attribute) and f.attr == "partial")
    if is_partial and node.args and _is_jit_ref(node.args[0]):
        return _static_from_kwargs(node.keywords)
    return None


def _decorated_jit(fn) -> Optional[frozenset]:
    for deco in fn.decorator_list:
        st = _jit_wrapper(deco)
        if st is not None:
            return st
    return None


class _JitResolver:
    """Maps callables to their static_argnums when they are jit
    entries; factory returns resolved through the call graph."""

    def __init__(self, graph):
        self.g = graph
        self._factory_memo: dict = {}

    def factory_returns_jit(self, fi, depth: int = 0) \
            -> Optional[frozenset]:
        """static_argnums if calling ``fi`` yields a jit-wrapped
        callable: fi itself jit-decorated, ``return jax.jit(...)``,
        or returning an inner jit-decorated def / jit-assigned name
        (the ``make_*_step`` factories); one more factory hop via the
        summary's call-assignments."""
        if depth > MAX_SYNC_DEPTH:
            return None
        key = fi.key
        if key in self._factory_memo:
            return self._factory_memo[key]
        self._factory_memo[key] = None       # cycle guard
        st = _decorated_jit(fi.node)
        if st is None:
            st = self._scan_returns(fi, depth)
        self._factory_memo[key] = st
        return st

    def _scan_returns(self, fi, depth) -> Optional[frozenset]:
        inner_jits: dict = {}
        for n in ast.walk(fi.node):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n is not fi.node:
                st = _decorated_jit(n)
                if st is not None:
                    inner_jits[n.name] = st
            elif isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name):
                st = _jit_wrapper(n.value)
                if st is not None:
                    inner_jits[n.targets[0].id] = st
        s = self.g.summary(fi)
        for expr in s.return_exprs:
            st = _jit_wrapper(expr)
            if st is not None:
                return st
            if isinstance(expr, ast.Name):
                st = inner_jits.get(expr.id)
                if st is not None:
                    return st
                callee = s.name_calls.get(expr.id)
                if callee is not None:
                    st = self.factory_returns_jit(callee, depth + 1)
                    if st is not None:
                        return st
        return None

    def call_static(self, call: ast.Call, sc, local_jits: dict,
                    attr_jits: dict) -> Optional[frozenset]:
        """static_argnums if this call dispatches into a jit entry."""
        f = call.func
        if isinstance(f, ast.Name):
            st = local_jits.get(f.id)
            if st is not None:
                return st
        elif isinstance(f, ast.Attribute) \
                and isinstance(f.value, ast.Name) \
                and f.value.id == "self":
            st = attr_jits.get(f.attr)
            if st is not None:
                return st
        callee = self.g.resolve_call(call, sc)
        if callee is not None:
            return _decorated_jit(callee.node)
        return None


def _module_attr_jits(mod, graph, resolver) -> dict:
    """attr name -> static_argnums for every ``self.attr = <jit>``
    assignment in any class of the module (subclasses assign the step
    the base-class hot loop dispatches)."""
    out: dict = {}
    for ci in mod.classes.values():
        for fi in ci.methods.values():
            sc = None
            for st in walk_scope(fi.node):
                if not (isinstance(st, ast.Assign)
                        and len(st.targets) == 1):
                    continue
                t = st.targets[0]
                if not (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    continue
                stat = _jit_wrapper(st.value)
                if stat is None and isinstance(st.value, ast.Call):
                    if sc is None:
                        sc = graph.scope(fi)
                    callee = graph.resolve_call(st.value, sc)
                    if callee is not None:
                        stat = resolver.factory_returns_jit(callee)
                if stat is not None:
                    out.setdefault(t.attr, stat)
    return out


# ---------------------------------------------------------------------------
# transitive sync detection

def _syncs_directly(fn) -> Optional[str]:
    # walk_scope: a sync inside a nested def/lambda the function may
    # never call in-loop is not the function's own sync
    for n in walk_scope(fn):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        if isinstance(f, ast.Attribute):
            if f.attr in SYNC_ATTRS:
                return f".{f.attr}()"
            if f.attr in NP_SYNC_FUNCS and isinstance(f.value, ast.Name) \
                    and f.value.id in NP_MODULES:
                return f"{f.value.id}.{f.attr}()"
    return None


class _SyncWalker:
    def __init__(self, graph):
        self.g = graph
        self._memo: dict = {}

    def syncs(self, fi, depth: int = 0) -> Optional[str]:
        """A sync reason reachable from ``fi`` (its own body, or any
        callee the graph resolves, depth-bounded), else None."""
        if depth > MAX_SYNC_DEPTH:
            return None
        if fi.key in self._memo:
            return self._memo[fi.key]
        self._memo[fi.key] = None            # cycle guard
        why = _syncs_directly(fi.node)
        if why is None:
            s = self.g.summary(fi)
            for _key, (callee, _line) in s.callees.items():
                sub = self.syncs(callee, depth + 1)
                if sub is not None:
                    why = f"{sub} via {callee.qualname}"
                    break
        self._memo[fi.key] = why
        return why


# ---------------------------------------------------------------------------
# hot-path declarations

def _parse_hot_paths(mod) -> tuple:
    """([(qualname, line)], shape findings)."""
    out: list = []
    findings: list = []
    for node in mod.tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "HOT_PATHS"):
            continue
        v = node.value
        if not isinstance(v, (ast.Tuple, ast.List)):
            findings.append(Finding(
                NAME, mod.rel, node.lineno,
                'HOT_PATHS must be a tuple of "func" / '
                '"Class.method" strings'))
            continue
        for e in v.elts:
            s = const_str(e)
            if s is None:
                findings.append(Finding(
                    NAME, mod.rel, node.lineno,
                    "HOT_PATHS entries must be string literals"))
                continue
            out.append((s, node.lineno))
    return out, findings


def _resolve_hot(mod, qualname: str):
    if "." in qualname:
        cls, meth = qualname.split(".", 1)
        ci = mod.classes.get(cls)
        if ci is not None:
            return ci.methods.get(meth)
        return None
    return mod.functions.get(qualname)


# ---------------------------------------------------------------------------
# one hot function's walk

def _target_names(t) -> list:
    if isinstance(t, ast.Name):
        return [t.id]
    if isinstance(t, (ast.Tuple, ast.List)):
        out = []
        for e in t.elts:
            out.extend(_target_names(e))
        return out
    if isinstance(t, ast.Starred):
        return _target_names(t.value)
    return []


def _collect_loop_vars(fn) -> set:
    """Names assigned inside any For/While body of ``fn`` -- the
    loop-varying Python values whose flow into shapes/static args is
    the retrace hazard."""
    out: set = set()

    def stmts(body, in_loop):
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, (ast.For, ast.AsyncFor)):
                out.update(_target_names(st.target))
                stmts(st.body, True)
                stmts(st.orelse, True)
            elif isinstance(st, ast.While):
                stmts(st.body, True)
                stmts(st.orelse, True)
            else:
                if in_loop:
                    if isinstance(st, ast.Assign):
                        for t in st.targets:
                            out.update(_target_names(t))
                    elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
                        out.update(_target_names(st.target))
                for attr in ("body", "orelse", "finalbody", "handlers"):
                    sub = getattr(st, attr, None)
                    if sub:
                        stmts([h for h in sub] if attr != "handlers"
                              else [s for h in sub for s in h.body],
                              in_loop)

    stmts(fn.body, False)
    return out


def _mentions(expr, names: set) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in names:
            return True
    return False


def _varying_slice(expr, loop_vars: set) -> bool:
    """``xs[:n]``-style subscript whose slice bound is loop-varying --
    a new argument shape every iteration."""
    if not (isinstance(expr, ast.Subscript)
            and isinstance(expr.slice, ast.Slice)):
        return False
    for bound in (expr.slice.lower, expr.slice.upper, expr.slice.step):
        if bound is not None and _mentions(bound, loop_vars):
            return True
    return False


class _HotWalker:
    """Order-sensitive walk of one hot function: device-value taint
    flows forward through assignments; findings fire only inside
    loops."""

    def __init__(self, fi, graph, resolver, syncer, local_jits,
                 attr_jits, loop_vars, rel, find):
        self.fi = fi
        self.g = graph
        self.resolver = resolver
        self.syncer = syncer
        self.local_jits = local_jits
        self.attr_jits = attr_jits
        self.loop_vars = loop_vars
        self.rel = rel
        self.find = find
        self.sc = graph.scope(fi)
        #: tainted device values: plain names AND dotted attribute
        #: chains ("self._flag") -- expr_key normalized
        self.taint: set = set()
        #: names assigned from a loop-varying-shape slice in the loop
        self.vshape: set = set()

    def walk(self) -> None:
        self._stmts(self.fi.node.body, False)

    # -- statements -------------------------------------------------------

    def _stmts(self, body, in_loop) -> None:
        for st in body:
            self._stmt(st, in_loop)

    def _stmt(self, st, in_loop) -> None:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            return
        if isinstance(st, ast.Assign):
            self._expr(st.value, in_loop)
            tainted = self._tainted(st.value)
            vshape = in_loop and (_varying_slice(st.value,
                                                 self.loop_vars))
            for t in st.targets:
                names = _target_names(t)
                if not names and isinstance(t, ast.Attribute):
                    # attribute targets carry taint too: ``self._flag
                    # = self.step(...)`` must not launder the device
                    # value out of the name-only set
                    k = expr_key(t)
                    if k is not None:
                        names = [k]
                for name in names:
                    (self.taint.add if tainted
                     else self.taint.discard)(name)
                    (self.vshape.add if vshape
                     else self.vshape.discard)(name)
            return
        if isinstance(st, ast.AugAssign):
            self._expr(st.value, in_loop)
            return
        if isinstance(st, (ast.For, ast.AsyncFor)):
            self._expr(st.iter, in_loop)
            self._stmts(st.body, True)
            self._stmts(st.orelse, True)
            return
        if isinstance(st, ast.While):
            self._truth_test(st.test, True)
            self._expr(st.test, True)
            self._stmts(st.body, True)
            self._stmts(st.orelse, True)
            return
        if isinstance(st, ast.If):
            self._truth_test(st.test, in_loop)
            self._expr(st.test, in_loop)
            self._stmts(st.body, in_loop)
            self._stmts(st.orelse, in_loop)
            return
        if isinstance(st, ast.Try):
            self._stmts(st.body, in_loop)
            for h in st.handlers:
                self._stmts(h.body, in_loop)
            self._stmts(st.orelse, in_loop)
            self._stmts(st.finalbody, in_loop)
            return
        if isinstance(st, (ast.With, ast.AsyncWith)):
            for item in st.items:
                self._expr(item.context_expr, in_loop)
            self._stmts(st.body, in_loop)
            return
        for child in ast.iter_child_nodes(st):
            if isinstance(child, ast.expr):
                self._expr(child, in_loop)

    def _tainted(self, expr) -> bool:
        for n in ast.walk(expr):
            if isinstance(n, ast.Name) and n.id in self.taint:
                return True
            if isinstance(n, ast.Attribute):
                k = expr_key(n)
                if k is not None and k in self.taint:
                    return True
            if isinstance(n, ast.Call) and self.resolver.call_static(
                    n, self.sc, self.local_jits,
                    self.attr_jits) is not None:
                return True
        return False

    def _truth_test(self, test, in_loop) -> None:
        """``if x:`` / ``while x:`` on a device value is an implicit
        bool() -- a sync.  Only direct names (and ``not x`` /
        ``x and y`` over them) fire; comparisons are value tests the
        author wrote deliberately."""
        if not in_loop:
            return
        nodes = [test]
        while nodes:
            n = nodes.pop()
            name = None
            if isinstance(n, ast.Name) and n.id in self.taint:
                name = n.id
            elif isinstance(n, ast.Attribute):
                k = expr_key(n)
                if k is not None and k in self.taint:
                    name = k
            if name is not None:
                self.find(self.rel, n.lineno,
                          f"implicit bool() on device value {name!r} "
                          "inside the hot loop -- a host sync every "
                          "iteration; accumulate the flag on device "
                          "and read it once after the loop")
                return
            if isinstance(n, ast.UnaryOp) and isinstance(n.op, ast.Not):
                nodes.append(n.operand)
            elif isinstance(n, ast.BoolOp):
                nodes.extend(n.values)

    # -- expressions ------------------------------------------------------

    def _expr(self, expr, in_loop) -> None:
        # walk_expr prunes nested def/lambda subtrees: a lambda built
        # in the loop but invoked later is not an in-loop sync
        for n in walk_expr(expr):
            if not isinstance(n, ast.Call):
                continue
            self._call(n, in_loop)

    def _call(self, call: ast.Call, in_loop) -> None:
        f = call.func
        if isinstance(f, ast.Attribute):
            if f.attr in SYNC_ATTRS and in_loop:
                self.find(self.rel, call.lineno,
                          f".{f.attr}() inside the hot loop forces a "
                          "device sync every iteration -- hoist it "
                          "after the loop (accumulate on device)")
                return
            if f.attr in NP_SYNC_FUNCS and in_loop \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id in NP_MODULES \
                    and call.args and self._tainted(call.args[0]):
                self.find(self.rel, call.lineno,
                          f"{f.value.id}.{f.attr}() on a device value "
                          "inside the hot loop is a host transfer "
                          "every iteration -- decode after the loop, "
                          "behind the unit flag")
                return
        elif isinstance(f, ast.Name):
            if f.id in HOST_CONVERTERS and in_loop and call.args \
                    and self._tainted(call.args[0]):
                self.find(self.rel, call.lineno,
                          f"{f.id}() on a device value inside the hot "
                          "loop is a host sync every iteration -- "
                          "keep the value on device (jnp) or read it "
                          "once after the loop")
                return
        static = self.resolver.call_static(call, self.sc,
                                           self.local_jits,
                                           self.attr_jits)
        if static is not None:
            if in_loop:
                self._jit_args(call, static)
            return
        if not in_loop:
            return
        callee = self.g.resolve_call(call, self.sc)
        if callee is None or callee.key == self.fi.key:
            return

        def _arg_tainted(a) -> bool:
            if isinstance(a, ast.Name):
                return a.id in self.taint
            if isinstance(a, ast.Attribute):
                k = expr_key(a)
                return k is not None and k in self.taint
            return False

        if any(_arg_tainted(a) for a in call.args):
            why = self.syncer.syncs(callee)
            if why is not None:
                self.find(self.rel, call.lineno,
                          f"{callee.qualname}() syncs the device "
                          f"value it is passed ({why}) inside the "
                          "hot loop -- resolve after the loop, or "
                          "keep the helper device-side")

    def _jit_args(self, call: ast.Call, static: frozenset) -> None:
        for i, a in enumerate(call.args):
            if _varying_slice(a, self.loop_vars) \
                    or (isinstance(a, ast.Name) and a.id in self.vshape):
                self.find(self.rel, call.lineno,
                          "jitted call argument has a loop-varying "
                          "shape -- a silent retrace+compile every "
                          "iteration; pad to a fixed-size ladder or "
                          "hoist the varying size to static_argnums "
                          "with a bounded value set")
                continue
            if i in static and _mentions(a, self.loop_vars):
                self.find(self.rel, call.lineno,
                          f"loop-varying value on static_argnums "
                          f"position {i} of a jitted call -- one "
                          "retrace per distinct value; bound the "
                          "ladder (powers of two) or make the "
                          "argument traced")


# ---------------------------------------------------------------------------

def run(ctx) -> list:
    findings: list = []

    def find(rel, line, msg):
        findings.append(Finding(NAME, rel, line, msg))

    graph = cg.get(ctx)
    resolver = _JitResolver(graph)
    syncer = _SyncWalker(graph)
    for path in ctx.package_files():
        try:
            src = ctx.source(path)
        except OSError:
            continue
        if not _PREFILTER_RE.search(src):
            continue
        mod = graph.load_file(path)
        if mod is None:
            continue
        rel = ctx.rel(path)
        hot, shape_findings = _parse_hot_paths(mod)
        findings.extend(shape_findings)
        if not hot:
            continue
        attr_jits = _module_attr_jits(mod, graph, resolver)
        for qualname, dline in hot:
            fi = _resolve_hot(mod, qualname)
            if fi is None:
                find(rel, dline,
                     f"HOT_PATHS declares unknown function "
                     f"{qualname!r} -- stale declaration")
                continue
            local_jits: dict = {}
            sc = graph.scope(fi)
            for st in walk_scope(fi.node):
                if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                        and isinstance(st.targets[0], ast.Name):
                    stat = _jit_wrapper(st.value)
                    if stat is None and isinstance(st.value, ast.Call):
                        callee = graph.resolve_call(st.value, sc)
                        if callee is not None:
                            stat = resolver.factory_returns_jit(callee)
                    if stat is not None:
                        local_jits[st.targets[0].id] = stat
            loop_vars = _collect_loop_vars(fi.node)
            _HotWalker(fi, graph, resolver, syncer, local_jits,
                       attr_jits, loop_vars, rel, find).walk()
    return findings
