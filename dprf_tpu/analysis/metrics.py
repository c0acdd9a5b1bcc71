"""Metric/span declaration hygiene (absorbed from
tools/check_metrics.py).

The PR 3 bug this makes impossible: ``dprf_compile_seconds`` was
declared with ``("engine",)`` labels in two call sites and with
``("engine", "cache")`` in a third -- the registry's get-or-create
semantics turn a second declaration site into either silent drift or
a runtime ValueError, depending on which import runs first.  Rules:

  1. every ``dprf_*`` metric name passed as a literal to
     ``.counter(`` / ``.gauge(`` / ``.histogram(`` appears at EXACTLY
     ONE call site across the package;
  2. every span-name literal passed to a ``.record("...")`` call is a
     member of ``telemetry/trace.py``'s ``SPAN_NAMES`` tuple, which
     holds no duplicates; likewise every ``.station("...")`` literal
     and the ``STATIONS`` tuple beside it;
  3. every metric an ALERT RULE references (ISSUE 10) -- the
     ``DEFAULT_RULES`` literal pack in ``telemetry/alerts.py`` and
     any ``DPRF_ALERT_RULES``-style fixture file under
     ``tests/fixtures/alert_rules*.json`` -- names a declared
     ``dprf_*`` metric.  A renamed metric would otherwise silently
     disarm its rule: the alert engine evaluates "condition false"
     against a metric that no longer exists, forever.
  4. every ``jax.profiler`` trace call (``.start_trace(`` /
     ``.stop_trace(`` / ``jax.profiler.trace(``) lives in
     ``telemetry/profiler.py`` (ISSUE 15): jax allows ONE active
     trace per process, so every starter must go through
     ProfileCapture's single-flight guard -- a raw call elsewhere
     is exactly the ``--profile``-vs-``DPRF_JAX_PROFILE`` collision
     the guard exists to prevent.  One-declaration-site discipline,
     same as metrics and spans.
"""

from __future__ import annotations

import ast
import os
import re

from dprf_tpu.analysis import Finding

NAME = "metrics"
DESCRIPTION = ("every dprf_* metric declared at one site; every span "
               "literal is in SPAN_NAMES and every station literal in "
               "STATIONS; every alert rule "
               "references a declared metric; jax.profiler calls "
               "only in telemetry/profiler.py")

METRIC_METHODS = {"counter", "gauge", "histogram"}
TRACE_REL = os.path.join("telemetry", "trace.py")
ALERTS_REL = os.path.join("telemetry", "alerts.py")
PROFILER_REL = os.path.join("telemetry", "profiler.py")

#: profiler-trace attribute calls that must not exist outside the
#: single-flight owner (rule 4): start/stop are unambiguous; a bare
#: ``.trace(`` only counts when called on something named "profiler"
PROFILER_METHODS = {"start_trace", "stop_trace"}

#: parse prefilter: a file with no metric/record call text cannot
#: contribute a declaration or span use
_RELEVANT_RE = re.compile(
    r"\.(?:counter|gauge|histogram|record|station)\s*\(")
_PROFILER_RE = re.compile(r"\.(?:start_trace|stop_trace|trace)\s*\(")


def _literal(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


#: literal-taking recorder method -> (what its literal names, the
#: tuple in telemetry/trace.py that declares those names)
DECLARED_IN = {"record": ("span", "SPAN_NAMES"),
               "station": ("station", "STATIONS")}


def _scan_file(idx):
    """(metric declarations, uses of declared names): the second as
    (name, lineno, the recorder method called with it)."""
    decls, span_uses = [], []
    for node in idx.calls:
        if not isinstance(node.func, ast.Attribute):
            continue
        first = _literal(node.args[0]) if node.args else None
        if (node.func.attr in METRIC_METHODS and first
                and first.startswith("dprf_")):
            decls.append((first, node.lineno))
        elif node.func.attr in DECLARED_IN and first is not None:
            span_uses.append((first, node.lineno, node.func.attr))
    return decls, span_uses


def _alert_rule_refs(idx):
    """(rule name, metric, lineno) triples from the ``DEFAULT_RULES``
    assignment -- a list of PURE dict literals by contract (the alert
    engine and this check share that shape), so the AST read is
    exact, or None when the assignment is missing."""
    if idx is None:
        return None
    for node in idx.assigns:
        if not any(isinstance(t, ast.Name) and t.id == "DEFAULT_RULES"
                   for t in node.targets):
            continue
        if not isinstance(node.value, (ast.List, ast.Tuple)):
            return None
        out = []
        for elt in node.value.elts:
            if not isinstance(elt, ast.Dict):
                continue
            d = {}
            for k, v in zip(elt.keys, elt.values):
                kk = _literal(k)
                if kk in ("name", "metric"):
                    d[kk] = _literal(v)
            out.append((d.get("name"), d.get("metric"), elt.lineno))
        return out
    return None


def _check_alert_rules(ctx, pkg_dir: str, declared: set) -> list:
    """Rule-pack validation (rule 3 of the module docstring): the
    default pack in telemetry/alerts.py plus every
    tests/fixtures/alert_rules*.json file an operator or test might
    feed DPRF_ALERT_RULES."""
    import json
    out = []
    alerts_py = os.path.join(pkg_dir, ALERTS_REL)
    if os.path.exists(alerts_py):
        rel = ctx.rel(alerts_py)
        refs = _alert_rule_refs(ctx.index(alerts_py))
        if refs is None:
            out.append(Finding(
                NAME, rel, 1,
                "DEFAULT_RULES literal rule pack not found in "
                "telemetry/alerts.py (it must stay a list of pure "
                "dict literals so this check can read it)"))
            refs = []
        for rule, metric, lineno in refs:
            if not metric:
                out.append(Finding(
                    NAME, rel, lineno,
                    f"alert rule {rule!r} has no literal 'metric' "
                    "key"))
            elif metric not in declared:
                out.append(Finding(
                    NAME, rel, lineno,
                    f"alert rule {rule!r} references metric "
                    f"{metric!r} that no package call site declares "
                    "-- stale or undeclared; the rule would be "
                    "silently disarmed"))
    fixtures = os.path.join(ctx.tests_dir, "fixtures")
    if os.path.isdir(fixtures):
        for fn in sorted(os.listdir(fixtures)):
            if not (fn.startswith("alert_rules")
                    and fn.endswith(".json")):
                continue
            p = os.path.join(fixtures, fn)
            try:
                with open(p, encoding="utf-8") as fh:
                    doc = json.load(fh)
            except (OSError, ValueError):
                out.append(Finding(
                    NAME, ctx.rel(p), 1,
                    "alert-rules fixture does not parse as JSON"))
                continue
            if not isinstance(doc, list):
                out.append(Finding(
                    NAME, ctx.rel(p), 1,
                    "alert-rules fixture must be a JSON list of "
                    "rule objects"))
                continue
            for i, r in enumerate(doc):
                rule = r.get("name") if isinstance(r, dict) else f"#{i}"
                metric = (r.get("metric")
                          if isinstance(r, dict) else None)
                if not isinstance(metric, str) or metric not in declared:
                    out.append(Finding(
                        NAME, ctx.rel(p), 1,
                        f"alert rule {rule!r} references metric "
                        f"{metric!r} that is not a declared dprf_* "
                        "metric"))
    return out


def _profiler_calls(idx):
    """(description, lineno) for every jax.profiler trace call in a
    file (rule 4): start/stop_trace attribute calls, plus ``.trace(``
    called on something named ``profiler``."""
    out = []
    for node in idx.calls:
        f = node.func
        if not isinstance(f, ast.Attribute):
            continue
        if f.attr in PROFILER_METHODS:
            out.append((f.attr, node.lineno))
        elif f.attr == "trace":
            v = f.value
            name = (v.attr if isinstance(v, ast.Attribute)
                    else v.id if isinstance(v, ast.Name) else None)
            if name == "profiler":
                out.append(("profiler.trace", node.lineno))
    return out


def _check_profiler_discipline(ctx, pkg_dir: str) -> list:
    """Rule 4: every jax.profiler trace call lives in
    telemetry/profiler.py -- the single-flight capture owner."""
    out = []
    profiler_rel = ctx.rel(os.path.join(pkg_dir, PROFILER_REL))
    for path in (ctx.package_files() + ctx.root_files()
                 + ctx.tools_files()):
        try:
            if not _PROFILER_RE.search(ctx.source(path)):
                continue
        except OSError:
            continue
        rel = ctx.rel(path)
        if rel == profiler_rel:
            continue
        idx = ctx.index(path)
        if idx is None:
            continue
        for what, lineno in _profiler_calls(idx):
            out.append(Finding(
                NAME, rel, lineno,
                f"jax.profiler call ({what}) outside "
                "telemetry/profiler.py -- jax allows ONE active "
                "trace; route captures through ProfileCapture's "
                "single-flight guard (session/begin_window)"))
    return out


def _declared_names(idx, tuple_name: str):
    """The SPAN_NAMES / STATIONS tuple, or None when the assignment is
    missing."""
    if idx is None:
        return None
    for node in idx.assigns:
        if not any(isinstance(t, ast.Name) and t.id == tuple_name
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            names = [_literal(e) for e in node.value.elts]
            if all(n is not None for n in names):
                return names
    return None


def run(ctx) -> list:
    pkg_dir = ctx.package_dir
    out = []
    decl_sites: dict = {}    # metric name -> [(rel, line), ...]
    span_sites = []          # (name, rel, line, recorder method)
    for path in ctx.package_files():
        try:
            if not _RELEVANT_RE.search(ctx.source(path)):
                continue
        except OSError:
            continue
        idx = ctx.index(path)
        if idx is None:
            continue
        decls, span_uses = _scan_file(idx)
        rel = ctx.rel(path)
        for metric, lineno in decls:
            decl_sites.setdefault(metric, []).append((rel, lineno))
        for span, lineno, method in span_uses:
            span_sites.append((span, rel, lineno, method))

    for metric, sites in sorted(decl_sites.items()):
        if len(sites) > 1:
            where = ", ".join(f"{r}:{ln}" for r, ln in sites)
            out.append(Finding(
                NAME, sites[0][0], sites[0][1],
                f"metric {metric!r} declared at {len(sites)} sites "
                f"({where}) -- declare once and share the helper "
                "(telemetry.declare_job_metrics pattern)"))

    trace_py = os.path.join(pkg_dir, TRACE_REL)
    trace_idx = ctx.index(trace_py) if os.path.exists(trace_py) else None
    for method, (kind, tuple_name) in DECLARED_IN.items():
        sites = [s for s in span_sites if s[3] == method]
        names = _declared_names(trace_idx, tuple_name)
        if names is None:
            if sites:
                out.append(Finding(
                    NAME, ctx.rel(trace_py), 1,
                    f"{tuple_name} tuple not found but {len(sites)} "
                    "call sites use its names"))
            continue
        dupes = {n for n in names if names.count(n) > 1}
        if dupes:
            out.append(Finding(
                NAME, ctx.rel(trace_py), 1,
                f"duplicate {tuple_name} entries: {sorted(dupes)}"))
        for span, rel, lineno, _ in sites:
            if span not in names:
                out.append(Finding(
                    NAME, rel, lineno,
                    f"{kind} {span!r} not declared in "
                    f"telemetry/trace.py {tuple_name}"))

    # alert rules (default pack + fixture files) must reference
    # declared metrics only
    out.extend(_check_alert_rules(ctx, pkg_dir, set(decl_sites)))
    # jax.profiler calls only in the single-flight owner (ISSUE 15)
    out.extend(_check_profiler_discipline(ctx, pkg_dir))
    return out
