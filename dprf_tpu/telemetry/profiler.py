"""Kernel-level profiling plane (ISSUE 15): on-demand jax.profiler
capture + dependency-free perfetto analysis.

Every observability layer so far stops at the sweep boundary: the
per-phase probes (telemetry/perf.py) say how long ``device`` took and
the program registry (telemetry/programs.py) says what XLA *predicted*
it costs -- nothing says where device time actually goes inside a
dispatch.  This module closes that gap in three pieces:

  1. **ProfileCapture** -- the single owner of every
     ``jax.profiler.start_trace`` in the repo.  jax allows ONE active
     trace per process, so the ``--profile`` flag, the
     ``DPRF_JAX_PROFILE`` env knob, and on-demand capture windows all
     route through its single-flight guard: a second starter degrades
     to a logged no-op instead of an exception mid-job.  On-demand
     captures are BOUNDED WINDOWS -- ``begin_window`` starts the
     trace, the caller keeps doing its normal work, and ``poll()``
     stops + analyzes once the window elapsed (so the capture records
     the real workload, not a synthetic one).  Raw capture dirs are
     size-capped (``DPRF_PROFILE_MAX_BYTES`` drops the .xplane.pb
     bulk) with keep-last-N retention (``DPRF_PROFILE_KEEP``).

  2. **The analyzer** -- ``analyze_trace`` parses the emitted
     ``perfetto_trace.json.gz`` (gzip JSON trace events; verified
     parseable on jax 0.9.0) with NO dependencies beyond stdlib:
     lanes come from the process/thread-name metadata events,
     per-event SELF time from the nesting stack, and every device-op
     event is classified by name (fusion / collective / copy-convert
     / custom-call) with compile and host-python lanes accounted
     separately.  The summary carries a top-ops table,
     compute/collective/copy fractions, and a generate/hash/compare
     sub-phase split mapped through per-engine declared name patterns
     (``PROFILE_PHASES`` on the engine classes; defaults below) --
     finally splitting the wordlist ``device`` blob and making Pallas
     custom-calls (which under-report flops to ``cost_analysis``)
     and superstep collective time measurable.

  3. **The divergence gauge** -- when a capture knows how many
     candidates were swept during its window, measured device-op
     seconds per candidate are compared against the program
     registry's ANALYZED cost at the chip's int32 issue ceiling
     (``dprf_profile_cost_divergence{engine}``): > 1 means the chip
     spent more device time than the XLA cost model predicts.

The fleet path (op_profile / op_profile_push RPC, alert-triggered
auto-capture) lives in runtime/rpc.py; the surfaces are ``dprf
profile``, ``dprf report``'s kernel-profile section, and ``dprf bench
--profile``.

Summary schema (``schema: 1``; wire-shipped summaries pass
``sanitize_summary`` -- bounded, known keys only)::

    {"schema": 1, "ts": <epoch s>, "window_s": <float>,
     "trigger": "manual|env|cli|bench|straggler|job_stalled",
     "path": "<capture dir on the capturing host>",
     "engine": "<engine or null>", "events": <int>,
     "seconds": {"fusion": s, "op": s, "collective": s, "copy": s,
                 "custom_call": s, "compile": s, "host": s,
                 "infra": s},
     "device_s": <float>, "fractions": {"compute": f,
     "collective": f, "copy": f},
     "phases": {"generate": s, "hash": s, "compare": s, "other": s},
     "top_ops": [{"name", "class", "self_s", "count"} x <= 20],
     "candidates": <int|null>, "device_s_per_cand": <float|null>,
     "divergence": <float|null>, "error": "<only on failure>"}
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import threading
import time
from collections import deque
from typing import Callable, Optional

from dprf_tpu.utils import env as envreg

#: opt-in: wrap sweep loops in a jax.profiler trace written here (the
#: historical knob; trace.jax_profile_ctx delegates to session_ctx)
PROFILE_ENV = "DPRF_JAX_PROFILE"
AUTOPROFILE_ENV = "DPRF_AUTOPROFILE"
COOLDOWN_ENV = "DPRF_PROFILE_COOLDOWN_S"
WINDOW_ENV = "DPRF_PROFILE_SECONDS"
KEEP_ENV = "DPRF_PROFILE_KEEP"
MAX_BYTES_ENV = "DPRF_PROFILE_MAX_BYTES"
DIR_ENV = "DPRF_PROFILE_DIR"

SUMMARY_SCHEMA = 1

#: op classes the analyzer buckets self-time into.  The first five are
#: DEVICE classes (their sum is ``device_s``); compile/host/infra are
#: the non-device lanes.
DEVICE_CLASSES = ("fusion", "op", "custom_call", "collective", "copy")
OP_CLASSES = DEVICE_CLASSES + ("compile", "host", "infra")

#: top-ops table length (and the wire bound on ingested summaries)
TOP_OPS = 20

#: largest trace file the analyzer will parse (compressed bytes): a
#: runaway capture must fail fast with an error summary, not pin a
#: worker loop parsing gigabytes of JSON
MAX_TRACE_BYTES = 128 << 20

#: wire-summary sanitization bounds (worker-shipped summaries are
#: client-controlled, like trace spans and heartbeat payloads)
MAX_SUMMARY_STR = 256
SUMMARY_KEYS = ("schema", "ts", "window_s", "trigger", "path",
                "engine", "events", "seconds", "device_s",
                "fractions", "phases", "top_ops", "candidates",
                "device_s_per_cand", "divergence", "error",
                "request_id")

#: summaries ProfileCapture keeps in memory (local history; the
#: coordinator keeps its own per-worker table)
HISTORY_MAX = 8

#: fallback phase patterns: matched (substring, lowercased) against
#: each device op's name + metadata text.  Engines refine these with a
#: ``PROFILE_PHASES`` class attribute (engines/device/engines.py) --
#: the per-engine declaration site the analyzer merges over these.
#: Order matters: generate and compare are matched BEFORE hash, whose
#: patterns are deliberately broad (the fused digest body is most of
#: a crack step).
DEFAULT_PROFILE_PHASES = {
    "generate": ("decode", "iota", "digit", "generate", "expand_word"),
    "compare": ("compare", "equal", " eq", "match", "hit",
                "reduce-or", "any_hit"),
    "hash": ("fusion", "hash", "round", "digest", "while", "crack",
             "custom-call", "mosaic"),
}
PHASE_ORDER = ("generate", "compare", "hash")

#: lock-discipline declaration (`dprf check` locks analyzer): the
#: capture object is touched by the worker loop, RPC handler threads
#: (request delivery), and CLI threads; all mutable capture state
#: moves under ``_lock``.  The jax start/stop calls themselves run
#: OUTSIDE the lock -- they can take seconds and must not stall a
#: concurrent single-flight check.  The module-level ``_deps`` warm
#: state is shared by every capture object.
GUARDED_BY = {
    "ProfileCapture": {
        "_lock": ("_owner", "_window", "_done", "_history",
                  "_last_ts"),
    },
    "<module>": {"_deps_lock": ("_deps",)},
}

# -- lazy-dependency warmup --------------------------------------------------
# jax.profiler.start_trace lazily imports its trace-export stack on
# first use (tensorflow + its scipy/sklearn/pandas train on stock
# installs) -- measured 60-90 s COLD on a throttled box, which would
# wedge a worker loop mid-sweep long enough to trip worker_missing.
# The warm runs on a daemon thread kicked at window-arm time; poll()
# refuses to start the trace until it finished, so the stall overlaps
# normal sweeping instead of blocking it.

_deps_lock = threading.Lock()
_deps: dict = {"state": None}     # None | "warming" | "ready"


def _warm_deps_thread() -> None:
    try:
        import tensorflow  # noqa: F401 -- the lazy stack start_trace
        # pulls in on first use; absent installs just skip the warm
    except Exception:   # noqa: BLE001
        pass
    try:
        import jax.profiler  # noqa: F401
    except Exception:   # noqa: BLE001
        pass
    with _deps_lock:
        _deps["state"] = "ready"


def warm_deps_async() -> bool:
    """Kick (once) the background import of the profiler's lazy
    dependency stack; True when a trace can start WITHOUT paying a
    cold-import stall inline."""
    with _deps_lock:
        if _deps["state"] == "ready":
            return True
        if _deps["state"] is None:
            _deps["state"] = "warming"
            threading.Thread(target=_warm_deps_thread, daemon=True,
                             name="dprf-profiler-warm").start()
        return False


def default_window_s() -> float:
    v = envreg.get_float(WINDOW_ENV, 3.0)
    return max(0.5, float(v or 3.0))


def autoprofile_enabled() -> bool:
    return envreg.get_bool(AUTOPROFILE_ENV)


def cooldown_s() -> float:
    v = envreg.get_float(COOLDOWN_ENV, 600.0)
    return max(0.0, float(v or 0.0))


def profile_dir() -> str:
    """Where a worker writes on-demand capture dirs: the declared
    knob, else a stable per-process dir under the temp root (raw
    traces never ship over the wire -- the summary names this
    path)."""
    d = envreg.get_path(DIR_ENV)
    if d:
        return d
    import tempfile
    return os.path.join(tempfile.gettempdir(),
                        f"dprf-profile-{os.getpid()}")


def _captures_counter(registry=None):
    from dprf_tpu.telemetry import get_registry
    return get_registry(registry).counter(
        "dprf_profile_captures_total",
        "kernel-profile capture windows completed, by trigger "
        "(manual/env/cli/bench or the firing alert rule)",
        labelnames=("trigger",))


def _divergence_gauge(registry=None):
    from dprf_tpu.telemetry import get_registry
    return get_registry(registry).gauge(
        "dprf_profile_cost_divergence",
        "measured device-op seconds per candidate / the program "
        "registry's analyzed cost at the int32 issue ceiling "
        "(> 1: the chip spends more device time than the XLA cost "
        "model predicts)", labelnames=("engine",))


def publish_divergence(engine: str, device_s_per_cand: float,
                       registry=None) -> Optional[float]:
    """Measured-vs-analyzed cost ratio for one capture; None when the
    engine has no analyzed program in this process or this process's
    chip kind has no band (nothing honest to divide by)."""
    from dprf_tpu.telemetry import perf as perf_mod
    from dprf_tpu.telemetry import programs as programs_mod
    ops = programs_mod.analyzed_ops_per_candidate(engine)
    if not ops or not device_s_per_cand or device_s_per_cand <= 0:
        return None
    chip = perf_mod.CHIP_INT_OPS_BANDS.get(perf_mod.local_device_kind())
    if chip is None:
        return None
    predicted = ops / chip[1]
    ratio = device_s_per_cand / predicted
    _divergence_gauge(registry).set(ratio, engine=engine)
    return ratio


# ---------------------------------------------------------------------------
# the dependency-free perfetto analyzer

def find_trace(path: str) -> Optional[str]:
    """The newest ``perfetto_trace.json.gz`` under a capture dir (jax
    writes ``plugins/profile/<ts>/``), or the file itself when handed
    one directly."""
    if os.path.isfile(path):
        return path
    hits = glob.glob(os.path.join(
        path, "**", "perfetto_trace.json.gz"), recursive=True)
    if not hits:
        return None
    return max(hits, key=lambda p: os.path.getmtime(p))


def _load_events(trace_file: str) -> list:
    opener = gzip.open if trace_file.endswith(".gz") else open
    with opener(trace_file, "rt", encoding="utf-8",
                errors="replace") as fh:
        doc = json.load(fh)
    evs = doc.get("traceEvents") if isinstance(doc, dict) else None
    return evs if isinstance(evs, list) else []


#: lane kinds, decided from the process/thread-name metadata: the
#: device-op lane holds per-HLO events (TPU: the "XLA Ops" threads of
#: "/device:*" processes; CPU backend: the "tf_XLAPjRtCpuClient/<id>"
#: execution threads), the compile lanes hold codegen/compile-pass
#: work (CPU backend: "tf_xla-cpu-codegen/<id>"), the host lane holds
#: the $file:line python frames.
def _lane_kind(proc_name: str, thread_name: str) -> str:
    p, t = proc_name.lower(), thread_name.lower()
    if "codegen" in t or "compile" in t:
        return "compile"
    if "/device:" in p:
        # xprof device processes: the op lane is "XLA Ops"; module/
        # step lanes would double-count every op's time
        if "xla ops" in t:
            return "device"
        if "xla modules" in t or t.startswith("step"):
            return "skip"
        return "device" if not t else "skip"
    if "pjrtcpuclient" in t or "xla:cpu" in t or "stream" in t:
        return "device"
    if t == "python" or "host" in p and t.startswith("py"):
        return "host"
    return "infra"


_COLLECTIVE_PAT = ("all-reduce", "all-gather", "all-to-all",
                   "reduce-scatter", "collective", "psum", "permute")
_COPY_PAT = ("copy", "convert", "transpose", "bitcast")
_CUSTOM_PAT = ("custom-call", "custom_call", "pallas", "mosaic")
_INFRA_PAT = ("threadpoollistener", "thunkexecutor", "taskdispatcher",
              "streamexecutor", "wait for ")


def classify_op(name: str, lane: str) -> str:
    """One event's class.  Host/compile lanes classify by lane; the
    device lane splits by op name so the fractions can separate
    compute from collectives and copies."""
    n = name.lower()
    if lane == "host" or n.startswith("$"):
        return "host"
    if lane == "compile":
        return "compile"
    if any(p in n for p in _INFRA_PAT):
        return "infra"
    if lane != "device":
        return "infra"
    if any(p in n for p in _COLLECTIVE_PAT):
        return "collective"
    if any(p in n for p in _CUSTOM_PAT):
        return "custom_call"
    if "fusion" in n:
        return "fusion"
    if any(n.startswith(p) or p in n for p in _COPY_PAT):
        return "copy"
    return "op"


def phase_patterns(engine: Optional[str]) -> dict:
    """The generate/hash/compare name patterns for an engine: the
    engine class's declared ``PROFILE_PHASES`` merged over the
    defaults.  Resolution is best-effort -- the analyzer must stay
    usable on a host without jax/the engine registry installed."""
    merged = {k: tuple(v) for k, v in DEFAULT_PROFILE_PHASES.items()}
    if not engine:
        return merged
    try:
        from dprf_tpu import get_engine
        eng = get_engine(engine, device="jax")
        declared = getattr(type(eng), "PROFILE_PHASES", None) or {}
        for k, pats in declared.items():
            if k in merged and isinstance(pats, (tuple, list)):
                merged[k] = tuple(str(p).lower() for p in pats) \
                    + merged[k]
    except Exception:   # noqa: BLE001 -- no jax / unknown engine:
        pass            # defaults still split most traces usefully
    return merged


def _self_times(events: list, lanes: dict) -> list:
    """(lane_kind, name, self_seconds) per event, self time via the
    per-lane nesting stack (an event's own dur minus its children's).
    Device lanes can hold overlapping async events; the stack model
    treats a later-starting overlap as a child, which attributes the
    overlap once -- the honest choice for wall-time fractions."""
    by_lane: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        name = e.get("name")
        if not isinstance(name, str):
            continue
        try:
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        kind = lanes.get((e.get("pid"), e.get("tid")), "infra")
        if kind == "skip" or dur < 0:
            continue
        by_lane.setdefault((e.get("pid"), e.get("tid"), kind),
                           []).append((ts, dur, name))
    out = []
    for (_, _, kind), evs in by_lane.items():
        evs.sort(key=lambda x: (x[0], -x[1]))
        stack: list = []    # [(end_ts, self_acc)]
        for ts, dur, name in evs:
            while stack and stack[-1][0] <= ts + 1e-9:
                stack.pop()
            if stack:
                stack[-1][1][0] -= dur
            acc = [dur]
            stack.append((ts + dur, acc))
            out.append((kind, name, acc))
    return [(k, n, max(0.0, a[0]) * 1e-6) for k, n, a in out]


def analyze_trace(path: str, engine: Optional[str] = None,
                  candidates: Optional[int] = None,
                  top: int = TOP_OPS, registry=None) -> dict:
    """Parse + aggregate one capture into the summary schema (module
    docstring).  ``path`` is a capture dir or the perfetto file
    itself; ``candidates`` (when the caller knows how many were swept
    during the window) turns on per-candidate cost and the
    divergence gauge."""
    trace_file = find_trace(path)
    if trace_file is None:
        return {"schema": SUMMARY_SCHEMA, "path": path, "engine": engine,
                "error": "no perfetto_trace.json.gz under this path"}
    try:
        size = os.path.getsize(trace_file)
    except OSError:
        size = 0
    if size > MAX_TRACE_BYTES:
        return {"schema": SUMMARY_SCHEMA, "path": path, "engine": engine,
                "error": f"trace too large to analyze ({size} bytes "
                f"> {MAX_TRACE_BYTES})"}
    try:
        events = _load_events(trace_file)
    except (OSError, ValueError) as e:
        return {"schema": SUMMARY_SCHEMA, "path": path, "engine": engine,
                "error": f"unparsable trace: {e}"}
    procs: dict = {}
    threads: dict = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            procs[e.get("pid")] = str(args.get("name", ""))
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = \
                str(args.get("name", ""))
    lanes = {key: _lane_kind(procs.get(key[0], ""), tname)
             for key, tname in threads.items()}

    classes = {c: 0.0 for c in OP_CLASSES}
    per_op: dict = {}
    patterns = phase_patterns(engine)
    phases = {"generate": 0.0, "hash": 0.0, "compare": 0.0,
              "other": 0.0}
    n_events = 0
    for kind, name, self_s in _self_times(events, lanes):
        n_events += 1
        cls = classify_op(name, kind)
        classes[cls] += self_s
        if cls in DEVICE_CLASSES:
            rec = per_op.setdefault(name, [cls, 0.0, 0])
            rec[1] += self_s
            rec[2] += 1
            low = name.lower()
            for ph in PHASE_ORDER:
                if any(p in low for p in patterns[ph]):
                    phases[ph] += self_s
                    break
            else:
                phases["other"] += self_s
    device_s = sum(classes[c] for c in DEVICE_CLASSES)
    fractions = {"compute": 0.0, "collective": 0.0, "copy": 0.0}
    if device_s > 0:
        fractions = {
            "compute": (classes["fusion"] + classes["op"]
                        + classes["custom_call"]) / device_s,
            "collective": classes["collective"] / device_s,
            "copy": classes["copy"] / device_s,
        }
    top_ops = sorted(
        ({"name": name, "class": rec[0],
          "self_s": round(rec[1], 6), "count": rec[2]}
         for name, rec in per_op.items()),
        key=lambda r: -r["self_s"])[:max(1, top)]
    out = {
        "schema": SUMMARY_SCHEMA,
        "ts": round(time.time(), 3),
        "path": path,
        "engine": engine,
        "events": n_events,
        "seconds": {c: round(classes[c], 6) for c in OP_CLASSES},
        "device_s": round(device_s, 6),
        "fractions": {k: round(v, 4) for k, v in fractions.items()},
        "phases": {k: round(v, 6) for k, v in phases.items()},
        "top_ops": top_ops,
        "candidates": candidates,
        "device_s_per_cand": None,
        "divergence": None,
    }
    if candidates and candidates > 0 and device_s > 0:
        spc = device_s / candidates
        out["device_s_per_cand"] = spc
        if engine:
            out["divergence"] = publish_divergence(
                engine, spc, registry=registry)
            # feed the roofline fallback chain: programs whose HLO
            # reports no flop count (probe-table steps) get an op
            # model from this measured cost (perf.ops_per_candidate)
            from dprf_tpu.telemetry import perf as perf_mod
            perf_mod.record_measured_cost(engine, spc,
                                          registry=registry)
    return out


def sanitize_summary(summary) -> Optional[dict]:
    """Bounded, known-keys-only view of a worker-shipped summary
    (client-controlled, like ingested spans): strings truncated,
    numeric fields coerced, top_ops capped at TOP_OPS entries."""
    if not isinstance(summary, dict):
        return None
    out: dict = {}
    for k in SUMMARY_KEYS:
        if k not in summary:
            continue
        v = summary[k]
        if k == "top_ops":
            rows = []
            for r in (v if isinstance(v, list) else [])[:TOP_OPS]:
                if not isinstance(r, dict):
                    continue
                try:
                    rows.append({
                        "name": str(r.get("name", "?"))[:MAX_SUMMARY_STR],
                        "class": str(r.get("class", "?"))[:32],
                        "self_s": float(r.get("self_s") or 0.0),
                        "count": int(r.get("count") or 0)})
                except (TypeError, ValueError):
                    continue
            out[k] = rows
        elif k in ("seconds", "fractions", "phases"):
            if isinstance(v, dict):
                clean = {}
                for kk, vv in list(v.items())[:16]:
                    try:
                        clean[str(kk)[:32]] = float(vv)
                    except (TypeError, ValueError):
                        continue
                out[k] = clean
        elif v is None or isinstance(v, bool):
            out[k] = v
        elif isinstance(v, (int, float)):
            out[k] = v
        else:
            out[k] = str(v)[:MAX_SUMMARY_STR]
    if not out:
        return None
    out.setdefault("schema", SUMMARY_SCHEMA)
    return out


def render_summary(doc: dict) -> str:
    """The human rendering (``dprf profile`` stdout / the report's
    kernel-profile section body)."""
    lines = []
    if doc.get("error"):
        lines.append(f"capture FAILED: {doc['error']}")
    head = (f"engine {doc.get('engine') or '?'} | "
            f"{doc.get('events', 0)} events | device "
            f"{doc.get('device_s', 0.0):.4f}s")
    if doc.get("window_s"):
        head += f" | window {doc['window_s']:.1f}s"
    if doc.get("trigger"):
        head += f" | trigger {doc['trigger']}"
    lines.append(head)
    fr = doc.get("fractions") or {}
    if fr:
        lines.append("  device fractions  "
                     + "  ".join(f"{k} {100.0 * fr.get(k, 0.0):.1f}%"
                                 for k in ("compute", "collective",
                                           "copy")))
    secs = doc.get("seconds") or {}
    aux = [f"{k} {secs[k]:.4f}s" for k in ("compile", "host")
           if secs.get(k)]
    if aux:
        lines.append("  off-device        " + "  ".join(aux))
    ph = doc.get("phases") or {}
    if any(ph.values()):
        lines.append("  phases            "
                     + "  ".join(f"{k} {ph.get(k, 0.0):.4f}s"
                                 for k in ("generate", "hash",
                                           "compare", "other")))
    if doc.get("device_s_per_cand"):
        d = doc.get("divergence")
        lines.append(f"  per candidate     "
                     f"{doc['device_s_per_cand']:.3e}s"
                     + (f"  (divergence {d:.2f}x vs analyzed cost)"
                        if d else ""))
    ops = doc.get("top_ops") or []
    if ops:
        lines.append(f"  {'OP':44s} {'CLASS':>11s} {'SELF':>10s} "
                     f"{'COUNT':>6s}")
        for r in ops:
            lines.append(f"  {r['name'][:44]:44s} {r['class']:>11s} "
                         f"{r['self_s']:>9.4f}s {r['count']:>6d}")
    if doc.get("path"):
        lines.append(f"  raw trace: {doc['path']}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# retention

def enforce_caps(root: str, keep: Optional[int] = None,
                 max_bytes: Optional[int] = None) -> None:
    """Bound the raw artifacts under a profile root: capture dirs
    (``plugins/profile/<ts>``) beyond keep-last-N are deleted oldest
    first, and a capture whose files exceed the byte cap drops its
    ``.xplane.pb`` bulk (the perfetto JSON -- what the analyzer reads
    -- is always kept)."""
    import shutil
    keep = envreg.get_int(KEEP_ENV) if keep is None else keep
    max_bytes = (envreg.get_int(MAX_BYTES_ENV)
                 if max_bytes is None else max_bytes)
    base = os.path.join(root, "plugins", "profile")
    try:
        runs = sorted(
            (os.path.join(base, d) for d in os.listdir(base)
             if os.path.isdir(os.path.join(base, d))),
            key=lambda p: os.path.getmtime(p))
    except OSError:
        return
    if keep and keep > 0:
        for old in runs[:-keep]:
            shutil.rmtree(old, ignore_errors=True)
        runs = runs[-keep:]
    if not max_bytes or max_bytes <= 0:
        return
    for run in runs:
        files = []
        total = 0
        for r, _, fns in os.walk(run):
            for fn in fns:
                p = os.path.join(r, fn)
                try:
                    total += os.path.getsize(p)
                except OSError:
                    continue
                files.append(p)
        if total <= max_bytes:
            continue
        for p in files:
            if p.endswith(".xplane.pb"):
                try:
                    os.unlink(p)
                except OSError:
                    pass


# ---------------------------------------------------------------------------
# the single-flight capture owner

class ProfileCapture:
    """The one object allowed to start/stop jax profiler traces in
    this process.  Three entry shapes share its single-flight slot:

      - ``session(dir)``: a context manager wrapping a whole run
        (the ``--profile`` flag and ``DPRF_JAX_PROFILE``);
      - ``begin_window`` / ``poll()``: the on-demand bounded window
        (op_profile requests, auto-capture) -- poll is ONE attribute
        read when no window is active, so the dispatch path pays
        nothing while capture is disabled;
      - ``capture(seconds)``: the synchronous convenience (bench,
        tests) -- begin, run ``busy_fn`` (or sleep), poll to done.
    """

    def __init__(self, registry=None):
        self._lock = threading.Lock()
        self._owner: Optional[str] = None
        #: active bounded window: {"deadline", "dir", "trigger",
        #: "engine", "request_id", "counter_fn", "cands0",
        #: "seconds"} -- None when idle (the poll fast path)
        self._window: Optional[dict] = None
        #: finished-but-unconsumed summaries, drained by poll().  A
        #: separate queue (not a state on the window) so a new window
        #: armed while the previous one is still analyzing on its
        #: background thread can never clobber an undelivered
        #: summary -- each request's result reaches its poller.
        self._done: deque = deque(maxlen=HISTORY_MAX)
        self._history: deque = deque(maxlen=HISTORY_MAX)
        #: per-trigger last capture wall time (the coordinator keeps
        #: its own cooldown ledger; this one rate-limits env-local
        #: paths)
        self._last_ts: dict = {}
        self._registry = registry

    # -- single-flight slot ---------------------------------------------

    def _acquire(self, owner: str) -> bool:
        with self._lock:
            if self._owner is not None:
                return False
            self._owner = owner
            return True

    def _release(self, owner: str) -> None:
        with self._lock:
            if self._owner == owner:
                self._owner = None

    def busy(self) -> Optional[str]:
        """The current owner label, or None when the slot is free."""
        with self._lock:
            return self._owner

    # -- session-length traces (--profile / DPRF_JAX_PROFILE) -----------

    @contextlib.contextmanager
    def session(self, directory: str, owner: str = "session",
                log=None):
        """Wrap a whole run in one trace.  Degrades to a no-op (with
        a logged warning) instead of killing the job when the slot is
        taken or the profiler cannot start -- e.g. ``--profile`` and
        ``DPRF_JAX_PROFILE`` naming different dirs on one process."""
        if not self._acquire(owner):
            if log is not None:
                log.warn("profiler busy; trace NOT started",
                         dir=directory, owner=self.busy())
            yield self
            return
        started = False
        try:
            import jax
            jax.profiler.start_trace(directory,
                                     create_perfetto_trace=True)
            started = True
        except Exception as e:   # noqa: BLE001 -- diagnostics only
            if log is not None:
                log.warn("jax profiler trace failed to start",
                         dir=directory, error=str(e))
        try:
            yield self
        finally:
            if started:
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception:    # noqa: BLE001
                    pass
                enforce_caps(directory)
                _captures_counter(self._registry).inc(trigger=owner)
            self._release(owner)

    # -- bounded on-demand windows --------------------------------------

    def begin_window(self, seconds: Optional[float] = None,
                     directory: Optional[str] = None,
                     trigger: str = "manual",
                     engine: Optional[str] = None,
                     request_id=None,
                     counter_fn: Optional[Callable] = None,
                     log=None) -> bool:
        """ARM a bounded capture window; the caller keeps doing its
        normal work and calls ``poll()`` until the summary lands.
        The trace itself starts LAZILY at the next ``poll()`` call --
        a worker that receives a request right before a minutes-long
        warmup compile must capture its steady-state sweeps, not a
        giant compile-stall trace (the loop only polls between
        units).  False when the single-flight slot is taken
        (callers report that in-band -- the collision contract)."""
        seconds = default_window_s() if seconds is None else \
            max(0.5, float(seconds))
        directory = directory or profile_dir()
        owner = f"window:{trigger}"
        if not self._acquire(owner):
            if log is not None:
                log.warn("profiler busy; capture window refused",
                         trigger=trigger, owner=self.busy())
            return False
        warm_deps_async()      # overlap the cold import with sweeping
        with self._lock:
            self._window = {
                "state": "armed", "deadline": None,
                "seconds": seconds, "dir": directory,
                "trigger": trigger, "engine": engine,
                "request_id": request_id, "counter_fn": counter_fn,
                "cands0": None, "owner": owner,
            }
        return True

    def _fail_window(self, w: dict, error: str) -> dict:
        self._release(w["owner"])
        return {"schema": SUMMARY_SCHEMA, "trigger": w["trigger"],
                "engine": w["engine"], "request_id": w["request_id"],
                "error": error}

    def poll(self) -> Optional[dict]:
        """Drive an armed window through its states: the first call
        (with the dep warm done) starts the trace; once the deadline
        elapsed the stop + analyze run on a BACKGROUND thread -- a
        million-event trace can take minutes to parse on a loaded
        host, and blocking the worker loop that long would trip the
        very worker_missing alert a capture is investigating; a later
        poll returns the finished summary exactly once.  One
        uncontended lock probe when no window is active -- the
        near-zero-overhead contract for the dispatch path (asserted
        in tests/test_profiler.py)."""
        start_me = None
        with self._lock:
            if self._done:
                return self._done.popleft()
            w = self._window
            if w is None:
                return None
            if w["state"] == "armed":
                if not warm_deps_async():
                    # the lazy import stack is still loading on the
                    # warm thread: keep sweeping, start next poll
                    return None
                w["state"] = "starting"
                start_me = w
            elif (w["state"] == "running"
                  and time.monotonic() >= w["deadline"]):
                w["state"] = "finishing"
                threading.Thread(target=self._finish_window,
                                 args=(w,), daemon=True,
                                 name="dprf-profiler-finish").start()
                return None
            else:
                return None
        w = start_me
        try:
            os.makedirs(w["dir"], exist_ok=True)
            import jax
            jax.profiler.start_trace(w["dir"],
                                     create_perfetto_trace=True)
        except Exception as e:   # noqa: BLE001 -- capture is
            # diagnostics; a broken profiler must not kill the
            # sweep -- the failure ships in-band as the summary
            with self._lock:
                self._window = None
            return self._fail_window(w, f"start_trace failed: {e}")
        if w["counter_fn"] is not None:
            try:
                w["cands0"] = int(w["counter_fn"]())
            except Exception:   # noqa: BLE001
                w["cands0"] = None
        with self._lock:
            w["deadline"] = time.monotonic() + w["seconds"]
            w["state"] = "running"
        return None

    def _finish_window(self, w: dict) -> None:
        """Background half of poll(): stop the trace (the perfetto
        gzip write alone can take seconds), free the single-flight
        slot, analyze, and queue the summary for the next poll to
        drain.  This thread is the SOLE releaser of a finishing
        window's slot (abort_window leaves it alone), so the release
        can never free a successor owner's slot."""
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:   # noqa: BLE001
            summary = self._fail_window(w, f"stop_trace failed: {e}")
        else:
            self._release(w["owner"])
            enforce_caps(w["dir"])
            cands = None
            if w["counter_fn"] is not None and w["cands0"] is not None:
                try:
                    cands = max(0, int(w["counter_fn"]()) - w["cands0"])
                except Exception:   # noqa: BLE001
                    cands = None
            summary = analyze_trace(w["dir"], engine=w["engine"],
                                    candidates=cands,
                                    registry=self._registry)
            summary["trigger"] = w["trigger"]
            summary["window_s"] = w["seconds"]
            if w["request_id"] is not None:
                summary["request_id"] = w["request_id"]
        _captures_counter(self._registry).inc(trigger=w["trigger"])
        with self._lock:
            if self._window is w:
                self._window = None
            self._done.append(summary)
            self._history.append(summary)
            self._last_ts[w["trigger"]] = time.time()

    def window_active(self) -> bool:
        with self._lock:
            return self._window is not None

    def finish_now(self, timeout_s: float = 120.0) -> Optional[dict]:
        """Drive the active window to completion synchronously (loop
        shutdown): a RUNNING window stops early -- a shorter capture
        than asked, but real data beats a silent abort when the job's
        last unit lands mid-window -- a FINISHING one is waited on
        (bounded; a 1M-event trace analyzes in ~15 s on one slow
        core), and an ARMED one that never started returns an
        in-band error summary so the requester gets an answer
        instead of a timeout.  Also drains a leftover undrained
        summary; None only when nothing landed inside the grace."""
        with self._lock:
            w = self._window
            st = w["state"] if w else None
        if w is None:
            return self.poll()       # drain any leftover summary
        if st == "armed":
            with self._lock:
                mine = self._window is w
                if mine:
                    self._window = None
            if mine:
                return self._fail_window(
                    w, "capture window never started before the job "
                    "ended")
            return self.poll()
        if st == "running":
            with self._lock:
                if self._window is w and w["state"] == "running":
                    w["state"] = "finishing"
                else:
                    w = None
            if w is not None:
                threading.Thread(target=self._finish_window,
                                 args=(w,), daemon=True,
                                 name="dprf-profiler-finish").start()
        deadline = time.monotonic() + max(0.0, timeout_s)
        while time.monotonic() < deadline:
            s = self.poll()
            if s is not None:
                return s
            time.sleep(0.05)
        return None

    def abort_window(self) -> None:
        """Discard an in-flight window (loop shutdown): stop the
        trace (if it ever started) and free the slot without
        analyzing.  A window already FINISHING stays with its
        background thread -- that thread stops/releases/queues on
        its own, and releasing here too would free a successor
        owner's slot.  No-op when idle."""
        with self._lock:
            w = self._window
            if w is None or w["state"] == "finishing":
                return
            self._window = None
        if w["state"] == "running":
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:   # noqa: BLE001
                pass
        self._release(w["owner"])

    def capture(self, seconds: Optional[float] = None,
                directory: Optional[str] = None,
                trigger: str = "manual",
                engine: Optional[str] = None,
                counter_fn: Optional[Callable] = None,
                busy_fn: Optional[Callable] = None,
                log=None) -> Optional[dict]:
        """Synchronous bounded capture: begin, keep the process busy
        (``busy_fn`` runs the real workload; default just sleeps the
        window), poll to completion.  None when the slot was taken."""
        if not self.begin_window(seconds, directory, trigger=trigger,
                                 engine=engine, counter_fn=counter_fn,
                                 log=log):
            return None
        while True:
            if busy_fn is not None:
                busy_fn()
            else:
                time.sleep(0.05)
            s = self.poll()
            if s is not None:
                return s

    # -- reads -----------------------------------------------------------

    def last_summary(self) -> Optional[dict]:
        with self._lock:
            return self._history[-1] if self._history else None

    def summaries(self) -> list:
        with self._lock:
            return list(self._history)

    def last_capture_ts(self, trigger: Optional[str] = None
                        ) -> Optional[float]:
        with self._lock:
            if trigger is not None:
                return self._last_ts.get(trigger)
            return max(self._last_ts.values(), default=None)


#: process-wide capture owner (the utils/logging.DEFAULT pattern):
#: worker loops, the CLI, and the env-knob path all share ONE
#: single-flight slot because jax allows one active trace per process
DEFAULT = ProfileCapture()


def get_profiler(profiler: Optional[ProfileCapture] = None
                 ) -> ProfileCapture:
    return profiler if profiler is not None else DEFAULT


def jax_profile_ctx(log=None):
    """``DPRF_JAX_PROFILE=<dir>``: a session trace context for a sweep
    loop, routed through the single-flight guard (a run also launched
    with ``--profile`` degrades this to a logged no-op); a null
    context when unset."""
    d = envreg.get_path(PROFILE_ENV)
    if not d:
        return contextlib.nullcontext()
    return DEFAULT.session(d, owner="env", log=log)
