"""Distributed tracing & flight recorder (ISSUE 4).

The metrics layer answers "how much / how fast"; this module answers
"WHICH unit, on WHICH worker, spent its time WHERE".  Every WorkUnit
gets a trace id when the Dispatcher splits it; each lifecycle step is
a SPAN -- ``lease``, ``rpc``, ``warmup``, ``sweep``, ``hit_verify``,
``complete`` / ``fail`` / ``reissue`` / ``park`` -- recorded by the
coordinator, dispatcher, and workers.  Trace context (trace id + lease
span id) rides the existing RPC messages: the lease response carries
it out, and the worker ships its spans back inside ``complete`` /
``fail``, so a remote worker's spans stitch onto the coordinator's
timeline with correct parent links even when the unit bounced between
hosts.

Spans land in two places:

  - a bounded in-memory ring (the "flight recorder"): the last N spans
    are always available for post-mortems and the ``op_trace_tail``
    RPC that feeds ``dprf top``;
  - a JSONL stream next to the session journal (``<session>
    .trace.jsonl``), size-capped with ``.1`` rotation like the
    telemetry snapshots, which ``dprf trace export`` converts to
    Chrome-trace / Perfetto JSON.

Span schema (one JSON object per line / ring entry)::

    {"name": "sweep", "ts": <epoch s>, "dur": <s>,
     "trace": "<unit trace id>", "span": "<id>", "parent": "<id|null>",
     "proc": "<coordinator|worker id|local>", "attrs": {...}}

``SPAN_NAMES`` below is the SINGLE declaration site for span names;
``tools/check_metrics.py`` (run from conftest) statically asserts that
every ``record("...")`` call site uses a declared name and that every
metric name is declared at exactly one site.

STATIONS (below ``SPAN_NAMES``) names the places of the sweep loop
where a unit's host time goes; ``TraceRecorder.station(name, unit=)``
opens one.  A station is a ``jax.profiler.TraceAnnotation``
``dprf:<name>`` -- so any profiler trace (``--profile``,
``DPRF_JAX_PROFILE``, a benchmark's slice) shows the host's stations
on the device trace's own clock -- and a row of the recorder's
``station_table()``: count and SELF seconds (its child stations taken
out), which the job's closing ``ran`` line prints as ``host=``.

Overhead: spans are per-UNIT events (a handful per ~20-second unit),
``record`` is a dict build + deque append + one buffered file write --
asserted <= 2% of the local sweep hot path in tests/test_trace.py.
``DPRF_TRACE=0`` disables recording entirely.  Opt-in
``DPRF_JAX_PROFILE=<dir>`` additionally wraps sweep loops in a
``jax.profiler`` trace for kernel-level drill-down.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import secrets
import sys
import threading
import time
from collections import deque
from typing import Optional

from dprf_tpu.utils import env as envreg

#: the one declaration site for span names (tools/check_metrics.py
#: enforces that every record() literal is a member).
SPAN_NAMES = ("lease", "rpc", "warmup", "sweep", "hit_verify",
              "complete", "fail", "reissue", "park", "restore")

#: the one declaration site for station names (tools/check_metrics.py
#: holds every station("...") literal to it).  ``targets`` is a job's,
#: before its first unit: hash-file parse (cli._setup_job), a bulk
#: list's table build and upload (MaskWorkerBase._setup_probe).  A
#: unit passes the others in this order; ``wait`` and ``decode`` open
#: inside ``resolve``.  A station is a unit or a dispatch, never a lane
#: or a batch of a fused program: a span each would cost what it
#: measures.
STATIONS = ("targets", "lease", "submit", "resolve", "wait",
            "decode", "verify", "complete")
_STATION_LABELS = {name: "dprf:" + name for name in STATIONS}

#: suffix appended to a session journal path for its span stream
TRACE_SUFFIX = ".trace.jsonl"

#: kill switch: DPRF_TRACE=0 disables span recording process-wide
ENABLE_ENV = "DPRF_TRACE"
#: size cap for the trace JSONL stream (rotated to `.1` when exceeded)
MAX_BYTES_ENV = "DPRF_TRACE_MAX_BYTES"
DEFAULT_MAX_BYTES = 16 << 20

#: span-id namespace: a per-process random prefix + a cheap counter --
#: unique across the fleet without paying a uuid4 per span
_ID_PREFIX = secrets.token_hex(4)
_ID_COUNTER = itertools.count(1)

#: ingest sanitization bounds (remote spans are client-controlled)
MAX_INGEST_SPANS = 64
MAX_ATTRS = 16
MAX_ATTR_STR = 256
MAX_ID_LEN = 64

#: lock-discipline declaration (`dprf check` locks analyzer): the
#: recorder is hit from RPC handler threads, the dispatcher (under
#: CoordinatorState.lock), and worker loops at once; ring and file
#: stream state must only move under ``_lock``.  The acquisition
#: order this induces -- CoordinatorState.lock, THEN _lock -- is
#: checked package-wide; code holding ``_lock`` must never call back
#: into the coordinator.
GUARDED_BY = {
    "TraceRecorder": {
        "_lock": ("_ring", "_fh", "_path", "_max_bytes",
                  "_file_bytes", "_busy", "_stations"),
    },
}

#: sliding window (seconds) the live device-busy fraction is computed
#: over, and the label-cardinality cap for its per-worker gauge
BUSY_WINDOW_S = 60.0
MAX_BUSY_WORKERS = 128

#: `dprf check` threads analyzer: the flight-recorder stream is owned
#: by the recorder across attach/rotate cycles and released by
#: detach_file() (also called on re-attach).
RELEASES = {
    "TraceRecorder": {"_fh": "detach_file"},
}


def new_trace_id() -> str:
    """Trace id for one work-unit lifecycle (assigned at split time)."""
    return secrets.token_hex(8)


def new_span_id() -> str:
    return f"{_ID_PREFIX}-{next(_ID_COUNTER):x}"


def trace_path(session_path: str) -> str:
    """Span-stream location for a session journal path (idempotent:
    a path that already IS a trace stream is returned unchanged, so
    ``dprf trace export`` accepts either)."""
    if session_path.endswith(TRACE_SUFFIX):
        return session_path
    return session_path + TRACE_SUFFIX


def trace_enabled() -> bool:
    return envreg.get_bool(ENABLE_ENV)


def trace_max_bytes() -> Optional[int]:
    """Byte cap for the trace JSONL stream; 0 disables the cap (cap
    semantics shared with the telemetry snapshot cap)."""
    from dprf_tpu.telemetry.snapshot import cap_bytes
    return cap_bytes(envreg.get_int(MAX_BYTES_ENV, DEFAULT_MAX_BYTES))


def _clean_id(v) -> Optional[str]:
    if isinstance(v, str) and 0 < len(v) <= MAX_ID_LEN:
        return v
    return None


def _clean_attrs(attrs) -> dict:
    if not isinstance(attrs, dict):
        return {}
    out = {}
    for k, v in itertools.islice(attrs.items(), MAX_ATTRS):
        k = str(k)[:32]
        if isinstance(v, bool) or isinstance(v, (int, float)):
            out[k] = v
        elif isinstance(v, str):
            out[k] = v[:MAX_ATTR_STR]
        else:
            out[k] = str(v)[:MAX_ATTR_STR]
    return out


class _BusyTracker:
    """Incremental per-worker device-busy fraction over a sliding
    window -- ``trace.overlap_report``'s union-hole math kept LIVE:
    each sweep span folds its [ts, ts+dur) interval into the worker's
    merged interval set, intervals older than the window are pruned,
    and the fraction is covered / elapsed-in-window.  Driven only
    from TraceRecorder._append under its ``_lock``."""

    __slots__ = ("window", "procs")

    def __init__(self, window: float = BUSY_WINDOW_S):
        self.window = window
        #: proc -> sorted merged [[start, end], ...] within the window
        self.procs: dict = {}

    def _label(self, proc: str) -> str:
        if proc not in self.procs and len(self.procs) >= MAX_BUSY_WORKERS:
            return "_overflow"
        return proc

    def observe(self, proc: str, start: float, end: float,
                now: float) -> tuple:
        """Fold one sweep interval in; returns (gauge label, updated
        fraction)."""
        proc = self._label(proc)
        iv = self.procs.setdefault(proc, [])
        lo, hi = 0, len(iv)
        while lo < hi:
            mid = (lo + hi) // 2
            if iv[mid][0] < start:
                lo = mid + 1
            else:
                hi = mid
        i = lo
        if i > 0 and iv[i - 1][1] >= start:
            i -= 1
            iv[i][1] = max(iv[i][1], end)
        else:
            iv.insert(i, [start, end])
        j = i + 1
        while j < len(iv) and iv[j][0] <= iv[i][1]:
            iv[i][1] = max(iv[i][1], iv[j][1])
            j += 1
        del iv[i + 1:j]
        return proc, self._fraction(iv, now)

    def _fraction(self, iv: list, now: float) -> float:
        """Prune to the window, then covered / elapsed where elapsed
        runs from max(window start, first retained sweep) to now --
        so a run younger than the window is not under-read."""
        floor = now - self.window
        while iv and iv[0][1] <= floor:
            iv.pop(0)
        if iv and iv[0][0] < floor:
            iv[0][0] = floor
        if not iv:
            return 0.0
        covered = sum(e - s for s, e in iv)
        span = now - max(floor, iv[0][0])
        if span <= 0:
            return 1.0
        return min(1.0, covered / span)

    def fractions(self, now: float) -> dict:
        return {proc: round(self._fraction(iv, now), 4)
                for proc, iv in self.procs.items()}


#: seconds the open stations' CHILDREN took, innermost last, per
#: thread (stations nest on the thread that opened them, whichever
#: recorder holds their table)
_open_stations = threading.local()
_NO_STATION = contextlib.nullcontext()
_perf = time.perf_counter


def _annotation_type():
    """``jax.profiler.TraceAnnotation`` where this process has already
    imported jax, else None: a coordinator that never touches jax
    must not import it to time its ledger."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return getattr(profiler, "TraceAnnotation", None)


class _Station:
    """One open station (``TraceRecorder.station``)."""

    __slots__ = ("_recorder", "_name", "_annotation", "_t0")

    def __init__(self, recorder, name, annotation):
        self._recorder = recorder
        self._name = name
        self._annotation = annotation

    def __enter__(self):
        try:
            _open_stations.stack.append(0.0)
        except AttributeError:
            _open_stations.stack = [0.0]
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = _perf()

    def __exit__(self, *exc):
        dur = _perf() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        stack = _open_stations.stack
        children = stack.pop()
        if stack:
            stack[-1] += dur
        rec = self._recorder
        with rec._lock:
            row = rec._stations.get(self._name)
            if row is None:
                row = rec._stations[self._name] = [0, 0.0]
            row[0] += 1
            row[1] += dur - children


class TraceRecorder:
    """Bounded flight-recorder ring + optional JSONL stream.

    Thread-safe; ``record`` is the only hot-path entry and returns the
    span dict (so a worker can ship it over RPC) or None when tracing
    is disabled.  One recorder per process is the normal shape (the
    module-level DEFAULT); tests construct their own.
    """

    def __init__(self, capacity: int = 4096, clock=time.time,
                 enabled: Optional[bool] = None, proc: str = "local",
                 registry=None):
        self._ring: deque = deque(maxlen=max(16, int(capacity)))
        self._clock = clock
        self.enabled = trace_enabled() if enabled is None else enabled
        self.proc = proc
        self._lock = threading.Lock()
        self._fh = None
        self._path: Optional[str] = None
        self._max_bytes: Optional[int] = None
        self._file_bytes = 0
        #: live device-utilization state: sweep spans fold into a
        #: sliding-window interval union per worker (ISSUE 9)
        self._busy = _BusyTracker()
        #: station name -> [times opened, self seconds]
        self._stations: dict = {}
        from dprf_tpu.telemetry import get_registry
        self._m_spans = get_registry(registry).counter(
            "dprf_trace_spans_total",
            "lifecycle spans recorded into the flight recorder")
        #: the trace_drops alert condition (telemetry/alerts.py): a
        #: sustained nonzero rate means the timeline is lying by
        #: omission -- spans over the ingest bound, failing
        #: sanitization, or lost to a dead stream write
        self._m_dropped = get_registry(registry).counter(
            "dprf_trace_spans_dropped_total",
            "spans dropped at remote ingest (over the per-message "
            "bound or failing sanitization) or lost to a failed "
            "trace-stream write")
        self._g_busy = get_registry(registry).gauge(
            "dprf_device_busy_fraction",
            "fraction of the sliding window each worker's sweep "
            "spans cover (union holes = device idle; the live form "
            "of tools/trace_overlap.py)", labelnames=("worker",))

    # -- recording -------------------------------------------------------

    def record(self, name: str, dur: float = 0.0, ts: Optional[float] = None,
               trace: Optional[str] = None, parent: Optional[str] = None,
               proc: Optional[str] = None,
               **attrs) -> Optional[dict]:
        """Record one span; ``ts`` defaults to now - dur (i.e. the
        caller measured ``dur`` ending now).  Returns the span dict
        (shippable over RPC) or None when disabled."""
        if not self.enabled:
            return None
        if ts is None:
            ts = self._clock() - dur
        span = {"name": name, "ts": round(float(ts), 6),
                "dur": round(float(dur), 6), "trace": trace,
                "parent": parent, "span": new_span_id(),
                "proc": proc if proc is not None else self.proc,
                "attrs": attrs}
        self._append(span)
        return span

    def station(self, name: str, unit: Optional[int] = None):
        """Context manager around one station of a unit's way through
        the sweep loop (``STATIONS``).  While a profiler trace runs it
        is an event ``dprf:<name>`` of the host's plane, carrying the
        unit's id, on the trace's own clock; with none running the
        annotation is a flag test.  Either way it adds one to the
        station's count and its self seconds to ``station_table()``.
        Disabled (``DPRF_TRACE=0``) it does nothing."""
        if not self.enabled:
            return _NO_STATION
        label = _STATION_LABELS[name]
        annotation = _annotation_type()
        if annotation is not None:
            annotation = (annotation(label) if unit is None
                          else annotation(label, unit=unit))
        return _Station(self, name, annotation)

    def station_table(self) -> dict:
        """{station: (times opened, self seconds)} since the recorder
        was made, in ``STATIONS`` order, stations never opened left
        out."""
        with self._lock:
            return {name: tuple(self._stations[name])
                    for name in STATIONS if name in self._stations}

    def ingest(self, spans, proc: Optional[str] = None,
               sent_at=None, limit: Optional[int] = None) -> int:
        """Fold REMOTE spans (shipped inside an RPC complete/fail
        message) into this recorder.  Client-controlled data, so
        sanitize hard: bounded count, declared span names only, scalar
        attrs, and ``proc`` forced to the server-known worker id when
        given -- a worker cannot impersonate another's timeline.
        ``limit`` overrides the per-message span bound (the
        ring-sized op_trace_push path); the per-unit default stays
        MAX_INGEST_SPANS.

        ``sent_at`` is the sender's wall clock at send time: span
        timestamps are REBASED by (our now - sent_at), so a fleet
        whose hosts disagree by NTP drift still renders one coherent
        timeline (residual error = one-way network latency, seconds of
        drift otherwise)."""
        if not self.enabled or not isinstance(spans, list):
            return 0
        offset = 0.0
        if isinstance(sent_at, (int, float)):
            offset = self._clock() - float(sent_at)
        n = 0
        bound = limit if limit is not None else MAX_INGEST_SPANS
        dropped = max(0, len(spans) - bound)
        for s in spans[:bound]:
            if not isinstance(s, dict):
                dropped += 1
                continue
            name = s.get("name")
            if not isinstance(name, str) or name not in SPAN_NAMES:
                dropped += 1
                continue
            try:
                ts = float(s.get("ts", 0.0))
                dur = float(s.get("dur", 0.0))
            except (TypeError, ValueError):
                dropped += 1
                continue
            clean = {"name": name, "ts": round(ts + offset, 6),
                     "dur": round(dur, 6),
                     "trace": _clean_id(s.get("trace")),
                     "parent": _clean_id(s.get("parent")),
                     "span": _clean_id(s.get("span")) or new_span_id(),
                     "proc": str(proc if proc is not None
                                 else s.get("proc", "?"))[:MAX_ID_LEN],
                     "attrs": _clean_attrs(s.get("attrs"))}
            self._append(clean)
            n += 1
        if dropped:
            self._m_dropped.inc(dropped)
        return n

    def _append(self, span: dict) -> None:
        self._m_spans.inc()
        busy = None
        lost_write = False
        with self._lock:
            if span["name"] == "sweep" and span["dur"] > 0:
                # live utilization: fold the sweep interval into the
                # worker's window union (both local records and
                # coordinator-rebased ingests land here)
                busy = self._busy.observe(
                    str(span.get("proc") or "?"), span["ts"],
                    span["ts"] + span["dur"], self._clock())
            self._ring.append(span)
            if self._fh is not None:
                try:
                    data = json.dumps(span, separators=(",", ":"),
                                      default=str) + "\n"
                    if (self._max_bytes is not None
                            and self._file_bytes
                            and self._file_bytes + len(data)
                            > self._max_bytes):
                        self._rotate_locked()
                    if self._fh is not None:
                        self._fh.write(data)
                        self._fh.flush()
                        self._file_bytes += len(data)
                except OSError:
                    # a full disk must not kill the job, but a span
                    # the stream lost is a drop the alert engine
                    # should see (counted below, outside the lock)
                    lost_write = True
        if busy is not None:
            # gauge set OUTSIDE _lock: code holding _lock must never
            # call into other locked subsystems (lock-order contract)
            self._g_busy.set(busy[1], worker=busy[0])
        if lost_write:
            self._m_dropped.inc()

    def _rotate_locked(self) -> None:
        """Size-cap rotation: the stream moves to ``<path>.1``
        (replacing any previous rotation) and restarts -- a long serve
        session holds at most ~2x the cap on disk.  An unusable
        rotation target truncates in place instead (the cap must hold
        either way); an unreopenable path degrades to ring-only."""
        try:
            self._fh.close()
        except OSError:
            pass
        mode = "a"
        try:
            os.replace(self._path, self._path + ".1")
        except OSError:
            mode = "w"
        try:
            self._fh = open(self._path, mode, encoding="utf-8")
            self._file_bytes = 0
        except OSError:
            self._fh = None
    _rotate_locked._holds_lock = "_lock"   # only _append calls it

    # -- file stream -----------------------------------------------------

    def attach_file(self, path: str,
                    max_bytes: Optional[int] = None) -> "TraceRecorder":
        """Stream subsequent spans to a JSONL file (the session's
        flight-recorder journal).  Ring contents recorded BEFORE the
        attach are not replayed -- the file is this run's record, the
        ring is the process's."""
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
            self._path = path
            self._max_bytes = (trace_max_bytes() if max_bytes is None
                               else (max_bytes or None))
            self._fh = open(path, "a", encoding="utf-8")
            try:
                self._file_bytes = os.path.getsize(path)
            except OSError:
                self._file_bytes = 0
        return self

    def detach_file(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
            self._fh = None
            self._path = None

    # -- reads -----------------------------------------------------------

    def tail(self, n: int = 200, trace: Optional[str] = None) -> list:
        """The most recent n spans (optionally one trace's), oldest
        first -- the op_trace_tail payload."""
        with self._lock:
            items = list(self._ring)
        if trace is not None:
            items = [s for s in items if s.get("trace") == trace]
        return [dict(s) for s in items[-max(1, int(n)):]]

    def tail_after(self, since: Optional[str], n: int = 200,
                   trace: Optional[str] = None) -> tuple:
        """Incremental flight-recorder read (``dprf top --follow``):
        (spans recorded AFTER the span id ``since``, resync flag),
        oldest first.  When ``since`` is unknown -- first call, or the
        ring wrapped past it -- the plain tail comes back with
        resync=True and the caller must REPLACE its buffer, not
        append."""
        with self._lock:
            items = list(self._ring)
        idx = None
        if since:
            # scan from the new end: the cursor is almost always near it
            for i in range(len(items) - 1, -1, -1):
                if items[i].get("span") == since:
                    idx = i
                    break
        resync = idx is None
        out = items if resync else items[idx + 1:]
        if trace is not None:
            out = [s for s in out if s.get("trace") == trace]
        n = max(1, int(n))
        if len(out) > n:
            # the increment itself overflows the window: the caller
            # cannot stitch it onto its buffer without a silent hole,
            # so this is a resync too (replace, newest n)
            out = out[-n:]
            resync = True
        return [dict(s) for s in out], resync

    def head_after(self, since: Optional[str], n: int = 200) -> tuple:
        """Forward pager for a FULL ring dump (op_trace_pull): (up to
        n spans recorded after span id ``since``, resync flag), oldest
        first, starting at the ring's OLDEST span when ``since`` is
        None.  Unlike ``tail_after`` -- which serves live follow and
        clamps to the newest window -- an oversized remainder pages
        from the front; the caller walks forward until a short page.
        An unknown cursor (the ring wrapped past it) restarts from the
        oldest with resync=True: the caller replaces its buffer."""
        with self._lock:
            items = list(self._ring)
        idx = None
        if since:
            # scan from the new end: the cursor is usually near it
            for i in range(len(items) - 1, -1, -1):
                if items[i].get("span") == since:
                    idx = i
                    break
        resync = since is not None and idx is None
        out = items if idx is None else items[idx + 1:]
        return [dict(s) for s in out[:max(1, int(n))]], resync

    def busy_fractions(self) -> dict:
        """{worker: live busy fraction} over the sliding window,
        recomputed against the current clock (so an idle fleet's
        fractions decay between sweeps) -- the op_trace_tail status
        payload and the ``dprf top`` header read this."""
        with self._lock:
            return self._busy.fractions(self._clock())

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._busy.procs.clear()
            self._stations.clear()


#: process-wide recorder, like telemetry.DEFAULT: library code with no
#: recorder threaded through records here
DEFAULT_TRACER = TraceRecorder()


def get_tracer(recorder: Optional[TraceRecorder] = None) -> TraceRecorder:
    return recorder if recorder is not None else DEFAULT_TRACER


def format_stations(table: dict, since: Optional[dict] = None) -> str:
    """``lease:0.016,submit:1.920,..``: self seconds by station, for a
    job's ``ran`` line; ``since`` is the table as the job began.
    Empty where no station was opened."""
    since = since or {}
    return ",".join(
        f"{name}:{seconds - since.get(name, (0, 0.0))[1]:.3f}"
        for name, (count, seconds) in table.items()
        if count > since.get(name, (0, 0.0))[0])


def span_id(span: Optional[dict]) -> Optional[str]:
    """The id of a recorded span, tolerating a disabled recorder's
    None."""
    return span["span"] if span else None


# ---------------------------------------------------------------------------
# trace-file loading + analysis (dprf trace export, tests)

def load_trace(path: str) -> list:
    """Read a span stream back (rotated ``.1`` part first, torn tail
    lines skipped), sorted by start time."""
    spans = []
    for p in (path + ".1", path):
        if not os.path.exists(p):
            continue
        with open(p, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    s = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(s, dict) and isinstance(s.get("name"), str) \
                        and "ts" in s:
                    spans.append(s)
    spans.sort(key=lambda s: (s.get("ts", 0.0), s.get("span") or ""))
    return spans


def lifecycle_report(spans: list) -> dict:
    """Reconstruct per-unit lifecycles: for every trace id, the ordered
    span names, the procs that touched it, lease/terminal accounting,
    and ORPHANS (spans whose parent id never appears in their trace --
    a broken context-propagation link)."""
    traces: dict = {}
    for s in spans:
        tid = s.get("trace")
        if not tid:
            continue
        t = traces.setdefault(tid, {"spans": [], "ids": set()})
        t["spans"].append(s)
        sid = s.get("span")
        if sid:
            t["ids"].add(sid)
    details = {}
    orphans = 0
    incomplete = []
    for tid, t in traces.items():
        names = [s["name"] for s in t["spans"]]
        t_orphans = [s.get("span") for s in t["spans"]
                     if s.get("parent") and s["parent"] not in t["ids"]]
        orphans += len(t_orphans)
        terminal = any(n in ("complete", "park") for n in names)
        if not terminal:
            incomplete.append(tid)
        details[tid] = {
            "names": names,
            "procs": sorted({str(s.get("proc")) for s in t["spans"]}),
            "leases": names.count("lease"),
            "reissues": names.count("reissue"),
            "terminal": terminal,
            "orphans": t_orphans,
        }
    return {"traces": len(traces), "spans": len(spans),
            "orphans": orphans, "incomplete": sorted(incomplete),
            "details": details}


def overlap_report(spans: list) -> dict:
    """Per-worker device-idle analysis of a span stream -- the
    ``tools/trace_overlap.py`` report, and the ROADMAP "span-level
    assertions back perf PRs" item.

    For every proc with ``sweep`` spans, the gaps are the HOLES in the
    union of its sweep intervals: walking spans by start time with a
    running coverage frontier ``end = max(end, span.ts + span.dur)``,
    a span starting past the frontier opens a device-idle hole of
    ``span.ts - end`` seconds.  (Pipelined sweeps overlap -- several
    units ride the stream at once and an ahead-batch's sweeps share a
    start time -- so pairwise prev/next differences would misread tied
    orderings; union holes are order-stable.)  On a pipelined worker
    the max hole must stay below the RPC round trip; the serial loop
    idles ~2 RTT per unit.  ``overlapped`` counts sweeps that started
    before the coverage frontier (pipeline overlap events), and
    ``complete_overlaps`` counts sweeps that started before the
    coordinator recorded the PREVIOUS unit's ``complete`` span --
    proof the report round trip overlapped device work.  (Both clocks
    are coordinator-rebased at ingest, so every comparison is within
    one timeline.)"""
    completes: dict = {}
    for s in spans:
        if s.get("name") == "complete":
            u = (s.get("attrs") or {}).get("unit")
            if u is not None:
                completes[u] = float(s.get("ts", 0.0))
    by_proc: dict = {}
    for s in spans:
        if s.get("name") == "sweep":
            by_proc.setdefault(str(s.get("proc")), []).append(s)
    workers = {}
    for proc, sw in by_proc.items():
        sw.sort(key=lambda s: float(s.get("ts", 0.0)))
        gaps, overlapped, c_overlaps = [], 0, 0
        end = None
        for i, s in enumerate(sw):
            ts = float(s.get("ts", 0.0))
            if end is not None:
                if ts > end:
                    gaps.append(ts - end)
                else:
                    overlapped += 1
            if i > 0:
                ct = completes.get(
                    (sw[i - 1].get("attrs") or {}).get("unit"))
                if ct is not None and ts < ct:
                    c_overlaps += 1
            send = ts + float(s.get("dur", 0.0))
            end = send if end is None else max(end, send)
        workers[proc] = {
            "sweeps": len(sw),
            "sweep_s": round(sum(float(s.get("dur", 0.0))
                                 for s in sw), 6),
            "gaps": len(sw) - 1,
            "holes": len(gaps),
            "idle_s": round(sum(gaps), 6),
            "max_gap_s": round(max(gaps), 6) if gaps else 0.0,
            "overlapped": overlapped,
            "complete_overlaps": c_overlaps,
        }
    return {"workers": workers,
            "max_gap_s": round(max(
                (w["max_gap_s"] for w in workers.values()),
                default=0.0), 6)}


def export_chrome_trace(spans: list) -> dict:
    """Spans -> Chrome-trace JSON (the "JSON Array Format" with
    metadata events), loadable in Perfetto / chrome://tracing.

    Mapping: pid = actor (coordinator / worker id / local), tid = one
    work-unit trace within that actor -- so a reissued unit renders as
    aligned lanes across the workers that touched it.  Timestamps are
    microseconds relative to the earliest span (absolute epoch kept in
    ``otherData``)."""
    pids: dict = {}
    tids: dict = {}
    events = []

    def pid_of(proc: str) -> int:
        if proc not in pids:
            pids[proc] = len(pids) + 1
            events.append({"name": "process_name", "ph": "M", "cat": "__metadata",
                           "pid": pids[proc], "tid": 0,
                           "args": {"name": proc}})
        return pids[proc]

    def tid_of(pid: int, tid_key) -> int:
        key = (pid, tid_key)
        if key not in tids:
            tids[key] = len(tids) + 1
            label = (f"unit trace {str(tid_key)[:10]}"
                     if tid_key != "-" else "untraced")
            events.append({"name": "thread_name", "ph": "M",
                           "cat": "__metadata", "pid": pid,
                           "tid": tids[key], "args": {"name": label}})
        return tids[key]

    t0 = min((float(s.get("ts", 0.0)) for s in spans), default=0.0)
    for s in spans:
        proc = str(s.get("proc") or "?")
        pid = pid_of(proc)
        tid = tid_of(pid, s.get("trace") or "-")
        dur_us = max(float(s.get("dur", 0.0)) * 1e6, 1.0)
        args = dict(s.get("attrs") or {})
        args.update({"trace": s.get("trace"), "span": s.get("span"),
                     "parent": s.get("parent")})
        events.append({"name": s["name"], "cat": "dprf", "ph": "X",
                       "ts": round((float(s["ts"]) - t0) * 1e6, 3),
                       "dur": round(dur_us, 3),
                       "pid": pid, "tid": tid, "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"tool": "dprf trace export",
                          "t0_epoch_s": t0, "spans": len(spans)}}


# ---------------------------------------------------------------------------
# dprf top rendering

def _fmt_age(s: float) -> str:
    if s < 0:
        return "expired"
    if s < 120:
        return f"{s:.0f}s"
    return f"{s / 60:.1f}m"


def _fmt_bytes(v) -> str:
    if not isinstance(v, (int, float)) or v <= 0:
        return "-"
    for unit, div in (("G", 1 << 30), ("M", 1 << 20), ("K", 1 << 10)):
        if v >= div:
            return f"{v / div:.1f}{unit}"
    return str(int(v))


def render_top(resp: dict, prev: Optional[tuple] = None) -> str:
    """One frame of the ``dprf top`` live view from an op_trace_tail
    response.  ``prev`` is (monotonic_time, status) of the previous
    frame, used for the interval throughput estimate."""
    status = resp.get("status") or {}
    spans = resp.get("spans") or []
    leases = resp.get("leases") or []
    done = status.get("done", 0)
    total = max(status.get("total", 0), 1)
    lines = []
    rate = ""
    if prev:
        t_prev, s_prev = prev
        dt = time.monotonic() - t_prev
        if dt > 0:
            rate = f" | {max(done - s_prev.get('done', 0), 0) / dt:,.0f}/s"
    state = "FINISHED" if status.get("stop") else "running"
    # live utilization & roofline distance (ISSUE 9): mean sweep-span
    # window coverage across workers, and the per-engine fraction of
    # the int32 roofline ceiling the fleet's throughput reaches
    busy = status.get("busy") or {}
    busy_s = ""
    if busy:
        busy_s = (f" | busy {100.0 * sum(busy.values()) / len(busy):.0f}%"
                  f" ({len(busy)}w)")
    roofline = status.get("roofline") or {}
    roof_s = ""
    if roofline:
        roof_s = " | roofline " + " ".join(
            f"{e}:{f:.2f}" for e, f in sorted(roofline.items()))
    # fleet HBM header (ISSUE 13): summed worker memory from the
    # heartbeat payloads; absent on fleets without memory stats
    hbm = status.get("hbm") or {}
    hbm_s = ""
    if hbm.get("limit"):
        hbm_s = (f" | hbm {_fmt_bytes(hbm.get('in_use', 0))}"
                 f"/{_fmt_bytes(hbm['limit'])}"
                 f" ({hbm.get('workers', 0)}w)")
    lines.append(
        f"dprf top — {state} | found {status.get('found', 0)}"
        f"/{status.get('targets', '?')} | "
        f"{100.0 * done / total:.2f}% covered | parked "
        f"{status.get('parked', 0)} | elapsed "
        f"{status.get('elapsed', 0.0):.0f}s{rate}{busy_s}{roof_s}"
        f"{hbm_s}")
    quarantined = status.get("quarantined") or []
    if quarantined:
        lines.append(f"quarantined workers: {', '.join(quarantined)}")
    # fleet health plane (ISSUE 10): firing alerts lead the frame --
    # an operator watching top must not need a second terminal to
    # learn the fleet is on fire
    firing = status.get("alerts") or []
    if firing:
        lines.append(f"FIRING ALERTS: {', '.join(firing)}")
    # per-job table (multi-tenant serve plane): one row per scheduler
    # job once the coordinator holds more than the default job
    jobs = status.get("jobs") or []
    if len(jobs) > 1:
        lines.append("")
        lines.append(f"{'JOB':6s} {'OWNER':12s} {'PRIO':>4s} "
                     f"{'STATE':10s} {'COVERED':>20s} {'FOUND':>7s} "
                     f"{'OUT':>4s} {'LEASES':>7s}")
        for j in jobs:
            cov = f"{j.get('done', 0)}/{j.get('total', 0)}"
            fnd = f"{j.get('found', 0)}/{j.get('targets', 0)}"
            lines.append(
                f"{str(j.get('id'))[:6]:6s} "
                f"{str(j.get('owner'))[:12]:12s} "
                f"{j.get('priority', 1):>4d} "
                f"{str(j.get('state'))[:10]:10s} {cov:>20s} "
                f"{fnd:>7s} {j.get('outstanding', 0):>4d} "
                f"{j.get('leases', 0):>7d}")
    # per-worker table: current lease + the worker's most recent span,
    # GROUPED by the job each worker is currently leased to (so a
    # multi-tenant fleet reads per job), with the live busy fraction
    last_span: dict = {}
    for s in spans:
        last_span[str(s.get("proc"))] = s
    by_worker = {str(l.get("worker")): l for l in leases}
    # workers known only to the health plane (heartbeating while
    # holding no lease -- or missing/dead) still get a row: a silent
    # worker that vanished from the lease table is exactly the one
    # the operator is looking for
    health = status.get("health") or {}
    workers = sorted(set(by_worker)
                     | set(health)
                     | {p for p in last_span
                        if p not in ("coordinator",)})
    # grouping key: the worker's current job first ("-" for idle
    # workers, sorted last), then worker id -- stable per-job blocks
    workers.sort(key=lambda w: (
        str((by_worker.get(w) or {}).get("job", "~")), w))
    mem = status.get("mem") or {}
    # kernel-profiling plane (ISSUE 15): last capture per worker --
    # the coordinator's pushed-summary table, with the heartbeat
    # payload's profile_ts/profile_trigger as the fallback for
    # env-local captures that never pushed
    profiles = status.get("profiles") or {}
    lines.append("")
    lines.append(f"{'WORKER':20s} {'JOB':>5s} {'STATE':10s} "
                 f"{'UNIT':>8s} {'RANGE':>24s} {'LEASE':>8s} "
                 f"{'BUSY':>5s} {'MEM':>6s} {'HEALTH':>8s} "
                 f"{'PROF':>14s} {'LAST SPAN':>10s}")
    # ages against the COORDINATOR's clock (shipped in status): the
    # spans carry its wall time, and the viewer's clock may be skewed
    now = status.get("now") or time.time()
    for w in workers:
        lease = by_worker.get(w)
        s = last_span.get(w)
        state = s["name"] if s else ("sweep" if lease else "idle")
        # the unit column names the owning job too (unit ids are only
        # unique within a job's ledger)
        jid = str(lease.get("job", "?")) if lease else "-"
        unit = f"{jid}#{lease['unit']}" if lease else "-"
        rng = (f"[{lease['start']},{lease['start'] + lease['length']})"
               if lease else "-")
        dl = _fmt_age(lease["deadline_s"]) if lease else "-"
        b = busy.get(w)
        b_s = f"{100.0 * b:.0f}%" if b is not None else "-"
        hw = str(health.get(w) or "-")[:8]
        m_s = _fmt_bytes(mem.get(w))
        p = profiles.get(w)
        p_ts, p_trig = ((p.get("ts"), p.get("trigger"))
                        if isinstance(p, dict) else (None, None))
        prof = (f"{_fmt_age(max(0.0, now - p_ts))}/"
                f"{str(p_trig or '?')[:8]}"
                if isinstance(p_ts, (int, float)) else "-")
        age = (_fmt_age(max(0.0, now - (s.get("ts", now)
                                        + s.get("dur", 0.0))))
               if s else "-")
        lines.append(f"{w[:20]:20s} {jid[:5]:>5s} {state:10s} "
                     f"{unit:>8s} {rng:>24s} {dl:>8s} {b_s:>5s} "
                     f"{m_s:>6s} {hw:>8s} {prof:>14s} {age:>10s}")
    lines.append("")
    lines.append("recent spans:")
    for s in spans[-8:]:
        tid = (s.get("trace") or "-")[:8]
        lines.append(f"  {s['name']:11s} trace={tid:8s} "
                     f"proc={str(s.get('proc'))[:16]:16s} "
                     f"dur={s.get('dur', 0.0):.3f}s")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# opt-in jax.profiler wrapping of sweep loops

def jax_profile_ctx(log=None):
    """``DPRF_JAX_PROFILE=<dir>``: a jax.profiler trace context for a
    sweep loop, now owned by telemetry/profiler.py's single-flight
    ProfileCapture (jax allows ONE active trace; the ``--profile``
    flag and on-demand capture windows share the same slot).  Kept
    here as a re-export for the loop call sites."""
    from dprf_tpu.telemetry import profiler as profiler_mod
    return profiler_mod.jax_profile_ctx(log=log)
