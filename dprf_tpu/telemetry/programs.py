"""Compiled-program registry (ISSUE 13): XLA-derived cost and memory
introspection for every step the fleet compiles.

The roofline story used to end at a hand-maintained
``OPS_PER_CANDIDATE`` table in telemetry/perf.py covering five fast
engines -- every other engine reported no roofline at all, and nothing
in the stack knew how much HBM a compiled step actually needs.  The
compiler knows both exactly: JAX's AOT surface exposes
``compiled.cost_analysis()`` (optimized-HLO flops / bytes accessed)
and ``compiled.memory_analysis()`` (argument / output / temp / code
bytes).  This module captures those numbers at every compile site --
worker warmup, ``aot_compile`` (prewarm), the sharded superstep, tune
rungs, bench -- into one process-wide registry:

  - ``register_program(...)``   called from the compile sites with the
        step + its warmup args.  Registration is CHEAP (no analysis):
        the expensive part is deferred so the hot warmup path never
        pays a second compile it didn't ask for.
  - ``analyze_pending(...)``    runs the deferred analysis:
        ``step.lower(args)`` (a cached trace after warmup, ~free) ->
        ``lowered.compile()`` (served by the persistent compilation
        cache wherever the CLI enabled it) -> cost/memory analysis +
        the program FINGERPRINT (sha256 over the lowered module text,
        backend, and jax version -- the same inputs the XLA compile
        cache keys on).  Called from the overlapped-warmup background
        thread, the worker heartbeat loop, tune, prewarm, and bench --
        never from a unit's dispatch path.
  - ``analyzed_ops_per_candidate(engine)``  the derived roofline
        input: optimized flops / candidates-per-dispatch of the
        engine's per-batch program.  telemetry/perf.py consults this
        FIRST and keeps the hand table only as a cross-check.
  - ``snapshot()`` / ``ingest(...)``  the wire surface: workers ship
        their analyzed records inside heartbeats; the coordinator
        merges them (bounded, sanitized) so ``op_programs`` / ``dprf
        programs`` shows the fleet's program table, not one process's.

Degradation contract: every jax call here is best-effort.  A backend
without cost analysis or a step that cannot AOT-lower
loses the analyzed record -- never the job.  ``DPRF_PROGRAM_ANALYSIS=0``
is the kill switch (the hand roofline models keep working).
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Optional

from dprf_tpu.telemetry import get_registry
from dprf_tpu.utils import env as envreg

#: kill switch for the deferred analysis (registration stays cheap
#: either way; with analysis off the registry simply never fills)
ANALYSIS_ENV = "DPRF_PROGRAM_ANALYSIS"

#: wire-record fields a coordinator accepts from a worker heartbeat
#: (client-controlled data: unknown keys drop, strings are bounded)
WIRE_KEYS = ("key", "fingerprint", "engine", "attack", "batch",
             "flops", "bytes_accessed", "flops_per_candidate",
             "peak_bytes", "argument_bytes", "output_bytes",
             "generated_code_bytes", "proc")
MAX_WIRE_STR = 128
#: records one ingest call may merge (heartbeats are unauthenticated
#: on open fleets; a junk worker must not grow coordinator memory)
MAX_INGEST = 256
#: total records a registry holds (fingerprint-keyed; a fleet compiles
#: a bounded program set, so hitting this means id churn, not scale)
MAX_RECORDS = 1024

#: lock-discipline declaration (`dprf check` locks analyzer): the
#: record/pending tables are written from warmup threads, heartbeat
#: loops, and RPC handler threads at once.
GUARDED_BY = {
    "ProgramRegistry": {"_lock": ("_records", "_pending", "_seq")},
}


def analysis_enabled() -> bool:
    return envreg.get_bool(ANALYSIS_ENV)


class ProgramRecord:
    """One analyzed executable: identity + compiler-derived costs."""

    __slots__ = ("key", "fingerprint", "engine", "attack", "batch",
                 "flops", "bytes_accessed", "peak_bytes",
                 "argument_bytes", "output_bytes",
                 "generated_code_bytes", "analyzed_at", "proc", "seq")

    def __init__(self, key, fingerprint, engine, attack, batch,
                 flops=None, bytes_accessed=None, peak_bytes=None,
                 argument_bytes=None, output_bytes=None,
                 generated_code_bytes=None, proc="local", seq=0):
        self.key = key
        self.fingerprint = fingerprint
        self.engine = engine
        self.attack = attack
        self.batch = int(batch or 0)
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.peak_bytes = peak_bytes
        self.argument_bytes = argument_bytes
        self.output_bytes = output_bytes
        self.generated_code_bytes = generated_code_bytes
        self.analyzed_at = time.time()
        self.proc = proc
        self.seq = seq

    @property
    def flops_per_candidate(self) -> Optional[float]:
        if not self.flops or self.batch <= 0:
            return None
        return self.flops / self.batch

    @property
    def bytes_per_candidate(self) -> Optional[float]:
        if not self.bytes_accessed or self.batch <= 0:
            return None
        return self.bytes_accessed / self.batch

    def total_peak_bytes(self) -> Optional[int]:
        """Peak device footprint of one dispatch: arguments + outputs
        + XLA temp allocations (the number an HBM budget reasons
        about; code size is reported separately -- it lives in HBM too
        but is shared across dispatches)."""
        parts = [self.argument_bytes, self.output_bytes,
                 self.peak_bytes]
        if all(p is None for p in parts):
            return None
        return int(sum(p or 0 for p in parts))

    def as_dict(self) -> dict:
        return {
            "key": self.key,
            "fingerprint": self.fingerprint,
            "engine": self.engine,
            "attack": self.attack,
            "batch": self.batch,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "flops_per_candidate": self.flops_per_candidate,
            "peak_bytes": self.peak_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "total_peak_bytes": self.total_peak_bytes(),
            "proc": self.proc,
        }


def _cost_dict(compiled) -> dict:
    """Normalized compiled.cost_analysis(): jax has returned both a
    dict and a single-element list of dicts across versions."""
    try:
        ca = compiled.cost_analysis()
    except Exception:   # noqa: BLE001 -- backend without cost analysis
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca if isinstance(ca, dict) else {}


def _memory_fields(compiled) -> dict:
    """compiled.memory_analysis() -> our field names; {} when the
    backend has no memory analysis (the documented None-degrade)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:   # noqa: BLE001
        return {}
    if ma is None:
        return {}
    out = {}
    for ours, theirs in (("peak_bytes", "temp_size_in_bytes"),
                         ("argument_bytes", "argument_size_in_bytes"),
                         ("output_bytes", "output_size_in_bytes"),
                         ("generated_code_bytes",
                          "generated_code_size_in_bytes")):
        v = getattr(ma, theirs, None)
        if isinstance(v, (int, float)):
            out[ours] = int(v)
    return out


def program_fingerprint(lowered) -> str:
    """sha256 over the lowered module text + backend + jax version --
    the same inputs the persistent XLA compile cache keys on, so two
    processes compiling the identical step agree on the fingerprint
    without sharing memory."""
    import jax
    h = hashlib.sha256()
    try:
        h.update(lowered.as_text().encode())
    except Exception:   # noqa: BLE001 -- a module that cannot print
        h.update(repr(lowered).encode())
    h.update(jax.default_backend().encode())
    h.update(jax.__version__.encode())
    return h.hexdigest()[:32]


class ProgramRegistry:
    """Process-wide table of compiled-program records + the pending
    (registered-but-unanalyzed) compile sites."""

    def __init__(self, registry=None):
        self._lock = threading.Lock()
        #: fingerprint -> ProgramRecord
        self._records: dict = {}
        #: (engine, attack, batch) -> (step, args): deferred analysis
        self._pending: dict = {}
        self._seq = 0
        self._metrics = registry

    def _gauges(self):
        m = get_registry(self._metrics)
        return m.gauge(
            "dprf_program_peak_bytes",
            "per-dispatch device footprint (arguments + outputs + XLA "
            "temp) of the engine's analyzed per-batch program "
            "(compiled.memory_analysis; absent on backends without "
            "memory analysis)", labelnames=("engine", "attack"))

    def register(self, engine: str, attack: str, batch: int,
                 step=None, args=None, compiled=None,
                 lowered=None) -> None:
        """Record a compile site.  Cheap: analysis is deferred unless
        the caller already holds the Compiled object (aot_compile,
        prewarm), in which case reading the analysis costs ~ms --
        pass ``lowered`` alongside so the record carries the REAL
        module fingerprint (cross-process dedup depends on it)."""
        if not analysis_enabled():
            return
        key = (str(engine), str(attack), int(batch or 0))
        if compiled is not None:
            self._analyze_one(key, compiled=compiled, lowered=lowered)
            return
        if step is None or args is None:
            return
        with self._lock:
            if key in self._pending or any(
                    r.engine == key[0] and r.attack == key[1]
                    and r.batch == key[2]
                    for r in self._records.values()):
                return
            self._pending[key] = (step, args)

    def analyze_pending(self) -> int:
        """Run the deferred analysis for every pending site; returns
        how many records landed.  The compile this triggers is served
        by the persistent compilation cache wherever the CLI enabled
        it (the step was just compiled by warmup); never called from a
        dispatch path."""
        if not analysis_enabled():
            return 0
        with self._lock:
            todo = list(self._pending.items())
            self._pending.clear()
        n = 0
        for key, (step, args) in todo:
            if self._analyze_one(key, step=step, args=args):
                n += 1
        return n

    def _analyze_one(self, key, step=None, args=None,
                     compiled=None, lowered=None) -> bool:
        engine, attack, batch = key
        fingerprint = None
        try:
            if compiled is None:
                lower = getattr(step, "lower", None)
                if lower is None:
                    return False
                lowered = lower(*args)
            if lowered is not None:
                fingerprint = program_fingerprint(lowered)
                with self._lock:
                    if fingerprint in self._records:
                        return False
            if compiled is None:
                compiled = lowered.compile()
            cost = _cost_dict(compiled)
            mem = _memory_fields(compiled)
        except Exception:   # noqa: BLE001 -- analysis is best-effort:
            # a backend that cannot lower/compile/analyze loses the
            # record, never the job
            return False
        if fingerprint is None:
            # last resort (a Compiled with no Lowered in hand): the
            # shape key stands in -- same-shape programs can alias
            h = hashlib.sha256(
                f"{engine}|{attack}|{batch}".encode())
            fingerprint = "c-" + h.hexdigest()[:30]
        flops = cost.get("flops")
        rec = ProgramRecord(
            key=f"{engine}|{attack}|b{batch}",
            fingerprint=fingerprint, engine=engine, attack=attack,
            batch=batch,
            flops=float(flops) if isinstance(flops, (int, float))
            and flops > 0 else None,
            bytes_accessed=cost.get("bytes accessed"), **mem)
        self._store(rec)
        return True

    def _store(self, rec: ProgramRecord) -> None:
        with self._lock:
            if len(self._records) >= MAX_RECORDS and \
                    rec.fingerprint not in self._records:
                return
            self._seq += 1
            rec.seq = self._seq
            self._records[rec.fingerprint] = rec
        peak = rec.total_peak_bytes()
        if peak is not None:
            self._gauges().set(peak, engine=rec.engine,
                               attack=rec.attack)

    def ingest(self, records, proc: str = "?",
               limit: int = MAX_INGEST) -> int:
        """Merge wire records a worker shipped (heartbeat payload).
        Client-controlled: bounded count, known keys only, strings
        truncated, numbers coerced -- junk drops silently."""
        if not isinstance(records, (list, tuple)):
            return 0
        n = 0
        for raw in records[:max(0, int(limit))]:
            if not isinstance(raw, dict):
                continue
            clean = {}
            for k in WIRE_KEYS:
                v = raw.get(k)
                if v is None:
                    continue
                if isinstance(v, str):
                    clean[k] = v[:MAX_WIRE_STR]
                elif isinstance(v, (int, float)) and not isinstance(
                        v, bool):
                    clean[k] = v
            fp = clean.get("fingerprint")
            eng = clean.get("engine")
            if not isinstance(fp, str) or not fp or not eng:
                continue
            with self._lock:
                known = fp in self._records
            if known:
                continue
            rec = ProgramRecord(
                key=clean.get("key") or "?", fingerprint=fp,
                engine=str(eng), attack=str(clean.get("attack", "?")),
                batch=int(clean.get("batch") or 0),
                flops=clean.get("flops"),
                bytes_accessed=clean.get("bytes_accessed"),
                peak_bytes=clean.get("peak_bytes"),
                argument_bytes=clean.get("argument_bytes"),
                output_bytes=clean.get("output_bytes"),
                generated_code_bytes=clean.get("generated_code_bytes"),
                proc=str(proc))
            self._store(rec)
            n += 1
        return n

    def records_since(self, seq: int) -> tuple:
        """(wire records newer than seq, newest seq) -- the worker
        heartbeat ships only what the coordinator has not seen."""
        with self._lock:
            out = [r.as_dict() for r in self._records.values()
                   if r.seq > seq]
            return out, self._seq

    def snapshot(self) -> list:
        """Every record as a JSON-ready dict, stable order (engine,
        attack, batch) -- the op_programs / `dprf programs` payload."""
        with self._lock:
            recs = list(self._records.values())
        recs.sort(key=lambda r: (r.engine, r.attack, r.batch))
        return [r.as_dict() for r in recs]

    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    def analyzed_ops_per_candidate(self, engine: str) -> Optional[float]:
        """Optimized flops per candidate of the engine's analyzed
        program -- the XLA-derived roofline input.  A PEEK: never
        forces analysis (the publish path runs per completed unit).
        When several program shapes exist (per-batch, wide, superstep)
        the smallest per-candidate cost wins: fused programs amortize
        fixed work, and the roofline ceiling should reflect the best
        the chip is asked to do."""
        with self._lock:
            vals = [r.flops_per_candidate
                    for r in self._records.values()
                    if r.engine == engine
                    and r.flops_per_candidate]
        return min(vals) if vals else None

    def peak_bytes_for(self, engine: str,
                       batch: int) -> Optional[int]:
        """Per-dispatch footprint of the program(s) recorded at
        exactly this (engine, batch) -- the tune ladder's projection
        anchor: scaling THIS rung's footprint to the next rung is
        honest; scaling some other shape's (a bench program, another
        attack) is not."""
        with self._lock:
            vals = [r.total_peak_bytes()
                    for r in self._records.values()
                    if r.engine == engine and r.batch == batch]
        vals = [v for v in vals if v is not None]
        return max(vals) if vals else None

    def peak_bytes(self, engine: Optional[str] = None) -> Optional[int]:
        """Largest analyzed per-dispatch footprint (optionally for one
        engine) -- the program-model fallback for peak_hbm_bytes on
        backends without memory_stats, and the tune ladder's
        projection anchor."""
        with self._lock:
            vals = [r.total_peak_bytes() for r in self._records.values()
                    if engine is None or r.engine == engine]
        vals = [v for v in vals if v is not None]
        return max(vals) if vals else None


#: process-wide registry (the utils/logging.DEFAULT pattern): compile
#: sites with no registry threaded through publish here; the serve
#: plane merges worker records into the same one.
DEFAULT = ProgramRegistry()


def get_programs(programs: Optional[ProgramRegistry] = None
                 ) -> ProgramRegistry:
    return programs if programs is not None else DEFAULT


def register_program(engine: str, attack: str, batch: int, step=None,
                     args=None, compiled=None, lowered=None,
                     programs=None) -> None:
    get_programs(programs).register(engine, attack, batch, step=step,
                                    args=args, compiled=compiled,
                                    lowered=lowered)


def analyze_pending(programs=None) -> int:
    return get_programs(programs).analyze_pending()


def analyzed_ops_per_candidate(engine: str,
                               programs=None) -> Optional[float]:
    return get_programs(programs).analyzed_ops_per_candidate(engine)


def render_table(records: list) -> str:
    """The human half of ``dprf programs``: one row per executable."""
    rows = [("engine", "attack", "batch", "flops/cand", "bytes/cand",
             "peak", "args", "out", "fingerprint")]

    def _b(v) -> str:
        if v is None:
            return "-"
        for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20),
                          ("KiB", 1 << 10)):
            if v >= div:
                return f"{v / div:.1f}{unit}"
        return str(int(v))

    for r in records:
        fpc = r.get("flops_per_candidate")
        batch = r.get("batch") or 0
        ba = r.get("bytes_accessed")
        bpc = (ba / batch) if ba and batch else None
        rows.append((
            str(r.get("engine")), str(r.get("attack")), str(batch),
            f"{fpc:.0f}" if fpc else "-",
            f"{bpc:.1f}" if bpc else "-",
            _b(r.get("total_peak_bytes")),
            _b(r.get("argument_bytes")), _b(r.get("output_bytes")),
            str(r.get("fingerprint"))[:12]))
    widths = [max(len(row[i]) for row in rows)
              for i in range(len(rows[0]))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     for row in rows)


__all__ = ["ANALYSIS_ENV", "DEFAULT", "ProgramRecord",
           "ProgramRegistry", "analysis_enabled", "analyze_pending",
           "analyzed_ops_per_candidate", "get_programs",
           "program_fingerprint", "register_program", "render_table"]
