"""Coordinator: owns the job -- issues WorkUnits, collects hits,
persists progress, decides when to stop.

The control plane (SURVEY.md section 1): everything here is thin host
code; the hot loop lives in the workers' fused device programs.  Hits
are deduped per target, written to the potfile and the session journal,
and the job stops when every target is cracked or the keyspace is
exhausted.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence

from dprf_tpu.engines.base import Target
from dprf_tpu.runtime.dispatcher import Dispatcher
from dprf_tpu.runtime.potfile import Potfile
from dprf_tpu.runtime.session import SessionJournal
from dprf_tpu.runtime.worker import Hit
from dprf_tpu.telemetry import get_registry
from dprf_tpu.telemetry import perf as perf_mod
from dprf_tpu.telemetry.trace import get_tracer, jax_profile_ctx


@dataclasses.dataclass
class JobSpec:
    engine: str
    device: str
    attack: str                 # "mask" | "wordlist"
    attack_arg: str             # mask string or wordlist path
    keyspace: int
    fingerprint: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class JobResult:
    found: dict                  # target_index -> plaintext bytes
    tested: int
    elapsed: float
    exhausted: bool
    #: units parked by the dispatcher's retry cap (poisoned ranges the
    #: run could not cover; 0 on a healthy job)
    parked: int = 0
    #: order-independent digest of the covered index set (ISSUE 19):
    #: what the final journal snapshot recorded; `dprf audit` must
    #: rebuild the same value from the session artifacts alone
    coverage_digest: str = ""

    @property
    def rate(self) -> float:
        return self.tested / self.elapsed if self.elapsed > 0 else 0.0


def preload_potfile(found: dict, targets: Sequence[Target],
                    potfile) -> None:
    """Seed `found` with targets the potfile already cracked, so no
    keyspace is spent rediscovering them.  Shared by the local
    Coordinator and the distributed CoordinatorState (cli.cmd_serve)."""
    if potfile is None:
        return
    for i, t in enumerate(targets):
        plain = potfile.get(t.raw)
        if plain is not None:
            found.setdefault(i, plain)


def restore_hits_into(found: dict, hits: list) -> None:
    """Seed `found` from a session journal's hit records (tolerant of
    malformed entries).  Shared by local and distributed resume paths."""
    for h in hits:
        try:
            found.setdefault(int(h["target"]), bytes.fromhex(h["plaintext"]))
        except (KeyError, ValueError):
            continue


#: `dprf check` retrace analyzer: loops in these functions drive the
#: device per work unit -- host syncs and shape-varying jit calls
#: inside them are silent perf bugs the compile cache can't see.
HOT_PATHS = ("Coordinator.run", "Coordinator._refill")


class Coordinator:
    def __init__(self, spec: JobSpec, targets: Sequence[Target],
                 dispatcher: Dispatcher, worker,
                 session: Optional[SessionJournal] = None,
                 potfile: Optional[Potfile] = None,
                 progress_cb: Optional[Callable] = None,
                 progress_interval: float = 5.0,
                 oracle=None, registry=None, recorder=None):
        self.spec = spec
        self.targets = list(targets)
        self.dispatcher = dispatcher
        self.worker = worker
        self.session = session
        self.potfile = potfile
        self.progress_cb = progress_cb
        self.progress_interval = progress_interval
        #: CPU oracle HashEngine.  Device hits are re-hashed on the host
        #: before they reach the potfile -- the same guard the distributed
        #: path applies in rpc.CoordinatorState (a kernel/XLA bug must
        #: not poison the potfile or silently end the search for a
        #: target it did not crack).  None = trust the worker (CPU path,
        #: where the worker IS the oracle).
        self.oracle = oracle
        self.rejected = 0
        self.found: dict[int, bytes] = {}
        #: flight recorder for the local job's sweep/hit_verify spans
        #: (the dispatcher records the lease ledger's into the same
        #: one by default)
        self.tracer = get_tracer(recorder)
        self._registry = get_registry(registry)
        #: verify-phase attribution (telemetry/perf.py): the oracle
        #: re-hash cost of every hit batch
        self._h_phase = perf_mod.phase_histogram(self._registry)
        from dprf_tpu.telemetry import declare_job_metrics
        jm = declare_job_metrics(self._registry)
        self._m_hits = jm["hits"]
        self._m_rejects = jm["rejects"]
        self._m_cands = jm["cands"]
        self._h_unit = jm["unit_seconds"]
        self._g_targets = jm["targets"]
        self._g_found = jm["found"]
        self._g_targets.set(len(self.targets))
        self._g_found.set(len(self.found))

    # -- pre-run bookkeeping ---------------------------------------------

    def preload_found(self) -> None:
        """Mark targets already cracked (potfile) or recorded in a resumed
        session so work stops early / never starts."""
        preload_potfile(self.found, self.targets, self.potfile)
        self._g_found.set(len(self.found))

    def restore_hits(self, hits: list) -> None:
        restore_hits_into(self.found, hits)
        self._g_found.set(len(self.found))

    # -- the run loop ----------------------------------------------------

    def _all_found(self) -> bool:
        return len(self.found) >= len(self.targets)

    def _record(self, hit: Hit, unit_id: int) -> bool:
        """Record one verified hit of unit `unit_id`; returns False (and
        records nothing) if the oracle re-hash rejects it."""
        if hit.target_index in self.found:
            return True
        target = self.targets[hit.target_index]
        if self.oracle is not None:
            with self.tracer.station("oracle", unit=unit_id):
                ok = self.oracle.verify(hit.plaintext, target)
            if not ok:
                from dprf_tpu.utils.logging import DEFAULT as log
                self.rejected += 1
                self._m_rejects.inc()
                log.warn("rejected unverifiable device hit; rescanning "
                         "unit with the CPU oracle", target=target.raw[:32],
                         cand_index=hit.cand_index)
                return False
        self.found[hit.target_index] = hit.plaintext
        self._m_hits.inc()
        self._g_found.set(len(self.found))
        if self.potfile is not None:
            self.potfile.add(target.raw, hit.plaintext)
        if self.session is not None:
            # job-tagged unconditionally (ISSUE 10): the journal's
            # header names this id as default_job, so resume folds
            # these lines back into the flat fields
            self.session.record_hit(hit.target_index, hit.cand_index,
                                    hit.plaintext,
                                    job=self.dispatcher.job_id)
        return True

    #: default units dispatched ahead of the oldest unresolved one
    #: (``DPRF_PIPELINE_DEPTH`` overrides -- worker.pipeline_depth is
    #: the one resolution site, shared with the remote worker_loop).
    #: Depth 2 is enough to overlap one unit's flag round trip with
    #: the next unit's compute (the only latency in the local loop);
    #: deeper queues just hold more leases without hiding more.
    PIPELINE_DEPTH = 2

    def _finish_unit(self, unit, hits) -> None:
        """Record a unit's resolved hits; any rejected hit means the
        device path is suspect for this range, so the whole unit is
        exactly rescanned with the CPU oracle (whose hits verify by
        construction) before the unit may count as covered."""
        rejected = False
        for hit in hits:
            rejected |= not self._record(hit, unit.unit_id)
        if rejected:
            from dprf_tpu.runtime.worker import CpuWorker, OrderedWorker
            rescan = CpuWorker(self.oracle, self.worker.gen,
                               self.worker.targets)
            order = getattr(self.worker, "order", None)
            if order is not None:
                # rank-ordered job: the unit's span is ranks, and the
                # rescan must decode it through the same bijection
                rescan = OrderedWorker(rescan, order)
            with self.tracer.station("oracle", unit=unit.unit_id):
                found = rescan.process(unit)
            for hit in found:
                self._record(hit, unit.unit_id)   # oracle-produced

    def _refill(self, pipeline) -> int:
        """Lease and submit until the pipeline is full, the dispatcher
        is done or a lease comes back empty (a dispatcher may hold
        leases back while units are in flight); returns the units
        submitted."""
        submitted = 0
        while not pipeline.full and not self.dispatcher.done():
            unit = self.dispatcher.lease()
            if unit is None:
                break
            if self._ensure_warm is not None:
                # join the background compile before the first step
                # dispatch (submitting mid-compile would race the jit
                # tracer against itself)
                self._ensure_warm()
            if self._warm_pending:
                # trace the overlapped compile at its REAL cost
                # (compile_seconds), parented onto the first lease so
                # the cold start is legible per unit
                self._warm_pending = False
                warm_s = getattr(self.worker, "compile_seconds", None)
                ctx = self.dispatcher.trace_context(unit.unit_id)
                if warm_s is not None:
                    self.tracer.record(
                        "warmup", dur=float(warm_s),
                        trace=ctx[0] if ctx else None,
                        parent=ctx[1] if ctx else None,
                        proc="local", engine=self.spec.engine,
                        cache=getattr(self.worker, "compile_cache", None),
                        overlapped=True)
            pipeline.submit(unit)
            submitted += 1
        return submitted

    def run(self) -> JobResult:
        from dprf_tpu.runtime.worker import UnitPipeline, pipeline_depth

        t0 = time.perf_counter()
        tested0 = self.dispatcher.progress()[0]
        last_report = t0
        # Overlapped warmup: kick the step compile onto a background
        # thread (a no-op for workers already warmed -- Pallas
        # factories -- or already started by the CLI) and join it only
        # at the first dispatch, so the compile overlaps session open
        # and the first leases instead of serializing with them.
        warmup_async = getattr(self.worker, "warmup_async", None)
        if warmup_async is not None:
            warmup_async()
        self._ensure_warm = getattr(self.worker, "ensure_warm", None)
        self._warm_pending = self._ensure_warm is not None
        if self.session is not None:
            self.session.open(self.spec.as_dict(),
                              default_job=self.dispatcher.job_id)
        # Submit-ahead FIFO (shared with the remote worker_loop):
        # device work for every queued unit is already dispatched;
        # resolving the head overlaps its readback latency with the
        # tail's compute.
        pipeline = UnitPipeline(self.worker,
                                pipeline_depth(self.PIPELINE_DEPTH))
        t_last_resolve = None
        # DPRF_JAX_PROFILE=<dir>: kernel-level drill-down beside the
        # span timeline (no-op when unset; degrades safely if a
        # profiler trace is already active via --profile)
        profile = jax_profile_ctx()
        profile.__enter__()
        try:
            while not self._all_found():
                self._refill(pipeline)
                if not len(pipeline):
                    if self.dispatcher.done() or \
                            self.dispatcher.outstanding_count() == 0:
                        break        # exhausted
                    time.sleep(0.01)
                    continue
                unit, p, t_submit, _ = pipeline.pop()
                ctx = self.dispatcher.trace_context(unit.unit_id)
                with self.tracer.station("resolve", unit=unit.unit_id):
                    hits = getattr(p, "resolve_head", p.resolve)()
                if getattr(p, "tiles", None):
                    # the unit's re-probes run behind the queued
                    # unit's program: refill before waiting for them,
                    # so the device has a program behind them while
                    # the host reads them back and verifies their lanes
                    ahead = self._refill(pipeline) > 0
                    with self.tracer.station("resolve",
                                             unit=unit.unit_id):
                        hits.extend(p.resolve_tiles(ahead))
                now_resolve = time.monotonic()
                unit_s = now_resolve - t_submit
                # inter-completion interval: the loop's true drain
                # rate once the pipeline is primed (unit_s includes
                # up to depth-1 units of queue wait) -- feeds the
                # roofline gauge; resets when the pipeline empties so
                # starvation never reads as slow hashing
                interval = (now_resolve - t_last_resolve
                            if t_last_resolve is not None else unit_s)
                t_last_resolve = (now_resolve if len(pipeline)
                                  else None)
                self.tracer.record(
                    "sweep", dur=unit_s,
                    trace=ctx[0] if ctx else None,
                    parent=ctx[1] if ctx else None, proc="local",
                    unit=unit.unit_id, length=unit.length,
                    hits=len(hits))
                if hits:
                    t_verify = time.monotonic()
                    rejected0 = self.rejected
                    with self.tracer.station("verify",
                                             unit=unit.unit_id):
                        self._finish_unit(unit, hits)
                    verify_s = time.monotonic() - t_verify
                    self._h_phase.observe(
                        verify_s, phase="verify",
                        engine=self.spec.engine,
                        job=str(self.dispatcher.job_id))
                    self.tracer.record(
                        "hit_verify",
                        dur=verify_s,
                        trace=ctx[0] if ctx else None,
                        parent=ctx[1] if ctx else None,
                        proc="coordinator", unit=unit.unit_id,
                        hits=len(hits),
                        rejected=self.rejected - rejected0)
                self._h_unit.observe(unit_s)
                self._m_cands.inc(unit.length, engine=self.spec.engine,
                                  device=self.spec.device)
                if interval > 0 and self.spec.device == "jax":
                    # live roofline distance from the drain rate (the
                    # device path runs in THIS process, so its chip
                    # is the local one)
                    perf_mod.publish_roofline(
                        self.spec.engine, unit.length / interval,
                        perf_mod.local_device_kind(),
                        registry=self._registry)
                # submit-to-resolve time feeds the adaptive unit sizer;
                # it includes up to PIPELINE_DEPTH-1 units of queue
                # wait, so the EWMA under-estimates throughput a little
                # -- which only biases units SMALLER than the target,
                # the safe direction
                with self.tracer.station("complete", unit=unit.unit_id):
                    self.dispatcher.complete(unit.unit_id,
                                             elapsed=unit_s)
                    if self.session is not None:
                        self.session.record_units(
                            self.dispatcher.completed_intervals(),
                            job=self.dispatcher.job_id,
                            digest=self.dispatcher.coverage_digest())
                now = time.perf_counter()
                if self.progress_cb and now - last_report >= self.progress_interval:
                    last_report = now
                    done, total = self.dispatcher.progress()
                    self.progress_cb(done, total, len(self.found),
                                     (done - tested0) / max(now - t0, 1e-9))
        finally:
            profile.__exit__(None, None, None)
            # Snapshot in finally: a Ctrl-C mid-job must not lose up to
            # snapshot_every-1 units of journaled coverage.
            if self.session is not None:
                self.session.snapshot(
                    self.dispatcher.completed_intervals(),
                    job=self.dispatcher.job_id,
                    digest=self.dispatcher.coverage_digest())
                self.session.close()
        elapsed = time.perf_counter() - t0
        done, total = self.dispatcher.progress()
        return JobResult(found=dict(self.found), tested=done - tested0,
                         elapsed=elapsed,
                         exhausted=self.dispatcher.exhausted(),
                         parked=self.dispatcher.parked_count(),
                         coverage_digest=self.dispatcher.coverage_digest())
