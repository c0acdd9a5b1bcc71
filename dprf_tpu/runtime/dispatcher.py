"""Dispatcher: keyspace splitter + work-unit lease ledger.

Units are generated lazily (a keyspace of 95^7 would be ~66M units --
never materialized).  The ledger tracks three populations:

  - issued-and-outstanding units, each with a lease deadline;
  - a reissue queue (failed or lease-expired units);
  - a completed-interval set, kept as merged [start, end) ranges so the
    resume journal stays tiny no matter how many units ran.

Failure detection / elastic recovery (SURVEY.md section 5): a worker
that stops heartbeating simply lets its lease expire; `reap_expired`
moves the unit to the reissue queue and another worker picks it up.

Two tuning hooks (ISSUE 2):

  - an optional AdaptiveUnitSizer resizes LAZILY-GENERATED units per
    leasing worker (already-split units -- resume gaps, reissues --
    keep their geometry; resizing them would tear the ledger); the
    dispatcher also reports every failed attempt / lease expiry to it,
    so a worker with a CRASH HISTORY gets smaller units, not just a
    slow one (ISSUE 4 satellite of a ROADMAP item);
  - a per-unit retry cap (default 5 failed attempts) PARKS a unit that
    keeps dying instead of reissuing it forever: a unit that crashes
    every worker that touches it (a generator edge case, a poisoned
    shape) must not livelock the whole job.  Parked ranges count as
    unreachable -- `done()` fires once everything else is covered --
    and surface in job status + dprf_units_poisoned_total, never as
    silent coverage.

Tracing (ISSUE 4): every unit gets a TRACE ID at split time; lease /
complete / fail / reissue / park events are recorded as spans into the
flight recorder (telemetry/trace.py), and `trace_context()` hands the
RPC layer the (trace id, lease span id) pair it propagates to remote
workers so their spans stitch onto the same timeline.
"""

from __future__ import annotations

import heapq
import time
from typing import Callable, Optional

from dprf_tpu.runtime.workunit import WorkUnit
from dprf_tpu.telemetry import get_registry
from dprf_tpu.telemetry.coverage import (CoverageLedger, IntervalSet,
                                         coverage_digest)
from dprf_tpu.telemetry.trace import get_tracer, new_trace_id, span_id

#: lock-discipline declaration (`dprf check` locks analyzer): the
#: Dispatcher has NO lock of its own -- every concurrent caller (the
#: RPC handlers, the server drain loop) serializes through
#: CoordinatorState.lock, which declares its ``dispatcher`` reference
#: guarded.  ``<extern>`` additionally forbids this class from ever
#: acquiring a declared lock itself: a hidden acquisition here would
#: be invisible to the callers' lock-order reasoning.  (The local
#: Coordinator drives its Dispatcher from one thread; no lock needed.)
GUARDED_BY = {"Dispatcher": {"<extern>": ()}}

#: re-export: the one interval implementation lives with the coverage
#: ledger now (telemetry/coverage.py); existing importers keep working
__all__ = ["Dispatcher", "IntervalSet"]


class Dispatcher:
    """Split [0, keyspace) into WorkUnits; lease, complete, reissue."""

    def __init__(self, keyspace: int, unit_size: int,
                 lease_timeout: float = 300.0,
                 clock: Optional[Callable[[], float]] = None,
                 registry=None, sizer=None,
                 max_unit_retries: Optional[int] = 5,
                 recorder=None, job_id: str = "j0", order=None):
        if unit_size <= 0:
            raise ValueError("unit_size must be positive")
        self.keyspace = keyspace
        self.unit_size = unit_size
        self.lease_timeout = lease_timeout
        #: rank<->index bijection (generators/order.py) or None for
        #: identity.  With an order, EVERY position in this ledger --
        #: unit spans, the done set, the split frontier, gaps -- is a
        #: RANK; the split frontier advancing is what makes low ranks
        #: (probable candidates) go out first.  Only the journal-facing
        #: views (completed_intervals, coverage_digest) translate to
        #: index space, so session artifacts stay order-independent.
        self.order = order
        #: the job this ledger belongs to (multi-tenant serve plane,
        #: jobs/scheduler.py): every unit-lifecycle metric and span
        #: this dispatcher records carries it, so per-job observability
        #: costs one label -- "j0" is the single-job/local default
        self.job_id = job_id
        #: tune.AdaptiveUnitSizer (or None): sizes fresh units per
        #: leasing worker toward a target seconds-per-unit
        self.sizer = sizer
        #: failed attempts (fail() or lease expiry) before a unit is
        #: parked; None = reissue forever (the pre-guard behavior)
        self.max_unit_retries = max_unit_retries
        self._clock = clock or time.monotonic
        self._next_start = 0
        self._next_id = 0
        #: min-heap of (start, unit_id, unit): reissues and resume
        #: resplits lease LOWEST RANK FIRST -- under an order, pending
        #:  units always hold the most probable uncovered candidates,
        #: so they must beat the frontier, not queue behind it
        self._pending: list[tuple] = []
        #: id -> (unit, worker, deadline, lease span id)
        self._outstanding: dict[int, tuple] = {}
        self._retries: dict[int, int] = {}         # id -> failed attempts
        self._parked: list[WorkUnit] = []
        self._parked_len = 0
        self._done = IntervalSet()
        self.tracer = get_tracer(recorder)
        #: unit id -> trace id, assigned at split time; entries are
        #: dropped on complete (bounded by live + parked units)
        self._trace_ids: dict[int, str] = {}
        # unit-lifecycle metrics carry the job id (ISSUE 8): one
        # declaration site, one label -- a multi-tenant coordinator's
        # /metrics splits cleanly per tenant job
        m = get_registry(registry)
        self._m_leased = m.counter(
            "dprf_units_leased_total", "WorkUnit leases handed out",
            labelnames=("job",))
        self._m_completed = m.counter(
            "dprf_units_completed_total", "WorkUnits marked done",
            labelnames=("job",))
        self._m_reissued = m.counter(
            "dprf_units_reissued_total",
            "WorkUnits returned to the queue",
            labelnames=("reason", "job"))
        self._g_outstanding = m.gauge(
            "dprf_units_outstanding", "leases currently held",
            labelnames=("job",))
        self._g_keyspace = m.gauge(
            "dprf_keyspace_total", "keyspace indices in the job",
            labelnames=("job",))
        self._g_covered = m.gauge(
            "dprf_keyspace_covered", "keyspace indices completed",
            labelnames=("job",))
        self._m_poisoned = m.counter(
            "dprf_units_poisoned_total",
            "units parked after exhausting their retry budget",
            labelnames=("job",))
        self._g_parked = m.gauge(
            "dprf_units_parked",
            "units currently parked (poisoned); drops to 0 on a "
            "retry-parked admin op", labelnames=("job",))
        self._g_keyspace.set(keyspace, job=job_id)
        self._g_covered.set(0, job=job_id)
        self._g_parked.set(0, job=job_id)
        #: coverage audit plane (ISSUE 19): every range-mutating
        #: lifecycle step below feeds this ledger through its one
        #: event API; it detects overlaps at insert, reports gaps
        #: against the keyspace, and carries the coverage digest
        self.coverage = CoverageLedger(keyspace, job_id=job_id,
                                       registry=registry, order=order)

    # -- construction from a resume journal ------------------------------

    @classmethod
    def from_completed(cls, keyspace: int, unit_size: int,
                       completed: list,
                       expect_digest: Optional[str] = None,
                       **kw) -> "Dispatcher":
        d = cls(keyspace, unit_size, **kw)
        if d.order is not None:
            # the journal records INDEX intervals (order-independent
            # session artifacts); fold them back through the bijection
            # so the rank-space ledger resumes -- and resplits below
            # the rank frontier -- exactly where the sweep stopped
            completed = d.order.rank_image(completed)
        for s, e in completed:
            d._done.add(s, e)
            d.coverage.event("restore", s, e)
            # restore spans mark a GENERATION boundary in the trace
            # stream and seed the new generation's covered set: the
            # offline replay (perfreport/audit.py) resets on them, so
            # a crash-restart legitimately re-sweeping ranges the
            # journal had not snapshotted yet is not misread as
            # double coverage -- while a true within-generation
            # double-complete still is
            d.tracer.record("restore", proc="coordinator",
                            job=d.job_id, start=s, length=e - s)
        d._g_covered.set(d._done.covered(), job=d.job_id)
        frontier = max((e for _, e in completed), default=0)
        for s, e in d._done.gaps(frontier):
            # re-split big gaps into unit-sized pieces
            d.coverage.event("resplit", s, e)
            for u in range(s, e, unit_size):
                unit = d._make_unit(u, min(unit_size, e - u))
                heapq.heappush(d._pending,
                               (unit.start, unit.unit_id, unit))
        d._next_start = frontier
        if expect_digest and d.coverage_digest() != expect_digest:
            # the PR 14 fingerprint discipline applied to coverage
            # state: a journal whose intervals do not reproduce the
            # digest it recorded describes a DIFFERENT sweep -- a
            # resume from it would punch silent coverage holes
            raise ValueError(
                "coverage digest mismatch on resume: journal recorded "
                f"{expect_digest} but its intervals rebuild to "
                f"{d.coverage_digest()} -- the journal is torn or "
                "edited; refusing to resume over silent holes")
        return d

    def _make_unit(self, start: int, length: int) -> WorkUnit:
        u = WorkUnit(self._next_id, start, length,
                     job_id=self.job_id,
                     order=(self.order.kind if self.order is not None
                            else "index"))
        self._next_id += 1
        # the unit's whole lifecycle -- every lease, failure, reissue,
        # wherever it lands -- shares this one trace id
        self._trace_ids[u.unit_id] = new_trace_id()
        self.coverage.event("split", u.start, u.end, unit=u.unit_id)
        return u

    def trace_context(self, unit_id: int) -> Optional[tuple]:
        """(trace id, lease span id) of the unit's CURRENT lease --
        what the RPC layer ships to the worker so its spans stitch
        onto this attempt; None once the unit is no longer leased."""
        entry = self._outstanding.get(unit_id)
        if entry is None:
            return None
        return self._trace_ids.get(unit_id), entry[3]

    # -- the worker-facing API -------------------------------------------

    def lease(self, worker_id: str = "local") -> Optional[WorkUnit]:
        """Hand out the next unit, or None if nothing is leasable now
        (either exhausted, or all remaining work is outstanding)."""
        with self.tracer.station("lease"):
            self.reap_expired()
            if self._pending:
                unit = heapq.heappop(self._pending)[2]
            elif self._next_start < self.keyspace:
                size = (self.sizer.next_size(worker_id)
                        if self.sizer is not None else self.unit_size)
                length = min(size, self.keyspace - self._next_start)
                unit = self._make_unit(self._next_start, length)
                self._next_start += length
            else:
                return None
            lease_span = self.tracer.record(
                "lease", trace=self._trace_ids.get(unit.unit_id),
                proc="coordinator", worker=worker_id, unit=unit.unit_id,
                job=self.job_id, start=unit.start, length=unit.length,
                lease_timeout_s=self.lease_timeout,
                attempt=self._retries.get(unit.unit_id, 0) + 1)
            self._outstanding[unit.unit_id] = (
                unit, worker_id, self._clock() + self.lease_timeout,
                span_id(lease_span))
            self.coverage.event("lease", unit.start, unit.end,
                                unit=unit.unit_id)
            self._m_leased.inc(job=self.job_id)
            self._g_outstanding.set(len(self._outstanding),
                                    job=self.job_id)
            return unit

    def lease_many(self, worker_id: str, n: int) -> list:
        """Up to n units for ONE worker in one call -- the RPC
        lease-ahead form: a pipelined remote worker holds several
        leases so the next super-step is on its device stream while
        the previous unit's hits decode and the report round trip
        flies.  Accounting stays strictly per-unit: each lease gets
        its own span, deadline, and reissue path, so an aheaded unit
        whose lease expires while queued is released exactly like a
        running one."""
        out = []
        for _ in range(max(0, int(n))):
            unit = self.lease(worker_id)
            if unit is None:
                break
            out.append(unit)
        return out

    def outstanding_for(self, worker_id: str) -> int:
        """Leases this worker currently holds (multi-outstanding
        accounting: the RPC layer caps lease-ahead against it)."""
        return sum(1 for (_, wid, _, _) in self._outstanding.values()
                   if wid == worker_id)

    def lease_holder(self, unit_id: int) -> Optional[str]:
        """Worker currently holding the unit's lease (None once it is
        completed, failed, or reaped)."""
        entry = self._outstanding.get(unit_id)
        return entry[1] if entry is not None else None

    def complete(self, unit_id: int, elapsed: Optional[float] = None,
                 worker_id: Optional[str] = None) -> bool:
        """Mark a leased unit done; returns True iff this call covered
        it.  A late completion of an already-reissued unit is
        idempotent: when ``worker_id`` is given and the lease moved to
        ANOTHER worker, the stale report is dropped (the live holder
        owns the completion -- no double-complete, no double count),
        and a unit with no live lease at all is simply ignored."""
        entry = self._outstanding.get(unit_id)
        if entry is None:
            return False
        if worker_id is not None and entry[1] != worker_id:
            return False   # reissued to another worker: stale report
        del self._outstanding[unit_id]
        unit, worker_id, _, lease_sid = entry
        self._done.add(unit.start, unit.end)
        self.coverage.event("complete", unit.start, unit.end,
                            unit=unit_id)
        self._retries.pop(unit_id, None)
        if self.sizer is not None and elapsed is not None:
            # throughput report feeds the ADAPTIVE sizer: the next unit
            # this worker leases is sized toward the target seconds
            self.sizer.observe(worker_id, unit.length, elapsed)
        # the span carries the unit's RANGE so the offline auditor
        # (perfreport/audit.py) can replay coverage from the trace
        # stream alone and cross-check it against the journal
        self.tracer.record(
            "complete", trace=self._trace_ids.pop(unit_id, None),
            parent=lease_sid, proc="coordinator", worker=worker_id,
            unit=unit_id, job=self.job_id, elapsed_s=elapsed,
            start=unit.start, length=unit.length)
        self._m_completed.inc(job=self.job_id)
        self._g_covered.set(self._done.covered(), job=self.job_id)
        self._g_outstanding.set(len(self._outstanding),
                                job=self.job_id)
        return True

    def _observe_failure(self, worker_id: Optional[str]) -> None:
        """Crash history -> unit sizing: every failed attempt / lease
        expiry shrinks the worker's NEXT units (tune.AdaptiveUnitSizer
        halves per recent failure), so a flaky host re-runs minutes of
        work when it dies, not hours -- low throughput alone would
        never catch a worker that is fast but keeps crashing."""
        if self.sizer is not None and worker_id is not None:
            observe = getattr(self.sizer, "observe_failure", None)
            if observe is not None:
                observe(worker_id)

    def _requeue(self, unit: WorkUnit, reason: str,
                 worker_id: Optional[str] = None,
                 lease_sid: Optional[str] = None) -> None:
        """Reissue a failed/expired unit -- unless it has burned its
        retry budget, in which case it is PARKED: its range becomes
        unreachable for this run (visible in status and the poisoned
        counter, and still a resume-journal gap) instead of bouncing
        between workers forever."""
        n = self._retries.get(unit.unit_id, 0) + 1
        self._retries[unit.unit_id] = n
        self._observe_failure(worker_id)
        tid = self._trace_ids.get(unit.unit_id)
        if (self.max_unit_retries is not None
                and n >= self.max_unit_retries):
            # parked ranges stay LIVE on the coverage ledger:
            # accounted, intentionally unreachable -- never a gap
            self.coverage.event("park", unit.start, unit.end,
                                unit=unit.unit_id)
            self._parked.append(unit)
            self._parked_len += unit.length
            self._m_poisoned.inc(job=self.job_id)
            self._g_parked.set(len(self._parked), job=self.job_id)
            self.tracer.record("park", trace=tid, parent=lease_sid,
                               proc="coordinator", unit=unit.unit_id,
                               job=self.job_id, worker=worker_id,
                               attempts=n, reason=reason)
            from dprf_tpu.utils.logging import DEFAULT as log
            log.warn("parking poisoned unit after repeated failures",
                     unit=unit.unit_id, start=unit.start,
                     length=unit.length, attempts=n, reason=reason)
        else:
            self.coverage.event("reissue", unit.start, unit.end,
                                unit=unit.unit_id)
            heapq.heappush(self._pending,
                           (unit.start, unit.unit_id, unit))
            self.tracer.record("reissue", trace=tid, parent=lease_sid,
                               proc="coordinator", unit=unit.unit_id,
                               job=self.job_id, worker=worker_id,
                               attempts=n, reason=reason)
            self._m_reissued.inc(reason=reason, job=self.job_id)

    def fail(self, unit_id: int,
             worker_id: Optional[str] = None) -> bool:
        """Release a leased unit back to the queue; returns True iff
        this call released it.  Stale-guarded like complete(): a fail
        report from a worker that no longer holds the lease must not
        tear the live holder's attempt off the ledger."""
        entry = self._outstanding.get(unit_id)
        if entry is None:
            return False
        if worker_id is not None and entry[1] != worker_id:
            return False   # reissued to another worker: stale report
        del self._outstanding[unit_id]
        unit, holder, _, lease_sid = entry
        self.coverage.event("fail", unit.start, unit.end,
                            unit=unit_id)
        self.tracer.record("fail",
                           trace=self._trace_ids.get(unit_id),
                           parent=lease_sid, proc="coordinator",
                           worker=holder, unit=unit_id,
                           job=self.job_id)
        self._requeue(unit, "failed", worker_id=holder,
                      lease_sid=lease_sid)
        self._g_outstanding.set(len(self._outstanding),
                                job=self.job_id)
        return True

    def reap_expired(self) -> int:
        now = self._clock()
        expired = [uid for uid, (_, _, dl, _) in self._outstanding.items()
                   if dl < now]
        for uid in expired:
            unit, worker_id, _, lease_sid = self._outstanding.pop(uid)
            self._requeue(unit, "lease_expired", worker_id=worker_id,
                          lease_sid=lease_sid)
        if expired:
            self._g_outstanding.set(len(self._outstanding),
                                    job=self.job_id)
        return len(expired)

    # -- status ----------------------------------------------------------

    def done(self) -> bool:
        # parked ranges are unreachable this run: waiting on them would
        # livelock the job, so "done" means everything REACHABLE is
        # covered (exhausted() still reports the honest full-coverage
        # answer)
        return (self._done.covered() >= self.keyspace - self._parked_len)

    def exhausted(self) -> bool:
        """True only when the WHOLE keyspace is covered (no parked
        holes) -- the answer `JobResult.exhausted` reports."""
        return self._done.covered() >= self.keyspace

    def idle(self) -> bool:
        """Nothing leasable and nothing outstanding (but not done:
        happens only transiently between reap and re-lease)."""
        return (not self._pending and not self._outstanding
                and self._next_start >= self.keyspace)

    def progress(self) -> tuple:
        return self._done.covered(), self.keyspace

    def completed_intervals(self) -> list[tuple]:
        """The covered set in INDEX space -- the journal/snapshot form.
        Under an order this is the index image of the rank-space done
        set, so the session artifacts a sweep leaves behind are
        identical no matter what order produced them."""
        if self.order is not None:
            return self.order.index_image(self._done.intervals())
        return self._done.intervals()

    def coverage_digest(self) -> str:
        """Order-independent digest of the covered set -- journaled
        with units snapshots and carried by JobResult; a resume must
        rebuild the same digest from the journaled intervals.
        Computed from the dispatcher's own done set (canonicalized to
        index space), so it never depends on the DPRF_COVERAGE
        telemetry knob."""
        return coverage_digest(self.keyspace, self.completed_intervals())

    def outstanding_count(self) -> int:
        return len(self._outstanding)

    def outstanding_indices(self) -> int:
        """Keyspace indices currently out on leases -- what a job
        quota (jobs/scheduler.py) is enforced against alongside the
        covered count."""
        return sum(u.length for u, _, _, _ in self._outstanding.values())

    def leasable(self) -> bool:
        """Whether a lease() call could hand out a unit right now
        (pending reissues, or unsplit keyspace left)."""
        return bool(self._pending) or self._next_start < self.keyspace

    def abandon(self) -> None:
        """Job-cancel teardown (jobs/scheduler.py): drop every pending
        and outstanding unit without completing or reissuing them.
        The ledger stops dead -- late reports from workers still
        holding these leases bounce off the scheduler's CANCELLED
        guard, so nothing lands after this."""
        self._pending.clear()
        self._outstanding.clear()
        self.coverage.event("abandon")
        self._g_outstanding.set(0, job=self.job_id)

    def parked_count(self) -> int:
        return len(self._parked)

    def parked_indices(self) -> int:
        """Keyspace indices inside parked (poisoned) units."""
        return self._parked_len

    def parked_units(self) -> list:
        return list(self._parked)

    def retry_parked(self) -> int:
        """Admin op (`dprf retry-parked` -> rpc.op_retry_parked):
        requeue every parked unit with a FRESH retry budget, without
        restarting the job.  The operator's tool for "the poison was
        environmental" (a bad worker build since replaced, a host that
        ran out of memory): the ranges become reachable again and
        `done()` stops treating them as holes.  Returns the number of
        units requeued.  dprf_units_poisoned_total keeps its count --
        it records parking EVENTS; the dprf_units_parked gauge drops
        to 0."""
        n = len(self._parked)
        for unit in self._parked:
            self._retries.pop(unit.unit_id, None)
            self.coverage.event("unpark", unit.start, unit.end,
                                unit=unit.unit_id)
            heapq.heappush(self._pending,
                           (unit.start, unit.unit_id, unit))
            self.tracer.record("reissue",
                               trace=self._trace_ids.get(unit.unit_id),
                               proc="coordinator", unit=unit.unit_id,
                               job=self.job_id, reason="retry_parked")
            self._m_reissued.inc(reason="retry_parked",
                                 job=self.job_id)
        self._parked = []
        self._parked_len = 0
        self._g_parked.set(0, job=self.job_id)
        if n:
            from dprf_tpu.utils.logging import DEFAULT as log
            log.info("requeued parked units with a fresh retry budget",
                     count=n)
        return n

    def outstanding_unit(self, unit_id: int) -> Optional[WorkUnit]:
        """The still-leased unit with this id (None once completed,
        failed, or reaped) -- lets the RPC layer attribute a completion
        report's candidate count without re-deriving unit geometry."""
        entry = self._outstanding.get(unit_id)
        return entry[0] if entry is not None else None

    def outstanding_leases(self) -> list:
        """Live-lease table for the ``dprf top`` view: every held
        lease with its worker, range, seconds until expiry, and trace
        id."""
        now = self._clock()
        return [{"unit": uid, "worker": wid, "start": u.start,
                 "length": u.length, "job": self.job_id,
                 "deadline_s": round(dl - now, 3),
                 "trace": self._trace_ids.get(uid)}
                for uid, (u, wid, dl, _) in self._outstanding.items()]
