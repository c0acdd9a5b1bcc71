"""Coverage audit plane (ISSUE 19): prove every candidate is tried
exactly once.

The metrics layer answers "how much / how fast" and the trace layer
answers "which unit, where"; this module answers the invariant that
actually defines correctness for a cracking run: **did the fleet
cover the keyspace exactly once?**  A silent gap is a missed password
and a silent overlap is wasted H/s, and the interval arithmetic that
decides both is spread across lease/complete/reissue/park, journal
resume, unit resplit, hit-capacity redrive, and sharded superstep
windows.

One ``CoverageLedger`` per job, owned and fed by its Dispatcher (and
therefore serialized by the same caller lock -- see GUARDED_BY).  The
ledger is an interval set over the generator's index space plus a
live-unit table: every range-mutating event flows through ONE event
API, ``ledger.event(name, ...)``, whose names are declared below in
``EVENT_NAMES`` exactly like ``trace.SPAN_NAMES`` -- and the
``coverage-events`` analyzer (analysis/coverage_events.py) statically
verifies both that every event literal is declared and that every
Dispatcher/worker site that mutates a unit's index range calls the
API (``COVERAGE_EVENT_SITES`` below is the site manifest it checks).

What the ledger detects, live:

  - **overlaps at insert time**: ``complete`` folds the unit's range
    into the covered set via an O(log n) merged-interval insert that
    returns the NEWLY covered length; any shortfall is double-covered
    keyspace (a stale lease that slipped the guard, a resume that
    re-ran finished work) and increments
    ``dprf_job_coverage_overlap_total``;
  - **gaps against the declared keyspace**: every index must at all
    times be covered, live on a split unit (pending / outstanding /
    parked), or not yet split (above the split frontier).  Anything
    else was LOST -- ``dprf_job_coverage_gap_total`` goes nonzero and
    the ``coverage_gap`` alert fires.

The ledger also computes an order-independent **coverage digest**:
sha256 over the keyspace size and the canonical merged covered
intervals (the same 16-hex shape as ``session.job_fingerprint``).
Journals and completion records carry it; a coordinator rebuild
(``Dispatcher.from_completed``) must REPRODUCE it from the journaled
intervals or refuse the resume -- the PR 14 fingerprint discipline
applied to coverage state.  ``dprf audit SESSION``
(perfreport/audit.py) reconstructs the whole story offline from
session artifacts alone.

Worker-side range mutations (hit-capacity redrive, rescan, sharded
superstep windows) happen on hot paths in worker processes, far from
any ledger.  They report through the module-level ``note()`` API:
a counter bump by default (far under the <=2% overhead budget), plus
an optional process-local collector that the chaos harness and tests
install to assert the windows tile each unit exactly once.

``DPRF_COVERAGE=0`` disables the plane process-wide (the ledger still
answers digests -- resume correctness must not depend on a telemetry
knob -- but stops detecting, counting, and exporting).
"""

from __future__ import annotations

import hashlib
import threading
from typing import Optional

from dprf_tpu.utils import env as envreg

#: the one declaration site for coverage event names (the
#: coverage-events analyzer enforces that every ``.event("...")`` /
#: ``coverage.note("...")`` literal is a member).  Range semantics:
#:
#:   split      a unit was cut from the keyspace (lazy split or resume
#:              resplit): its range becomes LIVE
#:   restore    journaled covered interval folded in at rebuild
#:   resplit    a resume gap below the frontier was re-split into units
#:   lease      a live unit went out on a lease (no range movement)
#:   complete   a live unit's range moved into the covered set
#:   fail       a leased unit was released by its worker
#:   reissue    a failed/expired unit went back on the queue
#:   park       a unit burned its retry budget (still live: parked
#:              ranges are accounted, intentionally unreachable)
#:   unpark     a parked unit re-entered the queue (retry-parked op)
#:   abandon    job cancel: every live unit dropped, ledger frozen
#:   force_complete  the coordinator completed a unit on worker
#:              consensus-of-rejection (rpc.op_complete): covered, but
#:              flagged -- the range may hold an unrecovered crack
#:   redrive    worker re-enqueued a sub-range after hit-buffer
#:              overflow (worker-side, via note())
#:   rescan     worker re-swept a collided tile/window (worker-side)
#:   window     one superstep window dispatched over [start, end)
#:              (worker-side; windows must tile the unit)
EVENT_NAMES = ("split", "restore", "resplit", "lease", "complete",
               "fail", "reissue", "park", "unpark", "abandon",
               "force_complete", "redrive", "rescan", "window")

#: worker-side events that flow through note() rather than a ledger
NOTE_EVENTS = ("redrive", "rescan", "window")

#: site manifest for the coverage-events analyzer: every
#: (file, function) here must exist and call the event API -- the
#: one-declaration-site discipline that keeps future refactors from
#: silently bypassing the audit.  Paths are repo-relative.
COVERAGE_EVENT_SITES = (
    ("dprf_tpu/runtime/dispatcher.py", "_make_unit"),
    ("dprf_tpu/runtime/dispatcher.py", "from_completed"),
    ("dprf_tpu/runtime/dispatcher.py", "lease"),
    ("dprf_tpu/runtime/dispatcher.py", "complete"),
    ("dprf_tpu/runtime/dispatcher.py", "fail"),
    ("dprf_tpu/runtime/dispatcher.py", "_requeue"),
    ("dprf_tpu/runtime/dispatcher.py", "retry_parked"),
    ("dprf_tpu/runtime/dispatcher.py", "abandon"),
    ("dprf_tpu/runtime/rpc.py", "op_complete"),
    ("dprf_tpu/runtime/worker.py", "_redrive_wide"),
    ("dprf_tpu/runtime/worker.py", "_rescan"),
    ("dprf_tpu/runtime/worker.py", "_redrive_wide_words"),
    ("dprf_tpu/runtime/worker.py", "_rescan_words"),
    ("dprf_tpu/runtime/worker.py", "_reprobe_tiles"),
    ("dprf_tpu/runtime/worker.py", "_tile_hits"),
    ("dprf_tpu/parallel/worker.py", "_redrive_sharded_words"),
    # every submit() in the sharded module notes its superstep /
    # per-batch dispatch windows ("window" tiling evidence); the
    # sharded word rescan is the inherited WordlistWorkerBase
    # _rescan_words above
    ("dprf_tpu/parallel/worker.py", "submit"),
)

#: kill switch: DPRF_COVERAGE=0 disables ledger accounting + notes
ENABLE_ENV = "DPRF_COVERAGE"
#: cap on gap/overlap intervals enumerated in reports and audits
MAX_GAPS_ENV = "DPRF_COVERAGE_MAX_GAPS"

#: lock-discipline declaration (`dprf check` locks analyzer): a
#: ledger belongs to one Dispatcher and inherits its serialization
#: (CoordinatorState.lock on the serve plane, single-threaded locally)
#: -- ``<extern>``, like the Dispatcher itself.  The worker-side note
#: state is module-global, touched from worker submit threads, and
#: guarded by its own module lock; note() must never call back into
#: coordinator-side locks while holding it.
GUARDED_BY = {
    "CoverageLedger": {"<extern>": ()},
    "<module>": {"_NOTE_LOCK": ("_NOTES", "_COLLECTOR")},
}


def coverage_enabled() -> bool:
    return envreg.get_bool(ENABLE_ENV)


def max_gaps() -> int:
    return max(1, envreg.get_int(MAX_GAPS_ENV, 64))


class IntervalSet:
    """Sorted, merged set of [start, end) integer intervals.

    The one interval implementation in the repo: the Dispatcher's
    completed set, the ledger's covered/accounted sets, and the
    offline auditor all use it.  ``add`` merges in O(log n + k) and
    returns the NEWLY covered length -- the overlap detector:
    ``(end - start) - add(start, end)`` indices were already covered.
    """

    def __init__(self, intervals=()):
        self._iv: list[list] = []
        for s, e in intervals:
            self.add(s, e)

    def add(self, start: int, end: int) -> int:
        if end <= start:
            return 0
        before = self._covered_within(start, end)
        iv = self._iv
        # binary search for insertion point by start
        lo, hi = 0, len(iv)
        while lo < hi:
            mid = (lo + hi) // 2
            if iv[mid][0] < start:
                lo = mid + 1
            else:
                hi = mid
        # merge with predecessor if touching
        i = lo
        if i > 0 and iv[i - 1][1] >= start:
            i -= 1
            iv[i][1] = max(iv[i][1], end)
        else:
            iv.insert(i, [start, end])
        # absorb successors
        j = i + 1
        while j < len(iv) and iv[j][0] <= iv[i][1]:
            iv[i][1] = max(iv[i][1], iv[j][1])
            j += 1
        del iv[i + 1:j]
        return (end - start) - before

    def _covered_within(self, start: int, end: int) -> int:
        """Indices of [start, end) already covered -- the pre-insert
        overlap measurement.  Binary search to the first interval that
        could intersect, then walk the (few) intersecting ones."""
        iv = self._iv
        lo, hi = 0, len(iv)
        while lo < hi:
            mid = (lo + hi) // 2
            if iv[mid][1] <= start:
                lo = mid + 1
            else:
                hi = mid
        covered = 0
        for s, e in iv[lo:]:
            if s >= end:
                break
            covered += min(e, end) - max(s, start)
        return covered

    def covered(self) -> int:
        return sum(e - s for s, e in self._iv)

    def contains_range(self, start: int, end: int) -> bool:
        for s, e in self._iv:
            if s <= start and end <= e:
                return True
        return False

    def gaps(self, upto: int) -> list[tuple]:
        """Uncovered ranges within [0, upto)."""
        out, prev = [], 0
        for s, e in self._iv:
            if s >= upto:
                break
            if s > prev:
                out.append((prev, min(s, upto)))
            prev = max(prev, e)
        if prev < upto:
            out.append((prev, upto))
        return out

    def intervals(self) -> list[tuple]:
        return [(s, e) for s, e in self._iv]


def coverage_digest(keyspace: int, intervals) -> str:
    """Order-independent digest of a coverage state: sha256 over the
    keyspace size and the CANONICAL merged [start, end) intervals --
    any insertion order (or pre-merged journal form) of the same
    covered set digests identically.  Same 16-hex shape as
    ``session.job_fingerprint``."""
    iv = IntervalSet(intervals)
    h = hashlib.sha256()
    h.update(f"{int(keyspace)}|".encode())
    h.update(",".join(f"{s}-{e}" for s, e in iv.intervals()).encode())
    return h.hexdigest()[:16]


class CoverageLedger:
    """Per-job live coverage accounting; see the module docstring.

    Every index of [0, keyspace) must at all times be in exactly one
    of: the covered set, a LIVE unit (split but not completed --
    pending, outstanding, or parked), or the unsplit tail above the
    split frontier.  ``complete`` moving a live range into the covered
    set is the only legal transfer; anything that breaks the partition
    surfaces as overlap (double-covered indices) or gap (lost
    indices).
    """

    def __init__(self, keyspace: int, job_id: str = "j0",
                 registry=None, enabled: Optional[bool] = None,
                 order=None):
        self.keyspace = int(keyspace)
        self.job_id = job_id
        #: rank<->index bijection of the owning dispatcher (or None =
        #: identity).  The ledger's interval arithmetic runs in the
        #: dispatcher's native space -- under an order that is RANK
        #: space, where exactly-once is the same invariant (a bijection
        #: preserves overlaps and gaps) -- and only digest()/
        #: covered_intervals() translate to the canonical index image
        #: the journal and `dprf audit` compare against.
        self.order = order
        self.enabled = (coverage_enabled() if enabled is None
                        else enabled)
        self._covered = IntervalSet()
        #: unit id -> (start, end) of every split-but-not-completed
        #: unit (pending, outstanding, or parked)
        self._live: dict[int, tuple] = {}
        self._live_len = 0
        #: split frontier: max end of any split unit or restored
        #: interval; [frontier, keyspace) is the unsplit tail
        self._frontier = 0
        self.overlap_total = 0
        self.abandoned = False
        #: event counts by declared name (includes worker-side names
        #: for schema completeness; those count in note(), not here)
        self.counts: dict[str, int] = {n: 0 for n in EVENT_NAMES}
        # the three coverage gauges -- this is their ONE declaration
        # site (analysis/metrics.py rule 1); the coverage_gap alert
        # rule (telemetry/alerts.py) reads the gap gauge
        from dprf_tpu.telemetry import get_registry
        m = get_registry(registry)
        self._g_fraction = m.gauge(
            "dprf_job_coverage_fraction",
            "fraction of the job's keyspace in the covered set",
            labelnames=("job",))
        self._g_overlap = m.gauge(
            "dprf_job_coverage_overlap_total",
            "keyspace indices covered MORE than once (a stale lease "
            "past the guard, a resume re-running finished work) -- "
            "wasted H/s, and evidence the exactly-once invariant "
            "broke", labelnames=("job",))
        self._g_gap = m.gauge(
            "dprf_job_coverage_gap_total",
            "keyspace indices in no population at all (not covered, "
            "not on a live unit, not unsplit) -- candidates LOST; "
            "the coverage_gap alert fires on nonzero",
            labelnames=("job",))
        if self.enabled:
            self._g_fraction.set(0.0 if self.keyspace else 1.0,
                                 job=job_id)
            self._g_overlap.set(0, job=job_id)
            self._g_gap.set(0, job=job_id)

    # -- the one event API ----------------------------------------------

    def event(self, name: str, start: int = 0, end: int = 0,
              unit: Optional[int] = None, **attrs) -> None:
        """Fold one range-mutating event into the ledger.  ``name``
        must be a declared member of EVENT_NAMES (the coverage-events
        analyzer enforces literal call sites; this guard catches
        dynamic ones)."""
        if name not in EVENT_NAMES:
            raise ValueError(f"undeclared coverage event: {name!r}")
        if not self.enabled:
            return
        self.counts[name] += 1
        if name == "split":
            if unit is not None:
                self._live[unit] = (start, end)
                self._live_len += end - start
            if end > self._frontier:
                self._frontier = end
            self._update_gauges()
        elif name == "restore":
            over = (end - start) - self._covered.add(start, end)
            if over:
                self.overlap_total += over
            if end > self._frontier:
                self._frontier = end
            self._update_gauges()
        elif name == "complete":
            rng = self._live.pop(unit, None)
            if rng is not None:
                self._live_len -= rng[1] - rng[0]
            over = (end - start) - self._covered.add(start, end)
            if over:
                self.overlap_total += over
            self._update_gauges()
        elif name == "abandon":
            self._live.clear()
            self._live_len = 0
            self.abandoned = True
            self._update_gauges()
        # lease/fail/reissue/park/unpark/resplit/force_complete move
        # no ranges between populations: count-only

    # -- verdicts --------------------------------------------------------

    def fraction(self) -> float:
        if self.keyspace <= 0:
            return 1.0
        return self._covered.covered() / self.keyspace

    def gaps(self) -> list[tuple]:
        """Lost ranges: keyspace indices neither covered, nor live on
        a split unit, nor above the split frontier.  Empty on every
        healthy ledger; an abandoned (cancelled) job's dropped units
        are intentional and not reported as loss."""
        if self.abandoned:
            return []
        acc = IntervalSet(self._covered.intervals())
        for s, e in self._live.values():
            acc.add(s, e)
        if self._frontier < self.keyspace:
            acc.add(self._frontier, self.keyspace)
        return acc.gaps(self.keyspace)[:max_gaps()]

    def gap_total(self) -> int:
        return sum(e - s for s, e in self.gaps())

    def digest(self) -> str:
        """Digest of the covered set over its canonical INDEX image;
        computed even when disabled (the resume rebuild check must not
        depend on a telemetry knob)."""
        return coverage_digest(self.keyspace, self.covered_intervals())

    def covered_intervals(self) -> list[tuple]:
        """Covered set in index space (the journal-comparable form);
        lazily translated -- the hot event path never pays for the
        bijection."""
        if self.order is not None:
            return self.order.index_image(self._covered.intervals())
        return self._covered.intervals()

    def live_units(self) -> dict:
        return dict(self._live)

    def summary(self) -> dict:
        """One-call state dump: the journal coverage record and the
        job-status payload."""
        return {"job": self.job_id,
                "keyspace": self.keyspace,
                "covered": self._covered.covered(),
                "fraction": round(self.fraction(), 6),
                "overlap": self.overlap_total,
                "gap": self.gap_total(),
                "live_units": len(self._live),
                "frontier": self._frontier,
                "abandoned": self.abandoned,
                "digest": self.digest(),
                "events": {n: c for n, c in self.counts.items() if c}}

    def _update_gauges(self) -> None:
        self._g_fraction.set(round(self.fraction(), 6),
                             job=self.job_id)
        self._g_overlap.set(self.overlap_total, job=self.job_id)
        self._g_gap.set(self.gap_total(), job=self.job_id)


# ---------------------------------------------------------------------------
# worker-side note API

#: module-global note state (GUARDED_BY <module> above): counters for
#: worker-side events, and an optional collector the chaos harness /
#: tests install to receive (name, start, end, attrs) per note
_NOTE_LOCK = threading.Lock()
_NOTES: dict = {n: 0 for n in NOTE_EVENTS}
_COLLECTOR = None


def note(name: str, start: int = 0, end: int = 0, **attrs) -> None:
    """Worker-side coverage event (redrive / rescan / superstep
    window).  Hot-path cheap by design: a guarded counter bump, plus
    the installed collector if any -- no RPC, no allocation beyond the
    attrs dict the caller already built."""
    if name not in EVENT_NAMES:
        raise ValueError(f"undeclared coverage event: {name!r}")
    if not coverage_enabled():
        return
    with _NOTE_LOCK:
        _NOTES[name] = _NOTES.get(name, 0) + 1
        cb = _COLLECTOR
    if cb is not None:
        # called OUTSIDE the note lock: a collector is arbitrary test
        # code and must not serialize worker submit threads
        cb(name, int(start), int(end), attrs)


def install_collector(cb) -> None:
    """Install a process-local collector receiving every note():
    ``cb(name, start, end, attrs)``.  Tests and the chaos harness use
    it to assert superstep windows / redrives tile each unit exactly
    once; pass None to uninstall."""
    global _COLLECTOR
    with _NOTE_LOCK:
        _COLLECTOR = cb


def notes() -> dict:
    """Snapshot of the worker-side note counters."""
    with _NOTE_LOCK:
        return dict(_NOTES)


def reset_notes() -> None:
    with _NOTE_LOCK:
        for k in list(_NOTES):
            _NOTES[k] = 0
