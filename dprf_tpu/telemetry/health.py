"""Fleet health plane (ISSUE 10): worker heartbeats, a
healthy->degraded->missing->dead state machine, and straggler
detection.

PRs 1/4/9 built the measurement side of observability; nothing turned
those streams into actionable signals -- a dead worker was only
noticed passively when its lease expired, and the
``dprf_worker_last_seen_timestamp`` gauge covered lease-HOLDERS only.
This module is the coordinator-side half of the fix:

  - every worker contact (an explicit ``op_heartbeat``, or the
    lease/complete traffic that makes one redundant) lands in a
    ``HealthRegistry`` via ``observe()``, carrying an optional
    capability/health payload (device kind, pipeline depth, queue
    depth, recent H/s, last error);
  - ``evaluate()`` (driven on the ``DPRF_ALERT_EVAL_S`` loop by
    ``CoordinatorState.health_tick``) ages each worker against the
    ``DPRF_HEARTBEAT_S`` interval -- HEALTHY within 2 beats, DEGRADED
    past 2, MISSING past 4, DEAD past 12 -- and flags STRAGGLERS: a
    worker whose throughput EWMA sits far below the fleet's robust
    median (modified z-score over the median absolute deviation; with
    a degenerate MAD, anything under half the median).

State lands in three places: the ``dprf_worker_health_state{worker}``
gauge (0=healthy 1=degraded 2=missing 3=dead -- the alert engine's
``worker_missing`` rule thresholds it), ``dprf_worker_straggler`` /
``dprf_worker_rate_hs`` gauges, and a TRANSITION queue the caller
drains from ``evaluate()`` -- ``cli.cmd_serve`` journals each one as a
``{"type": "worker_health"}`` session record, so a post-mortem can
replay exactly when the fleet decayed.

Thread model: ``observe()`` is called from RPC handler threads (under
``CoordinatorState.lock``) and ``evaluate()`` from the health-monitor
thread; all mutable state moves under ``_lock`` (declared below).
Transition CALLBACKS never fire under ``_lock`` -- they are queued and
drained by ``evaluate()``'s caller, which may take the coordinator
lock around journaling without creating a lock cycle.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from dprf_tpu.telemetry import get_registry
from dprf_tpu.utils import env as envreg

#: worker health states, in decay order; gauge values are the index
STATE_NAMES = ("healthy", "degraded", "missing", "dead")
HEALTHY, DEGRADED, MISSING, DEAD = range(4)

#: decay thresholds, in multiples of the heartbeat interval: one
#: missed beat is network noise, two is degraded, four is missing
#: (the ``worker_missing`` alert condition), twelve is dead
DEGRADED_AFTER = 2.0
MISSING_AFTER = 4.0
DEAD_AFTER = 12.0

#: distinct worker ids tracked (ids are client-controlled; past the
#: cap new ids share one "_overflow" record so churn cannot grow
#: coordinator memory -- same stance as the last-seen gauge cap)
MAX_WORKERS = 256

#: straggler rule: modified z-score (0.6745 * dev / MAD) at or below
#: -STRAGGLER_Z flags the worker; fleets smaller than the minimum
#: have no meaningful median to deviate from
STRAGGLER_Z = 3.5
STRAGGLER_MIN_FLEET = 3
#: MAD-degenerate fallback (a homogeneous fleet has MAD 0): a worker
#: under this fraction of the median is a straggler
STRAGGLER_FLOOR_FRAC = 0.5

#: throughput EWMA smoothing for the per-worker rate estimate
RATE_ALPHA = 0.3

#: heartbeat payload sanitization (client-controlled data).  The hbm_*
#: fields are the worker's device-memory totals (telemetry/devstats
#: summary; ISSUE 13) -- how the coordinator sees fleet HBM headroom
#: without a second RPC.
PAYLOAD_KEYS = ("engine", "device", "device_kind", "chips", "depth",
                "queue", "rate_hs", "error", "hbm_in_use",
                "hbm_limit", "hbm_peak", "profile_ts",
                "profile_trigger")
MAX_PAYLOAD_STR = 200

#: lock-discipline declaration (`dprf check` locks analyzer): observe
#: runs on RPC handler threads, evaluate on the monitor thread --
#: the worker table and transition queue move only under ``_lock``.
#: Gauges are set OUTSIDE the lock (the TraceRecorder contract: code
#: holding a declared lock never calls into other locked subsystems).
GUARDED_BY = {
    "HealthRegistry": {
        "_lock": ("_workers", "_transitions"),
    },
}


def heartbeat_interval(default: float = 10.0) -> float:
    """The ``DPRF_HEARTBEAT_S`` cadence; 0 disables explicit
    heartbeats (lease/complete traffic still counts as contact)."""
    v = envreg.get_float("DPRF_HEARTBEAT_S", default)
    return max(0.0, float(v or 0.0))


def _median(vals: list) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    if n % 2:
        return s[mid]
    return 0.5 * (s[mid - 1] + s[mid])


def _clean_payload(payload) -> dict:
    """Bounded, known-keys-only view of a worker's heartbeat payload
    (client-controlled, like ingested trace spans)."""
    if not isinstance(payload, dict):
        return {}
    out = {}
    for k in PAYLOAD_KEYS:
        if k not in payload:
            continue
        v = payload[k]
        if v is None or isinstance(v, bool) or isinstance(v, (int, float)):
            out[k] = v
        else:
            out[k] = str(v)[:MAX_PAYLOAD_STR]
    return out


class WorkerHealth:
    """One worker's live health record (mutated under the registry's
    lock only)."""

    __slots__ = ("worker", "state", "first_seen", "last_seen",
                 "rate_hs", "straggler", "payload", "contacts")

    def __init__(self, worker: str, now: float):
        self.worker = worker
        self.state = HEALTHY
        self.first_seen = now
        self.last_seen = now
        #: throughput EWMA from completed units (cands/s); None until
        #: the first complete carries an elapsed report
        self.rate_hs: Optional[float] = None
        self.straggler = False
        self.payload: dict = {}
        self.contacts = 0

    def as_dict(self, now: float) -> dict:
        return {"state": STATE_NAMES[self.state],
                "age_s": round(max(0.0, now - self.last_seen), 3),
                "rate_hs": (round(self.rate_hs, 3)
                            if self.rate_hs is not None else None),
                "straggler": self.straggler,
                "contacts": self.contacts,
                "payload": dict(self.payload)}


class HealthRegistry:
    """The coordinator's worker-health table + state machine."""

    def __init__(self, registry=None, clock=None, wall=None,
                 heartbeat_s: Optional[float] = None):
        self._clock = clock or time.monotonic
        self._wall = wall or time.time
        #: the aging unit; a 0/None interval falls back to the default
        #: so the state machine still works on fleets that disabled
        #: explicit heartbeats (lease traffic feeds observe instead)
        self.heartbeat_s = (heartbeat_s if heartbeat_s
                            else heartbeat_interval() or 10.0)
        self._lock = threading.Lock()
        self._workers: dict = {}
        #: queued transition dicts, drained (and only then surfaced to
        #: callbacks) by evaluate() -- see the module docstring
        self._transitions: list = []
        m = get_registry(registry)
        self._g_state = m.gauge(
            "dprf_worker_health_state",
            "worker health state machine: 0=healthy 1=degraded "
            "2=missing 3=dead (ages in DPRF_HEARTBEAT_S multiples; "
            "covers every contacting worker, not just lease holders)",
            labelnames=("worker",))
        self._g_straggler = m.gauge(
            "dprf_worker_straggler",
            "1 when the worker's throughput EWMA sits below the "
            "fleet's robust median by the MAD z-score threshold",
            labelnames=("worker",))
        self._g_rate = m.gauge(
            "dprf_worker_rate_hs",
            "per-worker throughput EWMA from completed units "
            "(the straggler detector's input)",
            labelnames=("worker",))

    def _entry(self, worker: str, now: float):
        """Get-or-create under the lock, with the id cap applied."""
        w = self._workers.get(worker)
        if w is None:
            if len(self._workers) >= MAX_WORKERS:
                worker = "_overflow"
                w = self._workers.get(worker)
            if w is None:
                w = self._workers[worker] = WorkerHealth(worker, now)
        return w
    _entry._holds_lock = "_lock"

    def _transition(self, w: WorkerHealth, to: int) -> None:
        self._transitions.append({
            "worker": w.worker, "from": STATE_NAMES[w.state],
            "to": STATE_NAMES[to], "ts": self._wall(),
            "age_s": round(max(0.0, self._clock() - w.last_seen), 3)})
        w.state = to
    _transition._holds_lock = "_lock"

    # -- contact ---------------------------------------------------------

    def observe(self, worker: str, payload=None,
                rate_hs: Optional[float] = None) -> None:
        """One sign of life from a worker: an explicit heartbeat
        (with payload), a lease poll, or a landed complete (with the
        unit's throughput).  Any contact resets the decay clock; a
        missing/dead worker REJOINS (transition back to healthy,
        journaled like the decay was)."""
        now = self._clock()
        gauge = None
        with self._lock:
            w = self._entry(str(worker), now)
            w.last_seen = now
            w.contacts += 1
            if payload is not None:
                w.payload.update(_clean_payload(payload))
            if rate_hs is not None and rate_hs > 0:
                w.rate_hs = (rate_hs if w.rate_hs is None
                             else w.rate_hs
                             + RATE_ALPHA * (rate_hs - w.rate_hs))
            if w.state != HEALTHY:
                self._transition(w, HEALTHY)
            gauge = (w.worker, w.state, w.rate_hs)
        self._g_state.set(gauge[1], worker=gauge[0])
        if gauge[2] is not None:
            self._g_rate.set(gauge[2], worker=gauge[0])

    # -- evaluation ------------------------------------------------------

    def _target_state(self, age: float) -> int:
        hb = self.heartbeat_s
        if age > DEAD_AFTER * hb:
            return DEAD
        if age > MISSING_AFTER * hb:
            return MISSING
        if age > DEGRADED_AFTER * hb:
            return DEGRADED
        return HEALTHY

    def _flag_stragglers(self) -> None:
        """MAD z-score of each live worker's throughput EWMA against
        the fleet median: robust to one outlier dragging the mean,
        deterministic, and cheap at fleet sizes."""
        live = [w for w in self._workers.values()
                if w.state <= DEGRADED and w.rate_hs is not None]
        flags: dict = {}
        if len(live) >= STRAGGLER_MIN_FLEET:
            rates = [w.rate_hs for w in live]
            med = _median(rates)
            mad = _median([abs(r - med) for r in rates])
            for w in live:
                if mad > 0:
                    z = 0.6745 * (w.rate_hs - med) / mad
                    flags[w.worker] = z <= -STRAGGLER_Z
                else:
                    flags[w.worker] = (med > 0 and w.rate_hs
                                       < STRAGGLER_FLOOR_FRAC * med)
        for w in self._workers.values():
            w.straggler = flags.get(w.worker, False)
    _flag_stragglers._holds_lock = "_lock"

    def evaluate(self) -> list:
        """One pass of the state machine + straggler detection;
        returns (and drains) every transition since the last call --
        including rejoins queued by ``observe`` -- so the caller can
        journal them without ever running under this lock."""
        now = self._clock()
        gauges = []
        with self._lock:
            for w in self._workers.values():
                target = self._target_state(now - w.last_seen)
                if target > w.state:     # decay only; observe() heals
                    self._transition(w, target)
            self._flag_stragglers()
            for w in self._workers.values():
                gauges.append((w.worker, w.state, w.straggler))
            out = self._transitions
            self._transitions = []
        for worker, state, straggler in gauges:
            self._g_state.set(state, worker=worker)
            self._g_straggler.set(1 if straggler else 0, worker=worker)
        return out

    # -- reads -----------------------------------------------------------

    def states(self) -> dict:
        """{worker: state name} -- the ``dprf top`` HEALTH column."""
        with self._lock:
            return {w.worker: STATE_NAMES[w.state]
                    for w in self._workers.values()}

    def snapshot(self) -> dict:
        """{worker: full record} for ``op_health``/``dprf health``."""
        now = self._clock()
        with self._lock:
            return {w.worker: w.as_dict(now)
                    for w in self._workers.values()}

    def slowest_worker(self) -> Optional[str]:
        """The live (healthy/degraded) worker with the lowest
        throughput EWMA -- who a stalled-job alert implicates when no
        label names a worker (the auto-capture target)."""
        with self._lock:
            live = [w for w in self._workers.values()
                    if w.state <= DEGRADED and w.rate_hs is not None
                    and w.worker != "_overflow"]
            if not live:
                return None
            return min(live, key=lambda w: w.rate_hs).worker

    def profile_by_worker(self) -> dict:
        """{worker: {"ts", "trigger"}} from the heartbeat payloads
        (ISSUE 15): each worker's last kernel capture, including
        env-local ones that never pushed a summary -- the fallback
        half of the ``dprf top`` PROF column."""
        with self._lock:
            out = {}
            for w in self._workers.values():
                ts = w.payload.get("profile_ts")
                if isinstance(ts, (int, float)) and not isinstance(
                        ts, bool):
                    out[w.worker] = {
                        "ts": ts,
                        "trigger": w.payload.get("profile_trigger")}
            return out

    def device_kind(self, worker: str) -> Optional[str]:
        """The chip kind this worker's last heartbeat reported (what
        keys its roofline band), or None before its first beat."""
        with self._lock:
            w = self._workers.get(worker)
            return w.payload.get("device_kind") if w else None

    def mem_by_worker(self) -> dict:
        """{worker: hbm bytes in use} from the heartbeat payloads
        (ISSUE 13) -- the ``dprf top`` MEM column; workers on
        backends without memory stats simply have no entry."""
        with self._lock:
            out = {}
            for w in self._workers.values():
                v = w.payload.get("hbm_in_use")
                if isinstance(v, (int, float)) and not isinstance(
                        v, bool):
                    out[w.worker] = int(v)
            return out

    def hbm_totals(self) -> Optional[dict]:
        """Fleet HBM headroom summed over LIVE (healthy/degraded)
        workers' heartbeat payloads: {in_use, limit, workers}; None
        when no worker reported memory stats -- exactly the
        coordinator-side view the capability payload exists for."""
        with self._lock:
            use = limit = n = 0
            for w in self._workers.values():
                if w.state > DEGRADED:
                    continue
                lv = w.payload.get("hbm_limit")
                uv = w.payload.get("hbm_in_use")
                if not isinstance(lv, (int, float)) or isinstance(
                        lv, bool) or lv <= 0:
                    continue
                limit += int(lv)
                use += int(uv) if isinstance(uv, (int, float)) \
                    and not isinstance(uv, bool) else 0
                n += 1
            if n == 0:
                return None
            return {"in_use": use, "limit": limit, "workers": n}


class HealthMonitor:
    """Background evaluation loop: calls ``tick`` (normally
    ``CoordinatorState.health_tick``) every ``DPRF_ALERT_EVAL_S``
    seconds -- the TelemetrySnapshotter shape: daemon thread, Event
    wait, ``stop()`` joins.  A tick failure is logged and the loop
    keeps going: a health-plane bug must never take the serve plane
    down with it."""

    def __init__(self, tick, interval: Optional[float] = None):
        from dprf_tpu.telemetry.alerts import eval_interval
        self.tick = tick
        self.interval = max(0.25, float(
            interval if interval is not None else eval_interval()))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.tick()
            except Exception as e:   # noqa: BLE001 -- keep monitoring
                from dprf_tpu.utils.logging import DEFAULT as log
                log.warn("health tick failed", error=str(e))
                continue

    def start(self) -> "HealthMonitor":
        if self._thread is None:
            self._thread = threading.Thread(target=self._run,
                                            daemon=True,
                                            name="dprf-health")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        try:
            self.tick()          # final pass: journal the end state
        except Exception:        # noqa: BLE001 -- shutdown path
            pass
