"""Host-level distributed backend: coordinator RPC + remote workers.

Inside one host/slice, parallelism is XLA collectives over ICI (the
sharded steps in dprf_tpu/parallel) -- there is no NCCL/MPI analogue to
manage.  ACROSS hosts, the control plane is deliberately tiny, exactly
the Dispatcher surface: lease a WorkUnit, report hits, complete.  This
module is that control plane: newline-delimited JSON over TCP.

    coordinator (dprf serve):  owns Dispatcher + found set + potfile/
        session persistence; hands out leases under a lock.
    worker (dprf worker):      connects, receives the job description,
        rebuilds engine/generator/targets locally, then loops
        lease -> fused device sweep -> complete(hits).

Fault model: a worker that dies simply stops leasing; its outstanding
unit's lease expires and the Dispatcher reissues it (idempotent -- units
are pure functions of the index range).  A worker that reports hits for
an already-reissued unit is harmless: hits are deduped by target.

Trust model: optional shared-secret authentication (--token).  When the
coordinator has a token, every connection must answer an HMAC-SHA256
challenge on hello before any other op is served (the challenge nonce
rotates after every failed attempt and a connection is dropped after a
few failures, so a connection cannot grind guesses against one nonce);
the worker may send its own nonce in hello, and the coordinator's reply
proves knowledge of the token over it -- mutual authentication.
Without a token the protocol is open -- bind to localhost or a trusted
network only (same stance as hashtopolis-style agents).  The transport
is cleartext either way: the token authenticates peers, it does not
encrypt the job.  The job description includes the raw hashlist lines;
wordlist files must exist on each worker host (they are referenced by
path, never shipped).
"""

from __future__ import annotations

import hmac as hmac_mod
import json
import re
import secrets
import socket
import socketserver
import threading
import time
from typing import Callable, Optional

from dprf_tpu.jobs.scheduler import CANCELLED as JOB_CANCELLED
from dprf_tpu.runtime.dispatcher import Dispatcher
from dprf_tpu.runtime.worker import Hit
from dprf_tpu.runtime.workunit import WorkUnit
from dprf_tpu.telemetry import declare_job_metrics, get_registry
from dprf_tpu.telemetry import perf as perf_mod
from dprf_tpu.telemetry import profiler as profiler_mod
from dprf_tpu.telemetry import programs as programs_mod
from dprf_tpu.telemetry.alerts import AlertEngine
from dprf_tpu.telemetry.health import HealthRegistry, heartbeat_interval
from dprf_tpu.telemetry.trace import get_tracer, jax_profile_ctx

MAX_LINE = 64 << 20   # hashlists can be large; candidates never cross

#: leases one worker may hold at once (and the clamp on a lease
#: request's ``ahead``): bounds how much of the queue a buggy or
#: greedy client can vacuum into one host's ledger
MAX_LEASE_AHEAD = 16

#: spans one op_trace_push message may carry (a worker's whole local
#: ring, vs the per-unit MAX_INGEST_SPANS bound on complete/fail)
TRACE_PUSH_MAX = 2048

#: lock-discipline declarations (`dprf check` locks analyzer).  Every
#: worker connection is its own handler thread in a
#: ThreadingTCPServer, all mutating this state: the listed
#: CoordinatorState attributes must only be touched inside ``with
#: <state>.lock`` (or a method annotated ``_holds_lock``).  The
#: _CompletionSender flags are single-writer latched (assigned only by
#: its own thread's ``_run``, read cross-thread) -- GIL-atomic by
#: design, which ``<atomic>`` makes the checker enforce rather than
#: assume.
GUARDED_BY = {
    "CoordinatorState": {
        "lock": ("found", "dispatcher", "scheduler", "rejected",
                 "worker_rejects", "unit_reject_workers",
                 "quarantined", "_pull_epoch", "_profile_requests",
                 "_profile_summaries", "_profile_seq",
                 "_profile_last", "_profile_inflight",
                 "_profile_unread"),
    },
    "_CompletionSender": {"<atomic>": ("error", "stop_seen")},
}

#: kernel-profile summaries retained per worker (op_profile serves
#: the newest first; older captures live in the session journal)
PROFILE_SUMMARIES_PER_WORKER = 4

#: a pending capture request nobody picked up (worker named wrong,
#: dead, or never leasing) expires after this long -- the table stays
#: bounded and a stale entry can't suppress that worker's future
#: auto-captures forever
PROFILE_REQUEST_TTL_S = 600.0

#: a DELIVERED capture request whose summary never came back (worker
#: died mid-capture) expires after this long; until then the serve
#: drain loop keeps the RPC plane up so a capture racing the job's
#: end can still land its push
PROFILE_INFLIGHT_TTL_S = 180.0

#: an UNDELIVERED request holds the serve drain only this long: its
#: target either leases within seconds (delivery moves it to the
#: inflight ledger) or already exited -- the full request TTL would
#: pin a finished serve for minutes on a dead target
PROFILE_QUEUED_DRAIN_S = 30.0

#: a landed-but-unread summary holds the serve drain this long: the
#: requester polls op_profile every ~0.5 s, so without this grace the
#: drain could break between the worker's push and the poller's next
#: read and the CLI would hit a closed socket instead of its summary
PROFILE_READ_GRACE_S = 10.0

#: resource-ownership declarations (`dprf check` threads analyzer):
#: every socket/stream attribute acquired outside a ``with`` names
#: the method that releases it, and the analyzer verifies that
#: method really closes it on the shutdown path.
RELEASES = {
    "CoordinatorClient": {"_sock": "close", "_fh": "close"},
}

#: `dprf check` retrace analyzer: the remote pipelined sweep loop --
#: a host sync here serializes the device stream against RPC latency.
HOT_PATHS = ("worker_loop",)


class RpcError(RuntimeError):
    """Protocol-level failure talking to the coordinator (error
    response, auth failure).  Distinct from RuntimeError so the CLI can
    report it cleanly without swallowing unrelated internal errors."""


# ---------------------------------------------------------------------------
# framing

def send_msg(sock: socket.socket, obj: dict) -> None:
    sock.sendall(json.dumps(obj, separators=(",", ":")).encode() + b"\n")


def recv_msg(fh) -> Optional[dict]:
    line = fh.readline(MAX_LINE)
    if not line:
        return None
    if not line.endswith(b"\n"):
        # readline returned MAX_LINE bytes without a newline: reject
        # loudly instead of parsing a truncated message and desyncing
        # the framing on whatever bytes remain
        raise ValueError(f"message exceeds the {MAX_LINE}-byte frame limit")
    return json.loads(line)


# ---------------------------------------------------------------------------
# coordinator side

class CoordinatorState:
    """Shared, locked serve-plane state behind the RPC handlers.

    Multi-tenant (ISSUE 8): the state owns a jobs.JobScheduler -- a
    queue of Job records, each with its OWN Dispatcher, found set, hit
    buffer, verifier, and limits -- and the ctor's (job, dispatcher,
    n_targets, verifier) become the DEFAULT job (id = the dispatcher's
    ``job_id``, "j0").  ``self.job`` / ``self.dispatcher`` /
    ``self.found`` / ``self.verifier`` stay aliases of that default
    job, so every pre-multi-tenant caller and client reads exactly
    what it always did; further jobs arrive over ``op_job_submit``.
    """

    def __init__(self, job: dict, dispatcher: Dispatcher, n_targets: int,
                 on_hit: Optional[Callable] = None,
                 on_progress: Optional[Callable] = None,
                 verifier: Optional[Callable] = None,
                 token: Optional[str] = None, registry=None,
                 recorder=None, scheduler=None, job_builder=None,
                 on_job_hit: Optional[Callable] = None,
                 on_job_event: Optional[Callable] = None,
                 on_job_progress: Optional[Callable] = None,
                 owner: str = "local", priority: int = 1,
                 quota: Optional[int] = None,
                 owner_quotas: Optional[dict] = None):
        from dprf_tpu.jobs.scheduler import JobScheduler
        self.job = job                    # serializable job description
        self.dispatcher = dispatcher
        self.n_targets = n_targets
        self.on_hit = on_hit              # (target_index, cand_index, plain)
        self.on_progress = on_progress
        #: per-job (Job, target_index, cand_index, plain): the
        #: multi-tenant hit hook (session journaling, potfile) -- fires
        #: for EVERY job, where on_hit stays default-job-only
        self.on_job_hit = on_job_hit
        #: (kind, Job) for job lifecycle events ("submit", "cancel",
        #: "pause", "resume") -- how the serve front-end journals them
        self.on_job_event = on_job_event
        #: (job_id, completed_intervals, coverage_digest) after every
        #: landed complete: the per-job session-journal hook (tagged
        #: ``units`` records, digest riding each snapshot -- ISSUE 19)
        self.on_job_progress = on_job_progress
        #: spec -> (wire_job, dispatcher, targets, verifier) for
        #: op_job_submit; defaults to jobs.build.build_job_runtime
        self.job_builder = job_builder
        #: (target_index, plaintext) -> bool.  A worker with a buggy or
        #: malicious device path could report a wrong plaintext; accepting
        #: it would permanently mark the target found and poison the
        #: potfile/session journal.  One oracle hash per hit is negligible.
        self.verifier = verifier
        self.rejected = 0
        #: a worker whose hits keep failing verification has a broken
        #: (or malicious) device path; quarantining it stops the
        #: lease -> reject -> requeue livelock (same unit bouncing to
        #: the same worker forever).
        self.worker_rejects: dict[str, int] = {}
        self.unit_reject_workers: dict[tuple, set] = {}
        self.quarantined: set[str] = set()
        self.token = token                # None = unauthenticated protocol
        self.lock = threading.Lock()
        self.t0 = time.perf_counter()
        #: flight-recorder pull epoch (op_trace_pull arm=True bumps
        #: it): lease responses carry it, and a worker seeing a new
        #: epoch ships its LOCAL ring back via op_trace_push
        self._pull_epoch = 0
        self.scheduler = scheduler if scheduler is not None \
            else JobScheduler(registry=registry,
                              owner_quotas=owner_quotas)
        default = self.scheduler.add(
            job, dispatcher, n_targets, verifier=verifier,
            owner=owner, priority=priority, quota=quota,
            job_id=dispatcher.job_id)
        #: the default job's found set IS self.found (same dict): the
        #: single-job callers that read/seed state.found keep working
        self.found = default.found
        self.default_job_id = default.job_id
        #: the registry the RPC port's /metrics endpoint serves; the
        #: Dispatcher publishes unit/keyspace metrics into the same one
        self.registry = get_registry(registry)
        #: the flight recorder op_trace_tail serves; should be the
        #: SAME one the Dispatcher records into so the timeline is
        #: whole (both default to the process-wide recorder)
        self.tracer = get_tracer(recorder)
        #: fleet health plane (ISSUE 10): worker state machine +
        #: straggler detection fed by op_heartbeat and the
        #: lease/complete traffic; evaluated by health_tick on the
        #: DPRF_ALERT_EVAL_S loop (cli.cmd_serve's HealthMonitor)
        self.health = HealthRegistry(registry=registry)
        #: declarative alert rules over the same registry; pending ->
        #: firing -> resolved lifecycle served via op_alerts
        self.alerts = AlertEngine(registry=registry)
        #: compiled-program registry (ISSUE 13): the coordinator's own
        #: compile sites land here, and op_heartbeat merges the
        #: records workers ship -- op_programs serves the fleet view.
        #: Has its own lock (never touched under self.lock).
        self.programs = programs_mod.get_programs()
        #: (transition dict) hook: cmd_serve journals each fleet
        #: health transition as a {"type": "worker_health"} record;
        #: fired by health_tick UNDER the lock so the journal writes
        #: serialize with the hit/progress writers
        self.on_worker_health: Optional[Callable] = None
        #: kernel-profiling plane (ISSUE 15): pending capture
        #: requests per worker (delivered on the next lease/heartbeat
        #: response), the sanitized summaries workers pushed back,
        #: and the auto-capture cooldown ledger
        self._profile_requests: dict = {}
        self._profile_summaries: dict = {}
        self._profile_seq = 0
        self._profile_last: dict = {}
        #: delivered-but-unanswered capture requests ({id: delivered
        #: monotonic ts}): serve's drain loop waits on these so a
        #: capture racing job-end can land; TTL-expired by the prune
        self._profile_inflight: dict = {}
        #: per-worker monotonic ts of a summary push nobody has read
        #: yet: holds the serve drain for a short grace so the
        #: requester's next poll can collect it (cleared only for the
        #: workers a read actually shipped -- a filtered poll for
        #: worker A must not drop worker B's grace)
        self._profile_unread: dict = {}
        #: (worker, summary) hook: cmd_serve journals each pushed
        #: capture as a {"type": "profile"} record; fired UNDER the
        #: lock like the other journaling hooks
        self.on_profile: Optional[Callable] = None
        m = self.registry
        #: verify-phase attribution (telemetry/perf.py): the oracle
        #: re-hash cost of every hit batch, labeled per job
        self._h_phase = perf_mod.phase_histogram(m)
        jm = declare_job_metrics(m)
        self._m_hits = jm["hits"]
        self._m_rejects = jm["rejects"]
        self._m_cands = jm["cands"]
        self._g_targets = jm["targets"]
        self._g_found = jm["found"]
        self._m_rpc = m.counter(
            "dprf_rpc_requests_total", "RPC ops served",
            labelnames=("op",))
        self._g_quar = m.gauge(
            "dprf_workers_quarantined", "workers benched for repeated "
            "unverifiable hits")
        self._g_seen = m.gauge(
            "dprf_worker_last_seen_timestamp",
            "unix time of each worker's last lease/complete/"
            "heartbeat (ISSUE 10: heartbeats widened this beyond "
            "lease holders)",
            labelnames=("worker",))
        self._g_targets.set(n_targets)
        self._g_found.set(0)
        self._g_quar.set(0)

    #: distinct worker ids the liveness gauge will track; label
    #: children live for the registry's lifetime, so id CHURN (every
    #: restart is a new hostname:pid) must not grow coordinator memory
    #: without bound on a long-lived job
    MAX_WORKER_LABELS = 1024

    def _touch_worker(self, wid: str) -> None:
        """Liveness: scrape-visible last-contact time per worker.
        Past the label cap, overflow ids share one child -- the fleet
        stays observable even when individual ids stop being.  (The
        check-then-set pair is not atomic; concurrent handlers can
        overshoot the cap by a few children, which is fine -- the cap
        bounds growth, it is not an exact quota.)"""
        if (not self._g_seen.has_labels(worker=wid)
                and self._g_seen.child_count() >= self.MAX_WORKER_LABELS):
            wid = "_overflow"
        self._g_seen.set(time.time(), worker=wid)

    def health_tick(self) -> None:
        """One fleet-health evaluation pass (ISSUE 10), driven by the
        HealthMonitor loop every ``DPRF_ALERT_EVAL_S`` seconds: age
        the worker state machine + straggler detection, update the
        per-job SLO gauges, journal the drained transitions, then run
        the alert rules against the registry.  Lock discipline: the
        health registry and alert engine evaluate under their OWN
        locks (never nested inside ours); only the scheduler pass and
        the journaling callback take ``self.lock``."""
        transitions = self.health.evaluate()
        with self.lock:
            self.scheduler.update_slos()
            if self.on_worker_health:
                for tr in transitions:
                    self.on_worker_health(tr)
        events = self.alerts.evaluate()
        # alert-triggered kernel profiling (ISSUE 15): a straggler or
        # stalled-job alert FIRING requests one bounded capture window
        # on the implicated worker, cooldown-rate-limited
        self._maybe_autoprofile(events)

    def _maybe_autoprofile(self, events: list) -> None:
        """Queue a capture request for each newly-firing straggler /
        job_stalled alert (``DPRF_AUTOPROFILE``): the straggler rule
        names its worker in the labels; a stalled job implicates the
        fleet's slowest live worker.  One request per cooldown window
        (``DPRF_PROFILE_COOLDOWN_S``, global AND per worker) -- a
        flapping fleet must not spend its cycles profiling itself."""
        if not profiler_mod.autoprofile_enabled():
            return
        fired = [e for e in events
                 if e.get("state") == "firing"
                 and e.get("rule") in ("straggler", "job_stalled")]
        if not fired:
            return
        cooldown = profiler_mod.cooldown_s()
        now = time.monotonic()
        from dprf_tpu.utils.logging import DEFAULT as log
        # resolved OUTSIDE self.lock: slowest_worker takes the health
        # registry's own lock, and health_tick's contract is that the
        # two are acquired sequentially, never nested
        slowest = (self.health.slowest_worker()
                   if any("worker" not in (e.get("labels") or {})
                          for e in fired) else None)
        with self.lock:
            self._prune_profile_requests(now)
            for e in fired:
                worker = (e.get("labels") or {}).get("worker")
                if worker is None:
                    worker = slowest
                if worker is None or worker in self._profile_requests:
                    continue
                if len(self._profile_requests) >= self.MAX_WORKER_LABELS:
                    break       # table bound; entries expire by TTL
                last = max((self._profile_last.get("_global", 0.0),
                            self._profile_last.get(str(worker), 0.0)))
                if last and now - last < cooldown:
                    continue
                self._profile_seq += 1
                self._profile_requests[str(worker)] = {
                    "id": self._profile_seq,
                    "seconds": profiler_mod.default_window_s(),
                    "trigger": str(e.get("rule")),
                    "queued_at": now}
                self._profile_last["_global"] = now
                self._profile_last[str(worker)] = now
                log.info("auto-capture requested", worker=worker,
                         rule=e.get("rule"))

    def _prune_profile_requests(self, now: float) -> None:
        """Expire pending capture requests nobody picked up inside
        the TTL (dead / misnamed / never-leasing workers) and
        delivered requests whose summary never came back: keeps the
        client-fed tables bounded, unsticks auto-capture, and
        unblocks the serve drain loop."""
        stale = [w for w, r in self._profile_requests.items()
                 if now - r.get("queued_at", now)
                 > PROFILE_REQUEST_TTL_S]
        for w in stale:
            del self._profile_requests[w]
        dead = [rid for rid, ts in self._profile_inflight.items()
                if now - ts > PROFILE_INFLIGHT_TTL_S]
        for rid in dead:
            del self._profile_inflight[rid]
        unread = [w for w, ts in self._profile_unread.items()
                  if now - ts > PROFILE_READ_GRACE_S]
        for w in unread:
            del self._profile_unread[w]
    _prune_profile_requests._holds_lock = "lock"

    def _profile_request_for(self, wid: str) -> Optional[dict]:
        """Pop the pending capture request riding out on this
        worker's next lease/heartbeat response (None for most)."""
        if not self._profile_requests:
            return None
        req = self._profile_requests.pop(wid, None)
        if req is None:
            return None
        self._profile_inflight[req["id"]] = time.monotonic()
        req = dict(req)
        req.pop("queued_at", None)    # coordinator-clock bookkeeping
        return req
    _profile_request_for._holds_lock = "lock"

    def profile_pending(self) -> bool:
        """True while a capture request is delivered but unanswered
        (inside its TTL), or queued and young enough that delivery is
        still plausible: the serve drain loop keeps the RPC plane up
        for these, so a capture racing the job's last units can still
        land its summary."""
        with self.lock:
            now = time.monotonic()
            self._prune_profile_requests(now)
            if self._profile_inflight:
                return True
            if any(now - ts < PROFILE_READ_GRACE_S
                   for ts in self._profile_unread.values()):
                return True
            return any(now - r.get("queued_at", now)
                       < PROFILE_QUEUED_DRAIN_S
                       for r in self._profile_requests.values())

    def refresh_found_gauge(self) -> None:
        """Re-sync dprf_targets_found/_total after out-of-band
        mutations (potfile preload / session restore in
        cli.cmd_serve, job submit/restore)."""
        with self.lock:
            self._g_found.set(self.scheduler.found_total())
            self._g_targets.set(self.scheduler.targets_total())

    def seed_found(self, hits: list) -> None:
        """Seed the DEFAULT job from journaled hit records (resume):
        goes through the job's hit buffer so `op_hits_pull` clients
        see restored hits too, tolerant of malformed entries."""
        with self.lock:
            job = self.scheduler.get(self.default_job_id)
            for h in hits:
                try:
                    job.record_hit(int(h["target"]), int(h["index"]),
                                   bytes.fromhex(h["plaintext"]))
                except (KeyError, ValueError, TypeError):
                    continue

    #: rejected completions before a worker is quarantined.  Lower than
    #: the unit threshold so a single bad worker is benched while its
    #: unit can still requeue to an honest one.
    MAX_WORKER_REJECTS = 2
    #: DISTINCT workers whose reports on one unit were all rejected
    #: before the unit is force-completed (a logged potential coverage
    #: hole beats a job that can never terminate when every worker's
    #: device path is divergent)
    MAX_UNIT_REJECT_WORKERS = 3

    # -- RPC ops ---------------------------------------------------------

    def op_hello(self, msg: dict,
                 auth_owner: Optional[str] = None) -> dict:
        # the default job + its scheduler id: a multi-job worker seeds
        # its per-job worker cache with this one and fetches further
        # specs through op_job_status as their units arrive.  The
        # echoed owner is the identity the handler loop AUTHENTICATED
        # this connection as -- the client's claim (msg["owner"]
        # rides the auth handshake) is confirmed only when the hmac
        # over the owner-derived token proved it; on an open or
        # admin connection there is no tenant scoping, so the echo
        # is None no matter what the client claimed.
        return {"ok": True, "job": self.job,
                "job_id": self.default_job_id,
                "owner": auth_owner if msg.get("owner") else None}

    def op_lease(self, msg: dict) -> dict:
        """Hand out the next unit(s), fair-share-selected ACROSS jobs
        (jobs/scheduler.py).  The lease-ahead form (``ahead=N``)
        returns up to N units in ``"units"`` so a pipelined worker
        fills its submit-ahead queue in ONE round trip; ``"unit"``
        stays the first entry for pre-ahead clients.  Every entry
        names its job; per-worker holdings are capped at
        MAX_LEASE_AHEAD across all jobs.  ``pull`` carries the
        flight-recorder pull epoch (op_trace_pull)."""
        with self.lock:
            pull = self._pull_epoch
            if self._stopped():
                return {"unit": None, "stop": True, "pull": pull}
            raw_wid = msg.get("worker_id")
            wid = str(raw_wid) if raw_wid is not None else "?"
            if raw_wid is not None:
                # any lease poll is a sign of life for the health
                # plane (the idle-aware heartbeat contract: flowing
                # traffic makes explicit beats redundant); the
                # registry caps its own id cardinality
                self.health.observe(wid)
            if wid in self.quarantined:
                return {"unit": None, "stop": False,
                        "quarantined": True, "pull": pull}
            # pending kernel-profile request rides the lease response
            # (ISSUE 15); one dict probe for the common no-request
            # case, so the lease path pays nothing when idle
            prof_req = self._profile_request_for(wid)
            try:
                ahead = int(msg.get("ahead", 1))
            except (TypeError, ValueError):
                ahead = 1
            ahead = max(1, min(ahead, MAX_LEASE_AHEAD))
            # reap BEFORE clamping against this worker's holdings: a
            # restarted worker (same --id) still "holding" its crashed
            # predecessor's expired leases would otherwise clamp to 0
            # forever -- lease() below is the only reap site during an
            # active job, and a clamp of 0 never reaches it
            self.scheduler.reap_expired()
            # age-based job GC (DPRF_JOB_TTL_S): terminal jobs past
            # their TTL leave the table here, journaled so a restart
            # does not resurrect them; the default job is never reaped
            # (state.found aliases its dict)
            for gone in self.scheduler.maybe_gc(
                    keep=(self.default_job_id,)):
                if self.on_job_event:
                    self.on_job_event("gc", gone)
            ahead = min(ahead, max(
                0, MAX_LEASE_AHEAD - self.scheduler.outstanding_for(wid)))
            pairs = self.scheduler.lease_many(wid, ahead)
            if not pairs:
                # nothing leasable right now; workers retry unless NO
                # non-terminal job could ever lease again (a paused
                # job keeps the fleet polling for its resume)
                resp = {"unit": None,
                        "stop": self.scheduler.idle_stop(),
                        "pull": pull}
                if prof_req is not None:
                    resp["profile"] = prof_req
                return resp
            # liveness gauge only for ids that actually HOLD a lease:
            # worker_id is client-controlled, and a label child lives
            # forever, so polls with throwaway ids must not grow the
            # registry (holding a lease bounds the id set by the unit
            # ledger)
            self._touch_worker(wid)
            entries = []
            for job, unit in pairs:
                e = {"id": unit.unit_id, "start": unit.start,
                     "length": unit.length, "job": job.job_id}
                # trace context OUT, per unit: the worker parents its
                # rpc/warmup/sweep spans onto this lease, so the spans
                # it ships back with complete/fail stitch onto the
                # coordinator timeline
                ctx = job.dispatcher.trace_context(unit.unit_id)
                if ctx is not None:
                    e["trace"] = {"trace": ctx[0], "span": ctx[1]}
                entries.append(e)
            resp = {"unit": entries[0], "units": entries, "pull": pull}
            if prof_req is not None:
                resp["profile"] = prof_req
            if "trace" in entries[0]:
                # legacy single-unit clients read a top-level context
                resp["trace"] = entries[0]["trace"]
            return resp

    def op_complete(self, msg: dict) -> dict:
        unit_id = int(msg["unit_id"])
        hits = msg.get("hits", [])
        # per-unit wall time reported by the worker: feeds the adaptive
        # unit sizer's per-worker throughput EWMA (tune.unit_sizer).
        # Client-controlled, so sanitize: a junk value must read as "no
        # report", never as a poisoned estimate.
        elapsed = msg.get("elapsed")
        if not (isinstance(elapsed, (int, float)) and elapsed > 0):
            elapsed = None
        # Parse + verify OUTSIDE the lock: the oracle re-hash takes
        # seconds for bcrypt/PBKDF2, and holding the lock there would
        # stall every other worker's lease/complete (and hand any buggy
        # worker a coordinator-wide DoS).
        raw_job = msg.get("job")
        with self.lock:
            job = self.scheduler.get(
                str(raw_job) if raw_job is not None else None)
            if job is None:
                # unknown job id: nothing to route to -- treat like a
                # stale report (the id was valid when leased only if
                # the coordinator restarted without it)
                return {"ok": True, "stop": self._stopped(),
                        "dropped": True}
            cancelled = job.state == JOB_CANCELLED
            already = set(job.found)
            # the job's verifier/targets are immutable after admission:
            # safe to use outside the lock below
            verifier = job.verifier
            n_targets = job.n_targets
            # trace context of the attempt, read BEFORE complete/fail
            # pops the lease; remote spans + the hit_verify span below
            # parent onto it
            ctx = job.dispatcher.trace_context(unit_id)
        self.tracer.ingest(msg.get("spans"),
                           proc=str(msg.get("worker_id", "?")),
                           sent_at=msg.get("clock"))
        if cancelled:
            # cancel-mid-flight: the unit was leased before the
            # cancel; neither its coverage nor its hits may land.
            # _stopped mutates scheduler state, so back under the lock
            with self.lock:
                stopped = self._stopped()
            return {"ok": True, "stop": stopped, "dropped": True}
        t_verify = time.monotonic()
        verified = []
        rejected = 0
        for h in hits:
            ti = int(h["target"])
            if ti in already or not 0 <= ti < n_targets:
                continue
            plain = bytes.fromhex(h["plaintext"])
            if verifier is not None and not verifier(ti, plain):
                rejected += 1
                continue
            verified.append((ti, int(h["cand"]), plain))
        if hits:
            verify_s = time.monotonic() - t_verify
            self._h_phase.observe(verify_s, phase="verify",
                                  engine=job.spec.get("engine", "?"),
                                  job=job.job_id)
            self.tracer.record(
                "hit_verify", dur=verify_s,
                trace=ctx[0] if ctx else None,
                parent=ctx[1] if ctx else None, proc="coordinator",
                unit=unit_id, job=job.job_id, hits=len(hits),
                rejected=rejected)
        with self.lock:
            if job.state == JOB_CANCELLED:  # cancelled during verify
                return {"ok": True, "stop": self._stopped(),
                        "dropped": True}
            for ti, cand, plain in verified:
                if not self.scheduler.record_hit(job, ti, cand, plain):
                    continue
                self._m_hits.inc()
                if self.on_hit and job.job_id == self.default_job_id:
                    self.on_hit(ti, cand, plain)
                if self.on_job_hit:
                    self.on_job_hit(job, ti, cand, plain)
            self._g_found.set(self.scheduler.found_total())
            # attribute the unit's candidates BEFORE complete() drops
            # it from the lease ledger: remote workers hash in their
            # own processes, so the coordinator's scrapeable registry
            # must carry the fleet's sweep count itself
            raw_wid = msg.get("worker_id")
            wid = str(raw_wid) if raw_wid is not None else "?"
            # stale-guard context: with lease-ahead a crashed worker's
            # LATE complete can arrive after its unit was reissued to
            # another worker -- the live holder owns the completion
            # (verified hits above were still recorded; hits dedupe)
            guard = wid if raw_wid is not None else None
            unit = job.dispatcher.outstanding_unit(unit_id)
            if rejected:
                # The reporting worker's device path is suspect: requeue
                # the range instead of marking it done, or a wrong
                # plaintext would punch a permanent silent coverage hole
                # where the true crack may live.
                from dprf_tpu.utils.logging import DEFAULT as log
                self.rejected += rejected
                job.rejected += rejected
                self._m_rejects.inc(rejected)
                self.worker_rejects[wid] = \
                    self.worker_rejects.get(wid, 0) + 1
                if (self.worker_rejects[wid] >= self.MAX_WORKER_REJECTS
                        and wid not in self.quarantined):
                    self.quarantined.add(wid)
                    self._g_quar.set(len(self.quarantined))
                    log.warn("quarantined worker after repeated "
                             "unverifiable hits", worker=wid,
                             rejects=self.worker_rejects[wid])
                rejecters = self.unit_reject_workers.setdefault(
                    (job.job_id, unit_id), set())
                rejecters.add(wid)
                if len(rejecters) >= self.MAX_UNIT_REJECT_WORKERS:
                    # several DIFFERENT workers all produced unverifiable
                    # hits for this unit; requeueing again would livelock
                    # the job -- complete it, record the possible hole
                    log.warn("completing unit after rejected reports "
                             "from several workers; range may hold an "
                             "unrecovered crack", unit=unit_id,
                             job=job.job_id, workers=len(rejecters))
                    if unit is not None:
                        # coverage ledger marker (ISSUE 19): the range
                        # counts as covered below, but the audit trail
                        # must show it was force-completed over
                        # unverifiable reports -- the one place a
                        # "covered" range may still hide a crack
                        job.dispatcher.coverage.event(
                            "force_complete", unit.start, unit.end,
                            unit=unit_id, workers=len(rejecters))
                    self.scheduler.complete(job, unit_id,
                                            worker_id=guard)
                else:
                    self.scheduler.fail(job, unit_id, worker_id=guard)
            else:
                completed = self.scheduler.complete(
                    job, unit_id, elapsed=elapsed, worker_id=guard)
                if completed and self.on_job_progress:
                    self.on_job_progress(
                        job.job_id,
                        job.dispatcher.completed_intervals(),
                        job.dispatcher.coverage_digest())
                if completed and unit is not None:
                    # liveness only for completions of real leases (see
                    # op_lease on label cardinality); stale or rejected
                    # units are NOT counted -- the range is (re)swept by
                    # the live holder, whose complete counts it once
                    self._touch_worker(wid)
                    # feed the straggler detector: this worker's
                    # per-unit throughput EWMA (telemetry/health.py)
                    self.health.observe(
                        wid, rate_hs=(unit.length / elapsed
                                      if elapsed else None))
                    self._m_cands.inc(unit.length,
                                      engine=job.spec.get("engine", "?"),
                                      device="remote")
                    if elapsed:
                        # live roofline distance from the fleet's
                        # per-unit throughput (telemetry/perf.py)
                        perf_mod.publish_roofline(
                            job.spec.get("engine", "?"),
                            unit.length / elapsed,
                            self.health.device_kind(wid),
                            registry=self.registry)
            if self.on_progress:
                done, total = self.scheduler.progress()
                self.on_progress(done, total,
                                 self.scheduler.found_total())
            return {"ok": rejected == 0, "stop": self._stopped()}

    def op_fail(self, msg: dict) -> dict:
        # the failing worker's spans (rpc, the aborted sweep) still
        # join the timeline -- exactly the attempts an operator wants
        # to see when a unit bounced between workers
        self.tracer.ingest(msg.get("spans"),
                           proc=str(msg.get("worker_id", "?")),
                           sent_at=msg.get("clock"))
        raw_wid = msg.get("worker_id")
        raw_job = msg.get("job")
        with self.lock:
            job = self.scheduler.get(
                str(raw_job) if raw_job is not None else None)
            if job is not None:
                self.scheduler.fail(
                    job, int(msg["unit_id"]),
                    worker_id=str(raw_wid) if raw_wid is not None
                    else None)
        return {"ok": True}

    # -- fleet health plane (ISSUE 10) -------------------------------------

    def op_heartbeat(self, msg: dict) -> dict:
        """Worker liveness + capability beacon.  Sent on the
        idle-aware ``DPRF_HEARTBEAT_S`` cadence (worker_loop): only
        when the main connection has been quiet for a beat --
        lease/complete traffic already counts as contact.  The
        payload (device kind, pipeline depth, queue depth, recent
        H/s, last error) is client-controlled and sanitized by the
        health registry; this op also touches the last-seen gauge,
        fixing its old lease-holders-only blind spot."""
        raw = msg.get("worker_id")
        if raw is None:
            return {"ok": False}
        wid = str(raw)
        payload = msg.get("payload")
        self.health.observe(wid, payload=payload)
        self._touch_worker(wid)
        # compiled-program records the worker analyzed since its last
        # beat (ISSUE 13): bounded, sanitized, fingerprint-deduped --
        # how the coordinator's op_programs table covers programs that
        # only ever compiled on worker hosts
        self.programs.ingest(msg.get("programs"), proc=wid)
        # THIS worker's free-HBM fraction feeds the adaptive unit
        # sizers (per-worker: the coordinator's own allocator says
        # nothing about a remote chip); junk payloads read as no
        # signal, never as a poisoned estimate
        frac = None
        if isinstance(payload, dict):
            limit, use = payload.get("hbm_limit"), \
                payload.get("hbm_in_use")
            if (isinstance(limit, (int, float)) and limit > 0
                    and isinstance(use, (int, float))
                    and not isinstance(limit, bool)
                    and not isinstance(use, bool)):
                frac = max(0.0, 1.0 - use / limit)
        if frac is not None:
            with self.lock:
                for j in self.scheduler.jobs():
                    if j.terminal():
                        continue
                    observe = getattr(
                        getattr(j.dispatcher, "sizer", None),
                        "observe_headroom", None)
                    if observe is not None:
                        observe(wid, frac)
        # a pending capture request also rides the heartbeat response
        # (ISSUE 15): an idle worker beats, never leases -- it must
        # still be profilable
        with self.lock:
            prof_req = self._profile_request_for(wid)
        resp = {"ok": True}
        if prof_req is not None:
            resp["profile"] = prof_req
        return resp

    # -- kernel-profiling plane (ISSUE 15) ---------------------------------

    def op_profile(self, msg: dict) -> dict:
        """``dprf profile --connect``: request one bounded capture
        window on a worker (``action: "request"``; the request rides
        that worker's next lease/heartbeat response, the raw trace
        stays on the worker host) and read back the sanitized
        summaries workers pushed (the default action)."""
        if msg.get("action") == "request":
            worker = msg.get("worker")
            seconds = msg.get("seconds")
            if not (isinstance(seconds, (int, float))
                    and not isinstance(seconds, bool) and seconds > 0):
                seconds = profiler_mod.default_window_s()
            if worker is None:
                # no target named: the slowest live worker is the one
                # an operator profiling a misbehaving fleet wants
                worker = self.health.slowest_worker()
                if worker is None:
                    states = self.health.states()
                    live = [w for w, s in states.items()
                            if s in ("healthy", "degraded")]
                    worker = live[0] if live else None
            if worker is None:
                return {"error": "no live worker to profile (name "
                        "one with worker=)"}
            with self.lock:
                self._prune_profile_requests(time.monotonic())
                existing = self._profile_requests.get(str(worker))
                if existing is not None:
                    # a request for this worker is already queued:
                    # share its id instead of orphaning it (the
                    # earlier requester's poll would never resolve)
                    return {"ok": True, "request_id": existing["id"],
                            "worker": str(worker), "pending": True}
                if (len(self._profile_requests)
                        >= self.MAX_WORKER_LABELS):
                    # worker names are client-controlled: bound the
                    # pending table like the summary/label tables
                    return {"error": "too many pending capture "
                            "requests; wait for deliveries or the "
                            "TTL"}
                self._profile_seq += 1
                rid = self._profile_seq
                self._profile_requests[str(worker)] = {
                    "id": rid, "seconds": float(seconds),
                    "trigger": "manual",
                    "queued_at": time.monotonic()}
            return {"ok": True, "request_id": rid,
                    "worker": str(worker)}
        want = msg.get("worker")
        with self.lock:
            # a poller waiting on ONE request names its worker: ship
            # that bucket alone, not the whole fleet's table (1024
            # workers x 4 summaries x 20 ops, every 0.5 s poll)
            summaries = {w: list(s) for w, s in
                         self._profile_summaries.items()
                         if want is None or w == str(want)}
            for w in summaries:           # read happened: drop grace
                self._profile_unread.pop(w, None)
            # queued_at is coordinator-local monotonic bookkeeping,
            # meaningless on any other host: never on the wire
            pending = {w: {k: v for k, v in r.items()
                           if k != "queued_at"}
                       for w, r in self._profile_requests.items()}
        return {"ok": True, "summaries": summaries,
                "pending": pending, "now": time.time()}

    def op_profile_push(self, msg: dict) -> dict:
        """A worker shipping its finished capture window's summary:
        sanitized + bounded exactly like spans and heartbeat
        payloads (client-controlled), stored newest-first per worker,
        and journaled as a ``{"type": "profile"}`` record via the
        cmd_serve hook."""
        raw = msg.get("worker_id")
        if raw is None:
            return {"ok": False}
        wid = str(raw)
        summary = profiler_mod.sanitize_summary(msg.get("summary"))
        if summary is None:
            return {"ok": False}
        self.health.observe(wid)
        with self.lock:
            rid = summary.get("request_id")
            if rid is not None:
                self._profile_inflight.pop(rid, None)
            self._profile_unread[wid] = time.monotonic()
            bucket = self._profile_summaries.setdefault(wid, [])
            bucket.insert(0, summary)
            del bucket[PROFILE_SUMMARIES_PER_WORKER:]
            if len(self._profile_summaries) > self.MAX_WORKER_LABELS:
                # ids are client-controlled; drop the oldest worker's
                # bucket rather than growing without bound
                oldest = min(
                    self._profile_summaries,
                    key=lambda w: self._profile_summaries[w][0].get(
                        "ts") or 0)
                if oldest != wid:
                    self._profile_summaries.pop(oldest, None)
            if self.on_profile:
                self.on_profile(wid, summary)
        from dprf_tpu.utils.logging import DEFAULT as log
        log.info("kernel profile received", worker=wid,
                 trigger=summary.get("trigger"),
                 device_s=summary.get("device_s"),
                 error=summary.get("error"))
        return {"ok": True}

    def op_programs(self, msg: dict) -> dict:
        """Compiled-program table for ``dprf programs --connect``:
        every analyzed executable this coordinator knows -- its own
        compile sites plus the records workers shipped in heartbeats
        -- with XLA-derived flops/bytes/peak-memory per program."""
        return {"ok": True, "programs": self.programs.snapshot(),
                "now": time.time()}

    def op_health(self, msg: dict) -> dict:
        """Fleet health snapshot for ``dprf health --connect``: every
        tracked worker's state-machine record, the per-job SLO rows
        (ETA / time-to-first-hit / stall flag), and the active
        alerts.  The health/alert reads run under their own locks,
        never nested inside ours."""
        workers = self.health.snapshot()
        active = self.alerts.active()
        with self.lock:
            slos = self.scheduler.slo_summaries()
        return {"ok": True, "workers": workers, "jobs": slos,
                "alerts": active, "now": time.time()}

    def op_alerts(self, msg: dict) -> dict:
        """Alert surface for ``dprf alerts --connect``: the active
        (pending/firing) set plus the recent transition history the
        engine keeps in memory (the full log is the session's
        ``.alerts.jsonl``)."""
        try:
            n = int(msg.get("n", 200))
        except (TypeError, ValueError):
            n = 200
        return {"ok": True, "alerts": self.alerts.active(),
                "history": self.alerts.history(n),
                "now": time.time()}

    def op_trace_tail(self, msg: dict) -> dict:
        """Flight-recorder read for ``dprf top``: the most recent
        spans plus the live lease table and job status -- everything a
        terminal view needs to show per-worker state, current unit,
        span in progress, and lease countdown."""
        try:
            n = int(msg.get("n", 200))
        except (TypeError, ValueError):
            n = 200
        n = max(1, min(n, 2000))
        trace = msg.get("trace")
        trace = trace if isinstance(trace, str) else None
        since = msg.get("since")
        resync = False
        if isinstance(since, str) and since:
            # incremental read (`dprf top --follow`): only spans newer
            # than the caller's cursor; resync=True means the cursor
            # fell off the ring and the payload is a full tail the
            # caller must REPLACE its buffer with
            spans, resync = self.tracer.tail_after(since, n, trace=trace)
        else:
            spans = self.tracer.tail(n, trace=trace)
        cursor = spans[-1].get("span") if spans else (
            since if isinstance(since, str) else None)
        # live utilization & roofline distance (ISSUE 9), computed
        # outside the state lock (the recorder has its own)
        busy = self.tracer.busy_fractions()
        roofline = perf_mod.roofline_snapshot(self.registry)
        # fleet health plane (ISSUE 10): per-worker state for the
        # HEALTH column + the firing alerts for the header line --
        # both read under their own locks
        health_states = self.health.states()
        firing = self.alerts.firing_names()
        # device memory view (ISSUE 13): per-worker HBM use for the
        # MEM column and the fleet total for the header -- from the
        # heartbeat payloads, so a CPU-only fleet simply shows none
        mem = self.health.mem_by_worker()
        hbm = self.health.hbm_totals()
        # last-capture-per-worker fallback from heartbeat payloads
        # (env-local captures that never pushed a summary)
        prof_hb = self.health.profile_by_worker()
        with self.lock:
            done, total = self.scheduler.progress()
            leases = []
            for j in self.scheduler.jobs():
                if not j.terminal():
                    leases.extend(j.dispatcher.outstanding_leases())
            status = {"done": done, "total": total,
                      "found": self.scheduler.found_total(),
                      "targets": self.scheduler.targets_total(),
                      "parked": self.scheduler.parked_total(),
                      "stop": self._stopped(),
                      "elapsed": time.perf_counter() - self.t0,
                      # the clock span timestamps live in: span ages
                      # must be computed against THIS, not the
                      # viewer's possibly-skewed wall clock
                      "now": time.time(),
                      # per-job rows for the dprf top admin view
                      "jobs": self.scheduler.summaries(),
                      # sliding-window device-busy per worker + the
                      # live per-engine roofline fraction (dprf top
                      # folds both into its header line)
                      "busy": busy,
                      "roofline": roofline,
                      # worker health states + firing alerts (the
                      # dprf top HEALTH column and header line)
                      "health": health_states,
                      "alerts": firing,
                      # per-worker HBM use + the fleet total (the
                      # dprf top MEM column and HBM header field)
                      "mem": mem,
                      "hbm": hbm,
                      # last kernel capture per worker (ISSUE 15):
                      # the dprf top PROF column reads age + trigger;
                      # pushed summaries win over the heartbeat
                      # payload's self-reported captures -- but an
                      # in-band ERROR push carries no ts, and must
                      # not blank a worker's known last-capture age
                      "profiles": {**prof_hb, **{
                          w: {"ts": b[0].get("ts"),
                              "trigger": b[0].get("trigger")}
                          for w, b in self._profile_summaries.items()
                          if b and b[0].get("ts") is not None}},
                      "quarantined": sorted(self.quarantined)}
        return {"ok": True, "spans": spans, "leases": leases,
                "status": status, "cursor": cursor, "resync": resync}

    def op_retry_parked(self, msg: dict) -> dict:
        """Admin op (`dprf retry-parked --connect`): requeue poisoned/
        parked units with a fresh retry budget on the LIVE jobs --
        without restarting them (a DONE-because-parked job returns to
        RUNNING).  Token-authenticated like every other RPC op when
        the coordinator has a token (it mutates the unit ledger,
        unlike the read-only /metrics scrape)."""
        with self.lock:
            n = self.scheduler.retry_parked()
        return {"ok": True, "retried": n}

    def op_metrics(self, msg: dict) -> dict:
        """Registry read over the RPC protocol (authenticated when the
        coordinator has a token); the HTTP GET path below serves the
        same registry for Prometheus scrapers."""
        if msg.get("format") == "json":
            return {"ok": True, "metrics": self.registry.snapshot()}
        return {"ok": True, "text": self.registry.render()}

    def op_status(self, msg: dict) -> dict:
        with self.lock:
            done, total = self.scheduler.progress()
            return {"done": done, "total": total,
                    "found": self.scheduler.found_total(),
                    "stop": self._stopped(),
                    # poisoned ranges (retry-cap parked), summed over
                    # EVERY job like done/total/found above: a tenant
                    # that "finished" with parked units did NOT sweep
                    # them, and the default-job-only count would hide
                    # that (per-job detail is in "jobs")
                    "parked": self.scheduler.parked_total(),
                    "parked_indices":
                        self.scheduler.parked_indices_total(),
                    "jobs": self.scheduler.summaries(),
                    "elapsed": time.perf_counter() - self.t0}

    # -- multi-tenant job admin (jobs/scheduler.py) -----------------------

    @staticmethod
    def _owner_denied(job, auth_owner: Optional[str]) -> Optional[dict]:
        """Owner enforcement (ISSUE 10 satellite): a connection
        authenticated with an owner-scoped token (``dprf token``) may
        only act on that owner's jobs; the admin token (and the open
        protocol) is exempt (auth_owner None)."""
        if auth_owner is not None and job.owner != auth_owner:
            return {"error": f"job {job.job_id} belongs to owner "
                    f"{job.owner!r}; this token is scoped to "
                    f"{auth_owner!r}"}
        return None

    def op_job_submit(self, msg: dict,
                      auth_owner: Optional[str] = None) -> dict:
        """Admit a new job to the scheduler.  The spec is rebuilt
        server-side (jobs/build.py): targets parsed, generator built,
        fingerprint recomputed -- a submission is DATA, never trusted
        structure.  The expensive build runs OUTSIDE the lock against
        a pre-reserved job id.  An owner-token connection's
        submission is FORCED to its authenticated owner -- the msg
        field cannot impersonate another tenant."""
        spec = msg.get("spec")
        builder = self.job_builder
        if builder is None:
            from dprf_tpu.jobs.build import build_job_runtime
            builder = build_job_runtime
        with self.lock:
            # a table wedged at the cap with TTL-expired terminal
            # jobs un-wedges HERE (force bypasses the GC's rate
            # limiter), before the capacity gate rejects the tenant
            for gone in self.scheduler.maybe_gc(
                    keep=(self.default_job_id,),
                    force=self.scheduler.full()):
                if self.on_job_event:
                    self.on_job_event("gc", gone)
            # capacity gate BEFORE the expensive build: a full table
            # must not cost target parsing, generator construction,
            # or per-job metric registration per rejected attempt
            if self.scheduler.full():
                return {"error": "job rejected: job table full "
                        f"({self.scheduler.MAX_JOBS} jobs)"}
            # per-owner aggregate quota (ISSUE 13 satellite): an owner
            # whose cap is already consumed is rejected at admission,
            # before the build -- the lease path enforces the same cap
            # for jobs admitted before the quota filled
            claimed = (auth_owner if auth_owner is not None
                       else str(msg.get("owner") or "?"))
            quota_err = self.scheduler.owner_quota_error(claimed)
            if quota_err is not None:
                return {"error": f"job rejected: {quota_err}"}
            jid = self.scheduler.reserve_id()
            lease_timeout = self.dispatcher.lease_timeout
        try:
            wire, dispatcher, targets, verifier = builder(
                spec, jid, registry=self.registry,
                recorder=self.tracer, lease_timeout=lease_timeout)
        except (ValueError, OSError, KeyError, TypeError) as e:
            return {"error": f"job rejected: {e}"}
        owner = (auth_owner if auth_owner is not None
                 else str(msg.get("owner") or "?"))
        try:
            priority = max(1, int(msg.get("priority") or 1))
        except (TypeError, ValueError):
            priority = 1
        quota = msg.get("quota")
        quota = int(quota) if isinstance(quota, (int, float)) else None
        rate = msg.get("rate")
        rate = float(rate) if isinstance(rate, (int, float)) else None
        with self.lock:
            try:
                job = self.scheduler.add(
                    wire, dispatcher, len(targets), verifier=verifier,
                    owner=owner, priority=priority, quota=quota,
                    rate=rate, job_id=jid)
            except ValueError as e:
                return {"error": str(e)}
            self._g_targets.set(self.scheduler.targets_total())
            summary = job.summary()
            # under the lock: the event hook journals (session file
            # writes must serialize with the on_hit/on_job_progress
            # writers, which also run under it)
            if self.on_job_event:
                self.on_job_event("submit", job)
        from dprf_tpu.utils.logging import DEFAULT as log
        log.info("job submitted", job=jid, owner=owner,
                 priority=priority, keyspace=wire["keyspace"],
                 fingerprint=wire["fingerprint"])
        return {"ok": True, "job": summary, "job_id": jid,
                "fingerprint": wire["fingerprint"],
                "keyspace": wire["keyspace"]}

    def op_job_list(self, msg: dict) -> dict:
        with self.lock:
            return {"ok": True, "jobs": self.scheduler.summaries()}

    def op_job_status(self, msg: dict) -> dict:
        """One job's summary plus its full wire spec -- the op a
        multi-job worker rebuilds an unfamiliar job from (the spec is
        the same shape op_hello ships for the default job)."""
        with self.lock:
            job = self.scheduler.get(self._job_arg(msg))
            if job is None:
                return {"error": f"unknown job {msg.get('job')!r}"}
            return {"ok": True, "job": job.summary(),
                    "spec": job.spec}

    def op_job_cancel(self, msg: dict,
                      auth_owner: Optional[str] = None) -> dict:
        with self.lock:
            jid = self._job_arg(msg) or ""
            job = self.scheduler.get(jid) if jid else None
            if job is None:
                return {"error": f"unknown job {msg.get('job')!r}"}
            denied = self._owner_denied(job, auth_owner)
            if denied is not None:
                return denied
            self.scheduler.cancel(jid)
            summary = job.summary()
            if self.on_job_event:
                self.on_job_event("cancel", job)
        return {"ok": True, "job": summary}

    def op_job_pause(self, msg: dict,
                     auth_owner: Optional[str] = None) -> dict:
        resume = bool(msg.get("resume"))
        with self.lock:
            jid = self._job_arg(msg) or ""
            job = self.scheduler.get(jid) if jid else None
            if job is None:
                return {"error": f"unknown job {msg.get('job')!r}"}
            denied = self._owner_denied(job, auth_owner)
            if denied is not None:
                return denied
            self.scheduler.pause(jid, resume=resume)
            summary = job.summary()
            if self.on_job_event:
                self.on_job_event("resume" if resume else "pause",
                                  job)
        return {"ok": True, "job": summary}

    def op_hits_pull(self, msg: dict,
                     auth_owner: Optional[str] = None) -> dict:
        """Cursor-based per-job hit delivery: the submitting client
        polls with its last cursor and receives only NEW hits -- the
        multi-tenant replacement for scraping the single global found
        set.  The cursor is the hit sequence number; hits never
        reorder, so a client can resume from any cursor.  An
        owner-token connection can only pull its OWN jobs' hits."""
        try:
            cursor = max(0, int(msg.get("cursor") or 0))
        except (TypeError, ValueError):
            cursor = 0
        with self.lock:
            job = self.scheduler.get(self._job_arg(msg))
            if job is None:
                return {"error": f"unknown job {msg.get('job')!r}"}
            denied = self._owner_denied(job, auth_owner)
            if denied is not None:
                return denied
            hits = [dict(h) for h in job.hits[cursor:]]
            return {"ok": True, "hits": hits,
                    "cursor": cursor + len(hits),
                    "state": job.state, "found": len(job.found),
                    "targets": job.n_targets}

    def _job_arg(self, msg: dict) -> Optional[str]:
        j = msg.get("job")
        return str(j) if j is not None else None
    _job_arg._holds_lock = "lock"   # callers hold self.lock

    #: ops the handler loop passes the connection's authenticated
    #: owner to (owner-scoped tenant tokens; see owner_token above)
    op_hello._wants_owner = True
    op_job_submit._wants_owner = True
    op_job_cancel._wants_owner = True
    op_job_pause._wants_owner = True
    op_hits_pull._wants_owner = True

    # -- incident-response trace collection -------------------------------

    def op_trace_pull(self, msg: dict) -> dict:
        """Flight-recorder dump for incident response (`dprf trace
        pull`): page through the coordinator's ring with a span-id
        cursor.  ``arm=True`` additionally bumps the PULL EPOCH, which
        rides every lease response -- each live worker seeing a new
        epoch ships its LOCAL ring back via op_trace_push, so the next
        pull holds the fleet-wide record, including spans that never
        rode a complete/fail message."""
        if msg.get("arm"):
            with self.lock:
                self._pull_epoch += 1
        try:
            n = int(msg.get("n", 1000))
        except (TypeError, ValueError):
            n = 1000
        n = max(1, min(n, 4096))
        since = msg.get("since")
        since = since if isinstance(since, str) else None
        # forward pager from the ring's OLDEST span: a pull is a full
        # dump, not a live tail -- the client walks until a short page
        spans, resync = self.tracer.head_after(since, n)
        cursor = spans[-1].get("span") if spans else since
        with self.lock:
            epoch = self._pull_epoch
        return {"ok": True, "spans": spans, "cursor": cursor,
                "resync": resync, "epoch": epoch}

    def op_trace_push(self, msg: dict) -> dict:
        """A worker shipping its local flight-recorder ring (the
        op_trace_pull arm handshake).  Sanitized exactly like the
        spans on complete/fail -- bounded count, declared names only,
        proc forced to the reporting worker id -- just with a ring-
        sized bound instead of the per-unit one."""
        ingested = self.tracer.ingest(
            msg.get("spans"), proc=str(msg.get("worker_id", "?")),
            sent_at=msg.get("clock"), limit=TRACE_PUSH_MAX)
        return {"ok": True, "ingested": ingested}

    def _stopped(self) -> bool:
        return self.scheduler.all_finished()
    _stopped._holds_lock = "lock"   # callers hold self.lock

    def finished(self) -> bool:
        with self.lock:
            return self._stopped()


def challenge_response(token: str, nonce_hex: str) -> str:
    """The proof a client sends for a hello challenge."""
    return hmac_mod.new(token.encode(), bytes.fromhex(nonce_hex),
                        "sha256").hexdigest()


# ---------------------------------------------------------------------------
# owner-scoped tenant tokens (ISSUE 10 satellite of a ROADMAP item)

#: owner tokens are self-describing: ``ot1.<owner>.<mac>`` where the
#: mac is derived from the coordinator's ADMIN secret -- so the
#: coordinator can verify any tenant's token without a token table,
#: and the auth layer knows WHO connected, not just that someone did
OWNER_TOKEN_PREFIX = "ot1."
_OWNER_RE = re.compile(r"^[A-Za-z0-9_-]{1,64}$")


def owner_token(secret: str, owner: str) -> str:
    """Mint a tenant token from the coordinator's admin secret
    (``dprf token --owner``).  A connection authenticated with it is
    scoped to ``owner``: the owner-enforcing job ops
    (cancel/pause/resume/hits_pull) only act on that owner's jobs,
    and a submission's owner field is forced to it.  The admin secret
    itself stays exempt (owner None = admin)."""
    if not _OWNER_RE.match(owner or ""):
        raise ValueError(
            "owner must be 1-64 chars of [A-Za-z0-9_-] "
            f"(got {owner!r})")
    mac = hmac_mod.new(secret.encode(),
                       b"dprf-owner:" + owner.encode(),
                       "sha256").hexdigest()[:32]
    return f"{OWNER_TOKEN_PREFIX}{owner}.{mac}"


def token_owner(token: Optional[str]) -> Optional[str]:
    """The owner a token is scoped to; None for admin/plain tokens."""
    if not token or not token.startswith(OWNER_TOKEN_PREFIX):
        return None
    owner = token[len(OWNER_TOKEN_PREFIX):].split(".", 1)[0]
    return owner or None


class _Handler(socketserver.StreamRequestHandler):
    #: failed auth attempts before the connection is dropped
    MAX_AUTH_FAILURES = 3

    def _serve_http(self, request_line: bytes) -> None:
        """One-shot HTTP responder on the RPC port: ``GET /metrics``
        returns the coordinator registry in Prometheus text format.
        Read-only observability is served even when the RPC protocol
        is token-authenticated -- it exposes rates and counts, never
        the job description or hits -- so a scraper needs no secret."""
        state: CoordinatorState = self.server.state   # type: ignore
        try:
            while True:            # drain request headers politely
                line = self.rfile.readline(MAX_LINE)
                if not line or line in (b"\r\n", b"\n"):
                    break
            parts = request_line.split()
            head_only = parts and parts[0] == b"HEAD"
            path = parts[1].decode("latin-1") if len(parts) > 1 else ""
            if path.split("?")[0] == "/metrics":
                body = state.registry.render().encode()
                head = (b"HTTP/1.0 200 OK\r\n"
                        b"Content-Type: text/plain; version=0.0.4; "
                        b"charset=utf-8\r\n"
                        b"Content-Length: %d\r\n"
                        b"Connection: close\r\n\r\n" % len(body))
            else:
                body = b"try /metrics\n"
                head = (b"HTTP/1.0 404 Not Found\r\n"
                        b"Content-Type: text/plain\r\n"
                        b"Content-Length: %d\r\n"
                        b"Connection: close\r\n\r\n" % len(body))
            # HEAD: headers only (Content-Length still describes what
            # GET would return)
            self.connection.sendall(head if head_only else head + body)
        except OSError:
            pass

    def handle(self):
        state: CoordinatorState = self.server.state   # type: ignore
        nonce = secrets.token_hex(16)      # challenge, rotated per failure
        auth_failures = 0
        authed = state.token is None
        #: owner this connection authenticated AS (owner-scoped
        #: tenant tokens, ISSUE 10): None = admin token or open
        #: protocol -- exempt from the per-owner job-op checks
        conn_owner: Optional[str] = None
        #: the token string this connection's hmacs are keyed with
        #: (the owner-DERIVED token for tenant connections)
        conn_token = state.token
        while True:
            try:
                line = self.rfile.readline(MAX_LINE)
            except OSError:
                return
            if not line:
                return
            if line.startswith((b"GET ", b"HEAD ")):
                # Prometheus/curl scrape on the RPC port: answer HTTP
                # and close (HTTP clients don't speak the JSON framing)
                self._serve_http(line)
                return
            if not line.endswith(b"\n"):
                return     # over the frame limit: drop, as recv_msg does
            try:
                msg = json.loads(line)
            except ValueError:
                return
            if not isinstance(msg, dict):
                return
            if not authed:
                if msg.get("op") == "hello":
                    mac = msg.get("hmac")
                    # a hello naming an owner authenticates against
                    # the owner-DERIVED token (owner_token): the
                    # coordinator needs no token table, and a valid
                    # mac proves both the secret chain AND the owner
                    # identity in one step
                    owner = msg.get("owner")
                    owner = (owner if isinstance(owner, str)
                             and _OWNER_RE.match(owner) else None)
                    expect = (owner_token(state.token, owner)
                              if owner else state.token)
                    if (isinstance(mac, str) and hmac_mod.compare_digest(
                            mac, challenge_response(expect, nonce))):
                        authed = True      # fall through to op_hello
                        conn_owner = owner
                        conn_token = expect
                    else:
                        # a fresh nonce per attempt: a failed guess
                        # teaches nothing about the next challenge
                        auth_failures += 1
                        nonce = secrets.token_hex(16)
                        try:
                            send_msg(self.connection,
                                     {"ok": False, "challenge": nonce})
                        except OSError:
                            return
                        if auth_failures >= self.MAX_AUTH_FAILURES:
                            return          # drop the connection
                        continue
                else:
                    try:
                        send_msg(self.connection,
                                 {"error": "unauthenticated"})
                    except OSError:
                        return
                    continue
            op = getattr(state, f"op_{msg.get('op', '')}", None)
            # unknown ops share ONE label child: op strings are
            # client-controlled, and each distinct label value lives in
            # the registry forever -- an open-protocol client must not
            # be able to grow coordinator memory one junk op at a time
            state._m_rpc.inc(
                op=str(msg.get("op", "?")) if op is not None
                else "unknown")
            if op is None:
                resp = {"error": f"unknown op {msg.get('op')!r}"}
            else:
                try:
                    if getattr(op, "_wants_owner", False):
                        # owner-scoped job ops receive the identity
                        # this CONNECTION authenticated as -- never a
                        # spoofable message field
                        resp = op(msg, auth_owner=conn_owner)
                    else:
                        resp = op(msg)
                except Exception as e:       # defensive: never kill server
                    resp = {"error": f"{type(e).__name__}: {e}"}
            if (msg.get("op") == "hello" and state.token
                    and isinstance(msg.get("cnonce"), str)):
                # mutual auth: prove WE know the token over the
                # client's nonce, so a worker with --token refuses a
                # spoofed coordinator (and the job it would hand out).
                # Keyed with the CONNECTION's token: a tenant client
                # verifies with its owner-derived token
                try:
                    resp["coordinator_hmac"] = challenge_response(
                        conn_token, msg["cnonce"])
                except ValueError:
                    resp = {"error": "bad cnonce (want hex)"}
            try:
                send_msg(self.connection, resp)
            except OSError:
                return


class CoordinatorServer:
    """Threaded TCP server around a CoordinatorState."""

    def __init__(self, state: CoordinatorState, host: str = "127.0.0.1",
                 port: int = 0):
        # bind manually so allow_reuse_address is set BEFORE bind():
        # otherwise a restart on the same port trips over TIME_WAIT
        self._srv = socketserver.ThreadingTCPServer(
            (host, port), _Handler, bind_and_activate=False)
        self._srv.daemon_threads = True
        self._srv.allow_reuse_address = True
        try:
            self._srv.server_bind()
            self._srv.server_activate()
        except BaseException:
            self._srv.server_close()
            raise
        self._srv.state = state            # type: ignore
        self.state = state
        self.address = self._srv.server_address

    def serve_until_done(self, poll: float = 0.5,
                         drain: float = 600.0) -> None:
        """Run until the job finishes, then keep serving until every
        outstanding lease resolves (workers mid-unit must be able to
        report their final hits and see the stop flag -- a fixed grace
        window would race against unit processing time) AND every
        in-flight kernel-profile capture lands or expires (a capture
        racing the job's last units stops + analyzes on the worker
        for seconds after the final complete; vanishing now would
        lose its push).  `drain` caps the wait so a worker that died
        holding a lease can't pin the server forever; dead captures
        expire on their own PROFILE_INFLIGHT_TTL_S."""
        t = threading.Thread(target=self._srv.serve_forever,
                             kwargs={"poll_interval": 0.1}, daemon=True)
        t.start()
        try:
            while not self.state.finished():
                time.sleep(poll)
            deadline = time.monotonic() + drain
            while time.monotonic() < deadline:
                with self.state.lock:
                    # expired leases (dead workers) won't be reaped by
                    # lease() anymore -- nobody is leasing -- so reap
                    # here or a dead worker would pin the drain loop
                    self.state.scheduler.reap_expired()
                    outstanding = \
                        self.state.scheduler.total_outstanding()
                if outstanding == 0 \
                        and not self.state.profile_pending():
                    break
                time.sleep(poll)
            time.sleep(poll)   # let final responses flush
        finally:
            self._srv.shutdown()
            self._srv.server_close()

    def start_background(self) -> threading.Thread:
        t = threading.Thread(target=self._srv.serve_forever,
                             kwargs={"poll_interval": 0.1}, daemon=True)
        t.start()
        return t

    def shutdown(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()


# ---------------------------------------------------------------------------
# worker side

class CoordinatorClient:
    """Blocking JSON-RPC client used by remote workers."""

    def __init__(self, host: str, port: int, timeout: float = 30.0,
                 token: Optional[str] = None):
        self._addr = (host, port)
        self._timeout = timeout
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._fh = self._sock.makefile("rb")
        self._token = token
        #: owner an ``ot1.`` tenant token is scoped to (None for the
        #: admin secret): sent with hello so the coordinator keys the
        #: challenge against the owner-derived token
        self._owner = token_owner(token)

    def clone(self) -> "CoordinatorClient":
        """A second authenticated connection to the same coordinator
        -- the async completion sender's channel, so report round
        trips ride beside the lease/sweep loop instead of inside it.
        Authentication is per-connection, so a token-auth'd clone
        answers its own hello challenge here."""
        peer = type(self)(self._addr[0], self._addr[1],
                          timeout=self._timeout, token=self._token)
        if self._token:
            try:
                peer.hello()
            except BaseException:
                peer.close()
                raise
        return peer

    def hello(self) -> dict:
        """Fetch the job, answering the coordinator's auth challenge if
        it has one.  When this client holds a token, the coordinator
        must in turn prove it knows the token over OUR nonce (mutual
        auth): a spoofed coordinator cannot hand this worker a job."""
        cnonce = secrets.token_hex(16)
        resp = self.call("hello", cnonce=cnonce, owner=self._owner)
        if resp.get("challenge"):
            if not self._token:
                raise RpcError(
                    "coordinator requires authentication; pass --token")
            resp = self.call("hello", cnonce=cnonce, owner=self._owner,
                             hmac=challenge_response(
                                 self._token, resp["challenge"]))
            if resp.get("challenge"):
                raise RpcError("authentication failed (wrong token?)")
        if self._token:
            proof = resp.get("coordinator_hmac")
            if not (isinstance(proof, str) and hmac_mod.compare_digest(
                    proof, challenge_response(self._token, cnonce))):
                raise RpcError("coordinator failed mutual authentication "
                               "(spoofed coordinator, or it has no/other "
                               "token)")
        return resp

    def call(self, op: str, **kw) -> dict:
        kw["op"] = op
        send_msg(self._sock, kw)
        resp = recv_msg(self._fh)
        if resp is None:
            raise ConnectionError("coordinator closed the connection")
        if "error" in resp:
            raise RpcError(f"coordinator error: {resp['error']}")
        return resp

    def close(self) -> None:
        # the makefile() stream holds its own reference to the socket
        # (and a buffer): closing only the socket leaks the stream
        # object and keeps the fd alive until GC
        try:
            self._fh.close()
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass


class _CompletionSender:
    """Ships ``complete``/``fail`` reports from a background thread on
    a dedicated connection, so the report round trip overlaps the next
    sweep instead of serializing with it.  Ordering is preserved (one
    FIFO queue, one thread); the first send failure is latched and
    re-raised by ``drain()`` -- the crash-surfacing contract of the
    serial loop.  Reports queued after a failure are dropped: their
    leases expire and reissue, and the latched error aborts the loop
    anyway."""

    def __init__(self, client: CoordinatorClient):
        import queue
        self._client = client
        self._q: "queue.Queue" = queue.Queue()
        self.error: Optional[BaseException] = None
        self.stop_seen = False
        self._t = threading.Thread(target=self._run, daemon=True,
                                   name="dprf-sender")
        self._t.start()

    def send(self, op: str, **kw) -> None:
        self._q.put((op, kw))

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            op, kw = item
            try:
                if self.error is None:
                    # clock stamped at SEND time: the coordinator
                    # rebases the shipped span timestamps against it
                    resp = self._client.call(op, clock=time.time(),
                                             **kw)
                    if resp.get("stop"):
                        self.stop_seen = True
            except Exception as e:   # noqa: BLE001 -- latched, then
                self.error = e       # re-raised by drain()
            finally:
                self._q.task_done()

    def drain(self) -> None:
        """Block until every queued report was sent (or dropped past a
        failure), then re-raise the first send failure."""
        self._q.join()
        if self.error is not None:
            raise self.error

    def close(self) -> None:
        self._q.put(None)
        self._t.join(timeout=30)
        self._client.close()


def worker_loop(client: CoordinatorClient, worker, worker_id: str,
                idle_sleep: float = 0.5, log=None, registry=None,
                recorder=None, depth: Optional[int] = None,
                worker_for: Optional[Callable] = None) -> int:
    """Pipelined lease -> submit-ahead -> resolve -> async-complete
    loop, until the coordinator says stop.  Returns units completed.

    worker: any object with .process(WorkUnit) -> list[Hit] (the same
    duck type the local Coordinator drives).  Submit-based workers
    (``process._submit_based``) enqueue unit N+1's device work BEFORE
    unit N resolves, so the next super-step is on the device stream
    while the host decodes hits and the RPC round trips fly; serial
    workers still gain the lease-ahead batch and the overlapped
    completion report.

    Multi-tenant (ISSUE 8): lease entries name their JOB; the optional
    ``worker_for(job_id)`` factory maps an unfamiliar job to its
    worker (cli.cmd_worker builds one that fetches the spec over
    op_job_status, fingerprint-checks it, and caches the rebuilt
    worker).  A factory returning None means the job cannot run on
    this host (missing wordlist file, divergent content fingerprint):
    its leases are failed back in-band and the loop keeps serving
    other jobs.  Without a factory every unit runs on the default
    ``worker`` -- the single-job fleet unchanged.  Complete/fail
    reports echo the job id so the coordinator routes them to the
    right ledger.

    ``depth=None`` (the default) runs the ADAPTIVE depth: EWMAs of
    the lease round trip and the inter-completion interval derive the
    live submit-ahead depth (~1 + rtt/unit_seconds) each iteration,
    capped by the ``DPRF_PIPELINE_DEPTH`` knob / ``--pipeline-depth``
    flag (worker.AdaptiveDepth).  An explicit integer pins the depth;
    1 is the serial fallback (one connection, synchronous completes).

    Crash surfacing matches the serial loop: a processing failure
    fails the aborted unit AND every queued lease, then re-raises;
    queued completion reports are drained before any return, and the
    first async send failure is re-raised.

    Tracing: the lease response's trace context parents this worker's
    ``rpc`` / ``warmup`` / ``sweep`` spans, which ship back inside the
    complete (or fail) message -- the coordinator's flight recorder
    then holds the unit's WHOLE lifecycle across every host that
    touched it.  When an operator ARMS a trace pull (op_trace_pull),
    the lease response's ``pull`` epoch bumps and this loop ships its
    whole LOCAL ring back once via op_trace_push.
    ``DPRF_JAX_PROFILE=<dir>`` additionally wraps the loop in a
    jax.profiler trace.
    """
    from dprf_tpu.runtime.worker import (AdaptiveDepth, UnitPipeline,
                                         pipeline_depth)

    m = get_registry(registry)
    tracer = get_tracer(recorder)
    # worker-side publication: candidates are counted where the hashing
    # happens (the local Coordinator does the same for in-process
    # jobs); declared through declare_job_metrics -- the ONE
    # declaration site (tools/check_metrics.py) -- so names and labels
    # can never drift from the coordinator's
    jm = declare_job_metrics(m)

    def _labels_of(w) -> tuple:
        return (getattr(getattr(w, "engine", None), "name", "unknown"),
                "cpu" if type(w).__name__ == "CpuWorker" else "jax")

    m_cands = jm["cands"]
    h_unit = jm["unit_seconds"]
    g_depth = m.gauge(
        "dprf_worker_pipeline_depth",
        "units this worker submits ahead of the oldest unresolved one "
        "(1 = serial loop; adapted to rtt/unit-seconds under the "
        "DPRF_PIPELINE_DEPTH cap unless pinned)")
    c_idle = m.counter(
        "dprf_worker_idle_seconds",
        "seconds this worker held no submitted unit between sweeps "
        "(pipeline drained: the device idles while RPCs fly)")
    # kernel-profiling plane (ISSUE 15): on-demand bounded capture
    # windows requested over lease/heartbeat responses.  The loop
    # keeps sweeping while the trace records; poll_profile() is ONE
    # attribute read when no window is active -- the zero-overhead
    # contract for the steady-state path.
    prof = profiler_mod.get_profiler()
    swept = [0]      # cumulative resolved candidates (window counter)

    def push_profile(summary: dict) -> None:
        # best-effort on the MAIN connection, like trace_push: a
        # dead link surfaces on the next lease anyway
        try:
            client.call("profile_push", worker_id=worker_id,
                        summary=summary)
        except Exception:   # noqa: BLE001 -- diagnostics only
            pass

    def begin_profile(req) -> None:
        if not isinstance(req, dict):
            return
        seconds = req.get("seconds")
        ok = prof.begin_window(
            seconds if isinstance(seconds, (int, float))
            and not isinstance(seconds, bool) else None,
            trigger=str(req.get("trigger") or "manual"),
            engine=_labels_of(worker)[0],
            request_id=req.get("id"),
            counter_fn=lambda: swept[0], log=log)
        if not ok:
            # single-flight collision (--profile / DPRF_JAX_PROFILE
            # already tracing): report it in-band, not silently
            push_profile({"schema": profiler_mod.SUMMARY_SCHEMA,
                          "request_id": req.get("id"),
                          "trigger": str(req.get("trigger")
                                         or "manual"),
                          "engine": _labels_of(worker)[0],
                          "error": "capture busy "
                          f"(active: {prof.busy()})"})

    def poll_profile() -> None:
        s = prof.poll()
        if s is not None:
            push_profile(s)

    adaptive = None
    if depth is None:
        adaptive = AdaptiveDepth(pipeline_depth())
        depth = adaptive.depth
    sender = None
    if depth > 1 or (adaptive is not None and adaptive.cap > 1):
        try:
            sender = _CompletionSender(client.clone())
        except (OSError, RpcError) as e:
            if log:
                log.warn("completion-sender connection failed; "
                         "running the serial loop", error=str(e))
            depth = 1
            adaptive = None
    g_depth.set(depth)
    pipe = UnitPipeline(worker, depth)
    done_units = 0
    stop_seen = False
    idle_mark: Optional[float] = None
    t_last_resolve: Optional[float] = None
    warm_pending = getattr(worker, "ensure_warm", None) is not None
    cur = None        # entry being submitted/resolved, for the fail path
    lease_q: list = []    # leased-but-not-yet-submitted batch remainder
    pull_seen = 0     # last trace-pull epoch this worker answered

    # idle-aware heartbeats (ISSUE 10): an explicit op_heartbeat goes
    # out only when the MAIN connection has been quiet for a whole
    # DPRF_HEARTBEAT_S beat -- lease round trips already count as
    # contact on the coordinator's health plane, so a busy loop never
    # pays the extra RPC.  The payload is this worker's live
    # capability/health record (device kind, pipeline depth, queue
    # depth, recent H/s, last async-send error).
    hb_s = heartbeat_interval()
    t_contact = time.monotonic()
    rate_ewma: Optional[float] = None
    chips: list = []      # lazily probed on the first beat
    prog_seq = [0]        # newest program-registry seq already shipped

    def _chip_count() -> Optional[int]:
        if not chips:
            try:
                import jax
                chips.append(jax.local_device_count())
            except Exception:   # noqa: BLE001 -- jax-less host
                chips.append(None)
        return chips[0]

    def maybe_heartbeat() -> None:
        nonlocal t_contact
        if hb_s <= 0 or time.monotonic() - t_contact < hb_s:
            return
        t_contact = time.monotonic()
        eng_name, dev = _labels_of(worker)
        err = (str(sender.error)[:200]
               if sender is not None and sender.error is not None
               else None)
        payload = {"engine": eng_name, "device": dev,
                   "device_kind": (perf_mod.local_device_kind()
                                   if dev == "jax" else None),
                   "chips": _chip_count(),
                   "depth": pipe.depth,
                   "queue": len(pipe),
                   "rate_hs": rate_ewma,
                   "error": err}
        # last kernel capture on THIS host (ISSUE 15): age + trigger
        # ride the beat so `dprf top` can show them per worker even
        # for env-local captures that never pushed a summary
        last_prof = prof.last_summary()
        if last_prof is not None:
            payload["profile_ts"] = last_prof.get("ts")
            payload["profile_trigger"] = last_prof.get("trigger")
        # device introspection rides the beat (ISSUE 13): HBM totals
        # in the payload (fleet memory headroom on the coordinator's
        # health plane) and the program records analyzed since the
        # last beat.  The deferred analysis runs HERE -- the beat only
        # fires when the loop has been quiet, so the cache-served
        # recompile it may trigger never delays a dispatch.
        try:
            from dprf_tpu.telemetry import devstats
            programs_mod.analyze_pending()
            hbm = devstats.summary()
            if hbm is not None:
                payload["hbm_in_use"] = hbm["in_use"]
                payload["hbm_limit"] = hbm["limit"]
                payload["hbm_peak"] = hbm["peak"]
        except Exception:   # noqa: BLE001 -- introspection is
            pass            # best-effort, never loop state
        records, newest = programs_mod.get_programs().records_since(
            prog_seq[0])
        try:
            resp = client.call("heartbeat", worker_id=worker_id,
                               payload=payload, programs=records)
            prog_seq[0] = newest
        except Exception:   # noqa: BLE001 -- best-effort beacon; a
            return          # dead link surfaces on the next lease
        # an idle worker never leases: capture requests must be able
        # to ride the heartbeat response too
        begin_profile(resp.get("profile"))

    def _worker_of(job_id):
        if worker_for is None or job_id is None:
            return worker
        return worker_for(job_id)

    def send_report(op: str, **kw) -> Optional[dict]:
        if sender is not None:
            sender.send(op, **kw)
            return None
        return client.call(op, clock=time.time(), **kw)

    def send_fail(unit_id: int, ship: list, job=None) -> None:
        try:
            send_report("fail", unit_id=unit_id, worker_id=worker_id,
                        spans=ship, job=job)
        except Exception:   # noqa: BLE001 -- best-effort, as serial
            pass            # (the lease expires and reissues anyway)

    def push_ring() -> None:
        # an operator armed a fleet-wide trace pull: ship this
        # worker's local flight recorder (spans that never rode a
        # complete/fail) on the MAIN connection, best-effort
        try:
            client.call("trace_push", clock=time.time(),
                        worker_id=worker_id,
                        spans=tracer.tail(TRACE_PUSH_MAX))
        except Exception:   # noqa: BLE001 -- diagnostics only
            pass

    try:
        with jax_profile_ctx(log=log):
            while True:
                if sender is not None and sender.error is not None:
                    # the coordinator stopped answering completion
                    # reports: surface it like a serial complete would
                    raise sender.error
                if adaptive is not None:
                    # adaptive lease-ahead: re-derive the live depth
                    # from the rtt/unit EWMAs under the env-knob cap
                    new_depth = adaptive.update()
                    if new_depth != pipe.depth:
                        pipe.depth = new_depth
                        g_depth.set(new_depth)
                want = pipe.depth - len(pipe)
                entries = []
                if want > 0 and not stop_seen:
                    t_lease = time.monotonic()
                    try:
                        resp = client.call("lease", worker_id=worker_id,
                                           ahead=want)
                    except ConnectionError:
                        # The coordinator serves through its drain
                        # window and answers every lease poll with an
                        # explicit stop flag once the job is over, so a
                        # worker always learns completion in-band and
                        # returns below.  A bare connection drop here
                        # therefore means the coordinator crashed
                        # mid-job: surface it so scripted workers don't
                        # report success on unfinished work.
                        raise ConnectionError(
                            "coordinator connection dropped before any "
                            "stop signal (coordinator crash mid-job?)")
                    if resp.get("quarantined"):
                        raise RpcError(
                            "coordinator quarantined this worker: its "
                            "reported hits repeatedly failed oracle "
                            "verification (divergent device path?)")
                    lease_rtt = time.monotonic() - t_lease
                    t_contact = time.monotonic()  # lease = contact
                    if adaptive is not None:
                        adaptive.observe_rtt(lease_rtt)
                    pull = resp.get("pull")
                    if isinstance(pull, int) and pull > pull_seen:
                        pull_seen = pull
                        push_ring()
                    begin_profile(resp.get("profile"))
                    entries = resp.get("units")
                    if entries is None:
                        # pre-lease-ahead coordinator: single unit with
                        # a top-level trace context
                        entries = []
                        if resp.get("unit"):
                            unit_d = dict(resp["unit"])
                            if resp.get("trace"):
                                unit_d.setdefault("trace", resp["trace"])
                            entries = [unit_d]
                    if not entries:
                        if resp.get("stop"):
                            stop_seen = True
                        elif sender is not None:
                            # all our reports are in flight: land them,
                            # then trust the freshest stop answer (the
                            # final complete's response carries it)
                            # instead of sleeping into another poll
                            sender.drain()
                            if sender.stop_seen:
                                stop_seen = True
                        if len(pipe) == 0:
                            if stop_seen:
                                break
                            # nothing leasable and nothing queued:
                            # this is exactly when the coordinator
                            # would otherwise go blind on us
                            maybe_heartbeat()
                            poll_profile()
                            time.sleep(idle_sleep)
                            continue
                    first = True
                    lease_q = list(entries)
                    while lease_q:
                        unit_d = lease_q.pop(0)
                        job = unit_d.get("job")
                        unit = WorkUnit(unit_d["id"], unit_d["start"],
                                        unit_d["length"],
                                        job_id=str(job) if job
                                        is not None else "j0")
                        ctx = unit_d.get("trace") or {}
                        tid, lease_sid = ctx.get("trace"), ctx.get("span")
                        ship: list = []
                        if first:
                            # one rpc span per lease round trip,
                            # parented on the batch's first lease
                            first = False
                            ev = tracer.record(
                                "rpc", dur=lease_rtt, trace=tid,
                                parent=lease_sid, proc=worker_id,
                                op="lease", unit=unit.unit_id,
                                job=job, units=len(entries))
                            if ev:
                                ship.append(ev)
                        # resolve the unit's JOB to its worker (the
                        # factory path may rebuild a job from
                        # op_job_status).  None = this job cannot run
                        # on THIS host (missing wordlist, divergent
                        # fingerprint): release the lease in-band and
                        # keep serving every other job -- one bad
                        # submission must not take down the fleet
                        # (its units park after the retry budget).
                        # cur is set BEFORE the call so an unexpected
                        # factory crash still releases the lease.
                        cur = (unit, None, time.monotonic(),
                               (tid, lease_sid, ship, job, worker))
                        w = _worker_of(job)
                        if w is None:
                            send_fail(unit.unit_id, ship, job=job)
                            cur = None
                            continue
                        cur = (unit, None, cur[2],
                               (tid, lease_sid, ship, job, w))
                        # join an overlapped warmup (cli.cmd_worker
                        # starts one before the loop, so the compile
                        # overlapped the lease round trip); under the
                        # fail path so a compile failure releases the
                        # lease like any processing failure
                        ensure_warm = getattr(w, "ensure_warm", None)
                        if ensure_warm is not None:
                            ensure_warm()
                        if warm_pending and w is worker:
                            # the compile ran overlapped on a background
                            # thread; report its REAL cost
                            # (compile_seconds), not the near-zero join
                            # time, so a fleet stalled on cold compiles
                            # is legible in the trace
                            warm_pending = False
                            warm_s = getattr(worker, "compile_seconds",
                                             None)
                            if warm_s is not None:
                                ev = tracer.record(
                                    "warmup", dur=float(warm_s),
                                    trace=tid, parent=lease_sid,
                                    proc=worker_id,
                                    engine=_labels_of(worker)[0],
                                    cache=getattr(worker,
                                                  "compile_cache",
                                                  None),
                                    overlapped=True)
                                if ev:
                                    ship.append(ev)
                        if idle_mark is not None:
                            # the pipeline had drained: that gap was
                            # device-idle time (RPCs with no submitted
                            # work to hide them behind)
                            c_idle.inc(time.monotonic() - idle_mark)
                            idle_mark = None
                        pipe.submit(unit,
                                    meta=(tid, lease_sid, ship, job, w),
                                    worker=w)
                        cur = None
                if len(pipe) == 0:
                    if stop_seen:
                        break
                    continue
                cur = pipe.pop()
                unit, pending, t_submit, \
                    (tid, lease_sid, ship, job, w) = cur
                hits = pending.resolve()
                cur = None
                swept[0] += unit.length
                now = time.monotonic()
                unit_s = now - t_submit
                # steady-state per-unit cost for the ADAPTIVE SIZER:
                # the interval between consecutive resolves.  unit_s
                # (submit->resolve) includes up to depth-1 units of
                # queue wait behind the device stream, which would read
                # as ~1/depth of the true throughput and shrink every
                # subsequent unit; the completion interval measures the
                # worker's real drain rate once the pipeline is primed.
                # After a drain (no leasable work) the interval would
                # instead carry starvation time, so it resets below and
                # the next unit falls back to its own unit_s.
                elapsed_report = (now - t_last_resolve
                                  if t_last_resolve is not None
                                  else unit_s)
                t_last_resolve = now
                if len(pipe) == 0:
                    idle_mark = now
                    t_last_resolve = None
                if adaptive is not None:
                    adaptive.observe_unit(elapsed_report)
                if elapsed_report > 0:
                    # recent-throughput EWMA for the heartbeat payload
                    inst = unit.length / elapsed_report
                    rate_ewma = (inst if rate_ewma is None
                                 else rate_ewma + 0.3 * (inst - rate_ewma))
                # a long sweep keeps the main connection quiet for its
                # whole duration: beat here if it starved the cadence
                maybe_heartbeat()
                # an elapsed capture window stops + analyzes + ships
                # here (one attribute read when no window is active)
                poll_profile()
                # the histogram gets the same per-unit cost: observing
                # unit_s here would inflate dprf_unit_seconds ~depth x
                # under pipelining with no throughput change
                h_unit.observe(elapsed_report)
                eng_name, device = _labels_of(w)
                m_cands.inc(unit.length, engine=eng_name, device=device)
                # ts backdates to t_submit, so consecutive sweep spans
                # OVERLAP when the loop pipelines (the invariant
                # tools/trace_overlap.py checks).
                ev = tracer.record("sweep", dur=unit_s, trace=tid,
                                   parent=lease_sid, proc=worker_id,
                                   unit=unit.unit_id, job=job,
                                   length=unit.length,
                                   hits=len(hits))
                if ev:
                    ship.append(ev)
                payload = [{"target": h.target_index,
                            "cand": h.cand_index,
                            "plaintext": h.plaintext.hex()}
                           for h in hits]
                # elapsed rides the complete report: the coordinator's
                # adaptive unit sizer turns it into this worker's next
                # unit length; the job id routes it to the right
                # ledger; spans stitch the attempt onto the
                # coordinator's flight recorder
                resp = send_report("complete", unit_id=unit.unit_id,
                                   hits=payload, worker_id=worker_id,
                                   elapsed=elapsed_report, spans=ship,
                                   job=job)
                done_units += 1
                if log and hits:
                    log.info("hits reported", count=len(hits))
                if resp is not None and resp.get("stop"):
                    stop_seen = True
                if sender is not None and sender.stop_seen:
                    stop_seen = True
                if stop_seen and len(pipe) == 0:
                    break
        # clean exit: every queued report must land before we return
        # (the serial loop's in-band completion contract); the first
        # async send failure re-raises here
        if sender is not None:
            sender.drain()
        return done_units
    except BaseException as e:
        if cur is not None:
            # the aborted attempt still joins the timeline: ship what
            # we have with the fail report, then release the lease (and
            # every still-queued one) for another worker
            unit, _, t_unit, (tid, lease_sid, ship, job, _w) = cur
            ev = tracer.record("sweep",
                               dur=time.monotonic() - t_unit,
                               trace=tid, parent=lease_sid,
                               proc=worker_id, unit=unit.unit_id,
                               job=job, error=type(e).__name__)
            if ev:
                ship.append(ev)
            send_fail(unit.unit_id, ship, job=job)
        for q_unit, _, _, meta in pipe.drain():
            send_fail(q_unit.unit_id, meta[2], job=meta[3])
        for unit_d in lease_q:
            # leased but never submitted (the batch aborted first):
            # release these too, or they pin the ledger until expiry
            send_fail(unit_d["id"], [], job=unit_d.get("job"))
        if sender is not None:
            try:
                sender._q.join()   # land the fails; the original
            except Exception:      # error outranks any send failure
                pass
        raise
    finally:
        # a capture window still in flight on a CLEAN stop gets a
        # bounded grace to finish + push, and summaries that already
        # finished but were never drained (the background analysis
        # landed between the last poll and the stop) ship too: the
        # job's last unit landing mid-window would otherwise kill
        # the capture silently and the requester waits out its full
        # --wait.  Error exits skip the grace (the connection is
        # gone; a push can't land).  finish_now with nothing in
        # flight is one lock probe, so the idle exit pays nothing.
        if stop_seen:
            for _ in range(profiler_mod.HISTORY_MAX):
                s = prof.finish_now()
                if s is None:
                    break
                push_profile(s)
        # whatever remains must not outlive the loop (the profiler
        # slot would stay taken for the process lifetime)
        prof.abort_window()
        if sender is not None:
            sender.close()
