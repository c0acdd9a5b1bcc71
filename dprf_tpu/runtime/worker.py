"""Workers: fetch WorkUnit -> generate candidates -> hash -> report hits.

DeviceMaskWorker is the TPU path: one fused jitted step per job
(ops/pipeline.py), asynchronously dispatched per batch so the device
pipeline never drains; results are resolved after the whole unit is
queued.  Only hit buffers cross back to the host.

CpuWorker is the reference path (`--device=cpu`): oracle engines over
host-materialized candidates.  It is also the fallback that rescans a
batch exactly if a device hit buffer ever overflows.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from dprf_tpu.engines.base import HashEngine, Target
from dprf_tpu.generators.base import CandidateGenerator
from dprf_tpu.runtime.workunit import WorkUnit
from dprf_tpu.telemetry import coverage
from dprf_tpu.telemetry.trace import get_tracer


@dataclasses.dataclass(frozen=True)
class Hit:
    target_index: int      # position in the job's target list
    cand_index: int        # global keyspace index
    plaintext: bytes


class PendingUnit:
    """A WorkUnit whose device work is fully enqueued but not yet
    resolved.  The unit-level flag (device-accumulated hit indicator)
    is already on its way back to the host; ``resolve()`` blocks on it
    and only fetches the queued hit buffers when it is nonzero.

    Callers that hold a PendingUnit while submitting the NEXT unit
    overlap the flag's readback with that unit's compute (see
    Coordinator.run / bench.run_config).

    ``resolve()`` is two steps back to back, which a caller may split:
    ``resolve_head()`` decodes the unit and dispatches the re-probes of
    its collided tiles, never blocking on them; ``resolve_tiles()``
    reads them back.  Between the two, ``tiles`` is non-empty, and
    Coordinator.run submits the next unit there, so the re-probes run
    ahead of that unit's program instead of in front of an empty
    device."""

    __slots__ = ("worker", "unit", "queued", "flag", "tiles")

    def __init__(self, worker, unit, queued, flag):
        self.worker = worker
        self.unit = unit
        self.queued = queued
        self.flag = flag
        #: the re-probes resolve_head dispatched and resolve_tiles
        #: reads back: one list of (start, end, output) a decoded
        #: window that had collided tiles
        self.tiles: list = []
        for kind, _, _ in queued:
            count_dispatches(worker, kind)

    def resolve(self) -> list["Hit"]:
        hits = self.resolve_head()
        hits.extend(self.resolve_tiles())
        return hits

    def resolve_head(self) -> list["Hit"]:
        """Wait for the flag, read back and decode every dispatch:
        the hits of every lane but those of collided tiles, whose
        re-probes go out on the device and into ``tiles``."""
        if self.flag is None:
            return []
        tracer, uid = get_tracer(), self.unit.unit_id
        with tracer.station("wait", unit=uid):
            flag = int(self.flag)
        if flag == 0:
            return []
        import jax
        hits: list[Hit] = []
        w = self.worker
        with tracer.station("decode", unit=uid):
            # every dispatch's hit buffers in one transfer: the
            # decoders read host arrays, a fused program's rows too
            with tracer.station("readback", unit=uid):
                queued = jax.device_get(self.queued)
            w._reprobes = self.tiles
            try:
                for kind, start, result in queued:
                    hits.extend(w._decode_queued(kind, start, result,
                                                 self.unit))
            finally:
                w._reprobes = None
        return hits

    def resolve_tiles(self, ahead: bool = False) -> list["Hit"]:
        """Read back the re-probes resolve_head dispatched and verify
        their lanes.  ``ahead``: the next unit was submitted in
        between, which `verify=` counts under ``ahead``."""
        tiles, self.tiles = self.tiles, []
        if not tiles:
            return []
        if ahead:
            counts = self.worker.verify_counts
            counts["ahead"] = counts.get("ahead", 0) + 1
        with get_tracer().station("decode", unit=self.unit.unit_id):
            return self.worker._tile_hits(tiles, self.unit)


def submit_or_process(worker, unit) -> "PendingUnit":
    """Uniform pipelining entry.  A worker is submitted asynchronously
    ONLY when its ``process`` is one of the submit-based
    implementations (marked ``_submit_based``): a subclass that
    overrides ``process`` with its own sweep logic (per-salt-block
    steps, per-target steps, sharded super-batches, chunked bcrypt,
    CpuWorker...) must run through that override, not through an
    inherited ``submit`` that would bypass it."""
    if getattr(type(worker).process, "_submit_based", False):
        return worker.submit(unit)
    return _ResolvedUnit(worker.process(unit))


class _ResolvedUnit:
    __slots__ = ("hits",)

    def __init__(self, hits):
        self.hits = hits

    def resolve(self):
        return self.hits


def count_dispatches(worker, kind: str, n: int = 1) -> None:
    """What actually ran, by dispatch shape (describe_worker reads it):
    one dict bump per dispatch, on the worker that dispatched."""
    counts = worker.__dict__.setdefault("dispatches", {})
    counts[kind] = counts.get(kind, 0) + n


def describe_worker(worker) -> dict:
    """What actually runs a job's units, for the job's log: the worker
    class, whether its kernels are interpreted ("n/a": it has no
    kernel, its step is plain XLA), the dispatch shapes used so far
    with their counts, the compile cost with its persistent-cache
    classification, and for a multi-target job what the host
    verified (`verify`: oracle hashes
    of maybe lanes, collided tiles resolved to their maybe lanes on
    the device, collided tiles rescanned whole on the host; for a bulk
    list also the lanes its bitmap passed and the hits the device
    confirmed exactly) and the bulk list's table (`targets`: digests,
    device bytes, `device` or `host-verify` mode).  A job
    that ran a slower path than the one expected must be readable
    from its own log."""
    w = getattr(worker, "_worker", worker)      # OrderedWorker
    counts = getattr(w, "dispatches", None) or {}
    out = {
        "worker": type(w).__name__,
        "interpret": getattr(w, "_interpret", "n/a"),
        "dispatch": ",".join(f"{k}:{n}" for k, n in
                             sorted(counts.items())) or "none",
        "compile_s": f"{getattr(w, 'compile_seconds', 0.0):.2f}",
        "cache": getattr(w, "compile_cache", "off"),
    }
    ptable = getattr(w, "probe_table", None)
    if ptable is not None:
        out["targets"] = (f"n:{ptable.num_targets},table_bytes:"
                          f"{ptable.nbytes},mode:{ptable.mode}")
    if getattr(w, "verify_counts", None):
        out["verify"] = ",".join(f"{k}:{n}" for k, n in
                                 w.verify_counts.items())
    if hasattr(w, "advance_impl"):    # bcrypt: "pallas" | "xla"
        out["advance"] = w.advance_impl
    if hasattr(w, "out_devices"):     # sharded workers (parallel/)
        out["out_devices"] = "/".join(str(i) for i in w.out_devices)
    return out


#: `dprf check` retrace analyzer: the per-batch device dispatch loop.
#: Everything submit() enqueues rides the device stream; a host sync
#: or a retrace inside it stalls every unit of every job.
HOT_PATHS = ("MaskWorkerBase.submit",)

#: env override for the submit-ahead depth both pipelined loops run at
PIPELINE_DEPTH_ENV = "DPRF_PIPELINE_DEPTH"


def pipeline_depth(default: int = 2) -> int:
    """The depth CAP shared by Coordinator.run and rpc.worker_loop --
    the ONE resolution site for the knob.  ``DPRF_PIPELINE_DEPTH``
    overrides (1 = serial fallback: no overlap, no async completion);
    clamped to [1, 64].  The local loop runs AT this depth; the remote
    loop ADAPTS its live depth to the measured RTT / unit-seconds
    ratio below it (AdaptiveDepth) -- the knob bounds how many leases
    one worker may queue, it no longer pins the working depth."""
    from dprf_tpu.utils import env as envreg
    return max(1, min(envreg.get_int(PIPELINE_DEPTH_ENV, int(default)),
                      64))


class AdaptiveDepth:
    """RTT-adaptive submit-ahead depth for the remote worker loop.

    The right depth is a physics answer, not a config answer: to keep
    the device stream full, a worker must hold enough units that the
    lease/complete round trips hide behind compute -- about
    ``1 + rtt/unit_seconds`` units.  A static depth (the old
    ``DPRF_PIPELINE_DEPTH`` semantics) over-leases on fat links
    (units sit idle in one worker's queue while another starves) and
    under-leases on thin ones.  This tracker keeps EWMAs of both
    quantities (same smoothing idea as tune.AdaptiveUnitSizer) and
    derives the live depth each loop iteration; the env knob / CLI
    flag remains as the CAP.

    Until both signals exist the depth stays at ``start`` (2: enough
    to overlap one round trip -- the pre-adaptive default)."""

    __slots__ = ("cap", "depth", "alpha", "_rtt", "_unit")

    def __init__(self, cap: int, start: int = 2, alpha: float = 0.3):
        self.cap = max(1, int(cap))
        self.depth = max(1, min(int(start), self.cap))
        self.alpha = alpha
        self._rtt: Optional[float] = None
        self._unit: Optional[float] = None

    def _ewma(self, cur: Optional[float], sample: float) -> float:
        if cur is None:
            return sample
        return cur + self.alpha * (sample - cur)

    def observe_rtt(self, seconds: float) -> None:
        if seconds > 0:
            self._rtt = self._ewma(self._rtt, seconds)

    def observe_unit(self, seconds: float) -> None:
        if seconds > 0:
            self._unit = self._ewma(self._unit, seconds)

    def update(self) -> int:
        """Recompute and return the live depth (monotonic per call,
        moves at most one step at a time: a single glitched sample
        must not swing a fleet's lease holdings)."""
        if self._rtt is not None and self._unit is not None:
            want = 1 + int(-(-self._rtt // max(self._unit, 1e-9)))
            want = max(1, min(want, self.cap))
            if want > self.depth:
                self.depth += 1
            elif want < self.depth:
                self.depth -= 1
        return self.depth


class UnitPipeline:
    """Bounded submit-ahead FIFO of (unit, PendingUnit): device work
    for every queued unit is already dispatched when it enters, so
    resolving the head overlaps its readback latency with the tail's
    compute.  The ONE pipelining implementation shared by the local
    Coordinator.run and the remote rpc.worker_loop -- over the RPC
    boundary the same overlap additionally hides the lease/complete
    round trips behind the device stream."""

    __slots__ = ("worker", "depth", "_q")

    def __init__(self, worker, depth: int):
        self.worker = worker
        self.depth = max(1, int(depth))
        self._q: list = []

    def __len__(self) -> int:
        return len(self._q)

    @property
    def full(self) -> bool:
        return len(self._q) >= self.depth

    def submit(self, unit, meta=None, worker=None) -> None:
        """Dispatch the unit's device work now (enqueue-only for
        submit-based workers; a serial worker's process runs here) and
        queue it for a later resolve.  ``worker`` overrides the
        pipeline's default for THIS unit -- a multi-job worker loop
        routes each unit to its job's worker while sharing one
        submit-ahead queue.
        The submit timestamp is taken BEFORE the dispatch so a
        serial unit's submit-to-resolve time covers its real
        work, not just queue wait."""
        import time
        t0 = time.monotonic()
        w = worker or self.worker
        # the pipeline itself never looks into a unit
        tracer, uid = get_tracer(), getattr(unit, "unit_id", None)
        with tracer.station("submit", unit=uid):
            pending = submit_or_process(w, unit)
        self._q.append((unit, pending, t0, meta))

    def pop(self):
        """Oldest (unit, pending, t_submit, meta); caller resolves."""
        return self._q.pop(0)

    def drain(self) -> list:
        """Abandon every queued entry (failure path): entries oldest
        first; in-flight device work is never resolved."""
        entries = self._q[:]
        self._q.clear()
        return entries


def word_cover_range(unit: WorkUnit, n_rules: int) -> tuple:
    """Covering word range [w_start, w_end) of a keyspace-index unit
    (index = word * n_rules + rule; ceil on the end)."""
    return unit.start // n_rules, -(-unit.end // n_rules)


def wordlist_lane_to_gidx(lane: int, ws: int, word_batch: int,
                          n_rules: int) -> int:
    """Rule-major flat step lane (r*B + b) -> global keyspace index for
    a step whose word window starts at ws.  Single source of truth for
    the decode every wordlist worker uses."""
    r, b = divmod(lane, word_batch)
    return (ws + b) * n_rules + r


class CpuWorker:
    """Oracle-engine worker; handles salted and unsalted engines."""

    def __init__(self, engine: HashEngine, gen: CandidateGenerator,
                 targets: Sequence[Target], chunk: int = 2048):
        self.engine = engine
        self.gen = gen
        self.targets = list(targets)
        self.chunk = chunk
        self._digest_map = {t.digest: i for i, t in enumerate(self.targets)}

    def process(self, unit: WorkUnit) -> list[Hit]:
        hits: list[Hit] = []
        for start in range(unit.start, unit.end, self.chunk):
            n = min(self.chunk, unit.end - start)
            # Rule-based generators may reject candidates (None): those
            # keyspace indices are holes — never hashed.
            pairs = [(start + j, c)
                     for j, c in enumerate(self.gen.candidates(start, n))
                     if c is not None]
            if not pairs:
                continue
            cands = [c for _, c in pairs]
            if self.engine.salted:
                for ti, t in enumerate(self.targets):
                    for (gidx, cand), d in zip(pairs, self.engine.hash_batch(
                            cands, params=t.params)):
                        if d == t.digest:
                            hits.append(Hit(ti, gidx, cand))
            else:
                for (gidx, cand), d in zip(pairs,
                                           self.engine.hash_batch(cands)):
                    ti = self._digest_map.get(d)
                    if ti is not None:
                        hits.append(Hit(ti, gidx, cand))
        return hits

    #: host loop, no device stream to overlap -- pipelining a CpuWorker
    #: just runs process() at submit time (tools/check_worker_contract)
    process._serial_only = True


class _MultiPending:
    """Pending handle over several sub-unit pendings (one per
    contiguous index run of a rank-ordered unit); resolve() drains
    them oldest-first, so device readbacks overlap later runs'
    compute exactly like the unit pipeline does across units."""

    __slots__ = ("_pendings",)

    def __init__(self, pendings):
        self._pendings = pendings

    def resolve(self) -> list["Hit"]:
        hits: list[Hit] = []
        for p in self._pendings:
            hits.extend(p.resolve())
        return hits


class OrderedWorker:
    """Rank-space adapter over any worker: the dispatcher's unit spans
    are RANKS (generators/order.py); this wrapper decodes each leased
    span into its contiguous index runs and submits every run through
    the wrapped worker's unchanged index-space path -- the device
    pipeline (fused steps, sharded supersteps, Pallas kernels) never
    sees a rank.  Runs are submitted in rank order, so the most
    probable candidates are swept (and their hits surface) first even
    within one unit.  Sub-units reuse the parent's unit id and job id:
    coverage accounting stays per leased unit, and every Hit carries
    its index-space cand_index exactly as before."""

    def __init__(self, worker, order):
        self._worker = worker
        #: the job's rank<->index bijection; the coordinator's rescan
        #: path (Coordinator._finish_unit) re-wraps its CPU oracle
        #: worker with this same object
        self.order = order

    def submit(self, unit: WorkUnit) -> "_MultiPending":
        subs = []
        for s, e in self.order.index_spans(unit.start, unit.end):
            subs.append(submit_or_process(
                self._worker, WorkUnit(unit.unit_id, s, e - s,
                                       job_id=unit.job_id)))
        return _MultiPending(subs)

    def process(self, unit: WorkUnit) -> list["Hit"]:
        return self.submit(unit).resolve()

    process._submit_based = True

    def __getattr__(self, name):
        # everything else (gen, targets, warmup_async, engine,
        # compile_seconds...) is the wrapped worker's business
        return getattr(self._worker, name)


class MaskWorkerBase:
    """Shared machinery for fused-pipeline mask workers.

    Subclasses set ``self.step`` (the jitted crack step) and
    ``self.stride`` (keyspace indices consumed per step call) in
    __init__ after calling ``_setup_targets``, and implement
    ``_batch_hits`` to decode one step result.
    """

    #: attack shape this worker family's program registry records
    #: carry (telemetry/programs.py); wordlist/combinator subclasses
    #: override
    ATTACK = "mask"

    #: lanes one collided-tile re-probe can return (beside
    #: PallasMaskWorker.RESCAN_CAPACITY, the tiles a batch can
    #: report): a tile expects 0.016 maybes at the kernel probe's
    #: false-positive rate, so 16 is ample
    TILE_LANES = 16

    #: a bulk list's ProbeTable (_setup_probe; describe_worker's
    #: `targets=`), and what a step that takes the table as data is
    #: handed behind (base digits, n_valid): ProbeTable.device_args
    probe_table = None
    _table_args = ()

    #: the collided-tile re-probe (ops/pallas_mask.make_tile_reprobe);
    #: None where the step has none (single target, XLA steps,
    #: pallas_ext steps): collided tiles are then rescanned on the host
    _reprobe = None
    #: where _reprobe_tiles hands a window's re-probes while a
    #: PendingUnit decodes (PendingUnit.resolve_head); None otherwise
    _reprobes = None

    def _setup_targets(self, engine, gen, targets: Sequence[Target],
                       hit_capacity: int, oracle: Optional[HashEngine],
                       probe_ok: bool = False):
        from dprf_tpu.ops import compare as cmp_ops
        from dprf_tpu.ops.pipeline import target_words

        self.engine = engine
        self.gen = gen
        self.targets = list(targets)
        self.hit_capacity = hit_capacity
        self.oracle = oracle
        digests = [t.digest for t in self.targets]
        self.multi = len(digests) > 1
        if self.multi:
            #: what the host verified (describe_worker's `verify=`):
            #: maybe lanes hashed, the oracle calls they took
            self.verify_counts = {"lanes": 0, "batches": 0, "tiles": 0,
                                  "host_tiles": 0}
        if self.multi and probe_ok:
            ptable = self._setup_probe(digests)
            if ptable is not None:
                return ptable
        if self.multi:
            table = cmp_ops.make_target_table(
                digests, little_endian=engine.little_endian)
            self._order = table.order
            return table
        self._order = np.zeros(1, dtype=np.int64)
        return target_words(digests[0], engine.little_endian)

    def _setup_probe(self, digests: list):
        """Bulk target lists (>= DPRF_TARGETS_PROBE_MIN digests) get
        the O(1)-per-candidate probe table (dprf_tpu/targets/) instead
        of the replicated compare table; None for any other list.
        Only workers whose step builder understands a ProbeTable pass
        probe_ok=True.  A table that cannot be built, or that the
        byte budget cut to host-verify mode for a job with no oracle
        to verify with, raises with the reason: the replicated table
        in its place would be another job than the one asked for."""
        import jax

        from dprf_tpu.targets import probe as probe_mod
        from dprf_tpu.utils.logging import DEFAULT as log
        if not probe_mod.probe_eligible(self.targets, self.engine):
            return None
        with get_tracer().station("targets"):
            ptable = probe_mod.build_probe_table(
                digests, little_endian=self.engine.little_endian,
                log=log)
            jax.block_until_ready(ptable.device_args())
        if ptable.mode == probe_mod.MODE_HOST_VERIFY \
                and self.oracle is None:
            # every survivor needs a host hash in this layout; without
            # an oracle the worker could never confirm a single hit
            raise ValueError(
                f"the probe table of {len(digests)} targets does not "
                "fit its device byte budget (DPRF_TARGETS_MAX_BYTES / "
                "DPRF_TARGETS_HEADROOM_FRAC) beside its exact-verify "
                "table, and host-verify mode needs an oracle engine")
        self._digest_map = {t.digest: i
                            for i, t in enumerate(self.targets)}
        self._order = ptable.order
        self.probe_table = ptable
        # distinct program-registry label: the probe step's roofline
        # is a different program from the replicated-compare step's
        self.ATTACK = self.ATTACK + "+probe"
        return ptable

    def warmup_args(self) -> tuple:
        """The step arguments a zero-work warmup dispatch uses -- same
        shapes/dtypes as production dispatches, so the compiled (and
        persistently cached) program is the one real units run."""
        import jax.numpy as jnp
        return (jnp.asarray(self.gen.digits(0), dtype=jnp.int32),
                jnp.int32(0))

    def warmup(self) -> None:
        """Force the step's compile now (jit is lazy).  The engine
        factory calls this for Pallas workers so a Mosaic/XLA compile
        failure raises at worker construction instead of mid-job."""
        args = self.warmup_args()   # built OUTSIDE the observer: arg
        # materialization can write tiny cache entries of its own
        self._timed_warmup(args)

    def _timed_warmup(self, args: tuple) -> None:
        """One observed warmup dispatch: times the compile, classifies
        it against the persistent compilation cache (hit/miss/off),
        and publishes dprf_compile_seconds{engine,cache} (the dominant
        fixed cost of a job; a scrape that shows minutes here explains
        a 'stalled' fleet that is really compiling)."""
        import time

        from dprf_tpu.compilecache import compile_observer
        from dprf_tpu.utils.sync import hard_sync
        t0 = time.perf_counter()
        # hard_sync reads a value back, so a RUNTIME kernel fault
        # also surfaces here, not just a compile failure
        with compile_observer(getattr(self.engine, "name",
                                      "unknown")) as obs:
            hard_sync(self.step(*args))
            if self._reprobe is not None:
                # same (base digits, n_valid) arguments: the re-probe
                # compiles here, never inside a job
                hard_sync(self._reprobe(*args))
        #: warmup/compile wall time; tune/autotuner.sweep folds it into
        #: a rung's fixed cost (covers workers warmed before the
        #: sweep's own clock started)
        self.compile_seconds = time.perf_counter() - t0
        #: "hit" | "miss" | "off": whether the persistent compilation
        #: cache served this step (bench and prewarm report it)
        self.compile_cache = obs.cache
        self._warmed = True
        # register the compiled program for XLA-derived introspection
        # (telemetry/programs.py).  Registration only -- the analysis
        # (a cache-served recompile + cost/memory read) is deferred to
        # an off-hot-path consumer (warmup_async's background thread,
        # the heartbeat loop, tune, bench, `dprf programs`).
        self._register_program(args)

    def _register_program(self, args: tuple, compiled=None,
                          lowered=None) -> None:
        from dprf_tpu.telemetry import programs as programs_mod
        programs_mod.register_program(
            getattr(self.engine, "name", "unknown"), self.ATTACK,
            int(getattr(self, "stride", 0) or 0), step=self.step,
            args=args, compiled=compiled, lowered=lowered)

    def aot_compile(self) -> None:
        """Compile the step WITHOUT dispatching (``dprf prewarm``):
        lower + compile populates the persistent compilation cache
        with exactly the executable a same-shape warmup dispatch
        loads.  Steps that cannot AOT-lower fall back to a plain
        warmup dispatch (still zero keyspace work: n_valid = 0).

        Tracing/lowering happens OUTSIDE the observer: it is pure
        Python the cache can never serve, and folding it in would
        understate the cache's effect on the XLA compile itself
        (``xla_compile_seconds``, the >=5x acceptance quantity)."""
        import time
        args = self.warmup_args()
        lower = getattr(self.step, "lower", None)
        if lower is None:
            return self.warmup()
        from dprf_tpu.compilecache import compile_observer
        t0 = time.perf_counter()
        lowered = lower(*args)
        relowered = (self._reprobe.lower(*args)
                     if self._reprobe is not None else None)
        trace_s = time.perf_counter() - t0
        with compile_observer(getattr(self.engine, "name",
                                      "unknown")) as obs:
            compiled = lowered.compile()
            if relowered is not None:
                relowered.compile()
        #: the XLA compile alone -- what the persistent cache
        #: eliminates (trace/lower cost is irreducible host Python)
        self.xla_compile_seconds = obs.seconds
        self.compile_seconds = trace_s + obs.seconds
        self.compile_cache = obs.cache
        # the Compiled object is in hand here: analysis is a ~ms read,
        # so prewarm's program table fills with no extra compile; the
        # Lowered rides along for the real module fingerprint
        self._register_program(args, compiled=compiled,
                               lowered=lowered)

    def warmup_async(self):
        """Overlapped warmup: start warmup() on a background thread so
        the step compile runs while the caller finishes job setup
        (potfile preload, session restore, first leases).  Join with
        ``ensure_warm()`` before the first step dispatch -- cold-start
        wall time becomes max(compile, setup) instead of their sum.
        DPRF_ASYNC_WARMUP=0 degrades to a synchronous warmup."""
        import threading

        from dprf_tpu.utils import env as envreg
        if getattr(self, "_warmed", False) or \
                getattr(self, "_warm_thread", None) is not None:
            return self
        if not envreg.get_bool("DPRF_ASYNC_WARMUP"):
            self.warmup()
            return self
        self._warm_error = None

        def _run():
            try:
                self.warmup()
            except BaseException as e:   # noqa: BLE001 -- re-raised
                # by ensure_warm on the caller's thread
                self._warm_error = e
                return
            # deferred program analysis on the SAME background thread:
            # the recompile it triggers is persistent-cache-served (the
            # warmup above just populated the cache) and overlaps job
            # setup exactly like the warmup did.  Best-effort: the
            # analyzed roofline is observability, never job state.
            try:
                from dprf_tpu.telemetry import programs as programs_mod
                programs_mod.analyze_pending()
            except Exception:   # noqa: BLE001
                pass

        t = threading.Thread(target=_run, name="dprf-warmup",
                             daemon=True)
        self._warm_thread = t
        t.start()
        return self

    def ensure_warm(self) -> None:
        """Join an in-flight warmup_async(); re-raises its failure on
        the calling thread (the same place a synchronous warmup would
        have raised).  No-op when warmup never ran or already ran."""
        t = getattr(self, "_warm_thread", None)
        if t is None:
            return
        t.join()
        self._warm_thread = None
        err = getattr(self, "_warm_error", None)
        if err is not None:
            self._warm_error = None
            raise err

    def _batch_flag(self, result):
        """Scalar that is nonzero iff this batch needs host attention
        (hits or overflow).  Element 0 of every step result is its hit
        count; subclasses with extra buffers override."""
        return result[0]

    #: largest number of batches fused into one super-step dispatch
    #: and the smallest chunk worth a dedicated compile.  Power-of-two
    #: inner sizes bound the compile cache at log2(SUPER_CAP) entries.
    SUPER_CAP = 256
    SUPER_MIN = 8

    #: fusion mechanism for multi-batch units.  "scan" wraps the step
    #: in ops/superstep.make_super_step (lax.scan with stacked
    #: outputs) -- right for the XLA-pipeline steps, whose bodies are
    #: plain jnp ops.  "wide" rebuilds the worker's own step at
    #: inner*stride lanes via _make_step: the SAME single-pallas_call
    #: program shape as a plain batch, just a longer (sequential)
    #: grid.  "loop" is the kernel superstep: a scalar/small-buffer-
    #: carry fori_loop over ONE offset-aware compiled kernel
    #: (ops/superstep.make_loop_super_step) -- the sharded runtime's
    #: superstep shape on a single chip.  Pallas workers set "loop"
    #: or "wide"; kernels pay no extra HBM for either (tile state is
    #: VMEM, raw output is batch/4 bytes), unlike the XLA steps whose
    #: materialized candidate blocks scale with batch.  A worker runs
    #: the ONE shape its mode names (plus per-batch for remainders):
    #: a fused program the compiler refuses raises, it does not
    #: degrade to another shape.
    SUPER_MODE = "scan"

    def _super_batch(self) -> int:
        """Keyspace indices consumed per super-step iteration."""
        return self.stride

    def _super_step(self, inner: int):
        from dprf_tpu.ops.superstep import make_super_step
        cache = getattr(self, "_super_cache", None)
        if cache is None:
            cache = self._super_cache = {}
        # keyed by the step OBJECT, not just inner: some workers swap
        # self.step between sweeps (descrypt's salt blocks).  The
        # cached entry holds a strong ref to its step so the id key
        # can never be reused by a successor object.
        key = (id(self.step), inner)
        entry = cache.get(key)
        if entry is None:
            entry = cache[key] = (self.step, make_super_step(
                self.step, inner, self._super_batch(), self._batch_flag))
        return entry[1]

    def _super_inner(self, remaining_chunks: int) -> int:
        """Power-of-two scan length for a super dispatch, or 0 for the
        per-batch path.  DPRF_SUPERSTEP=0 disables super dispatch."""
        from dprf_tpu.ops.superstep import max_inner
        from dprf_tpu.utils import env as envreg
        if not envreg.get_bool("DPRF_SUPERSTEP"):
            return 0
        cap = max_inner(self._super_batch(), self.SUPER_CAP)
        if remaining_chunks < self.SUPER_MIN or cap < self.SUPER_MIN:
            return 0
        return min(cap, 1 << (remaining_chunks.bit_length() - 1))

    def _make_step(self, batch: int):
        """Rebuild this worker's step at a different lane count.
        Wide-capable subclasses (SUPER_MODE == "wide") override; the
        contract is the per-batch step's exactly, with hit capacities
        scaled up by batch // self.stride (shape-derived at decode)."""
        raise NotImplementedError

    def _make_loop_parts(self, inner: int):
        """(offset-aware per-batch step, accumulation groups) for
        ops/superstep.make_loop_super_step.  Loop-capable subclasses
        (SUPER_MODE == "loop") override; the step must be built with
        the WINDOW buffer capacities so its overflow/collision
        inflation exceeds the window buffers too."""
        raise NotImplementedError

    def _call_fused(self, key, fn, *args):
        """Dispatch a lazily built fused program.  Its FIRST call
        compiles (synchronously, before it enqueues), so that call is
        observed: the compile is timed, classified against the
        persistent cache and folded into compile_seconds /
        compile_cache -- a job whose warmup hit the cache but whose
        fused program compiled cold must not read as a hit."""
        seen = self.__dict__.setdefault("_fused_seen", set())
        if key in seen:
            return fn(*args)
        seen.add(key)
        from dprf_tpu.compilecache import compile_observer
        with compile_observer(getattr(self.engine, "name",
                                      "unknown")) as obs:
            out = fn(*args)
        self.compile_seconds = (getattr(self, "compile_seconds", 0.0)
                                + obs.seconds)
        if getattr(self, "compile_cache", "off") != "miss":
            self.compile_cache = obs.cache
        return out

    def _loop_dispatch(self, inner: int, base, n_valid):
        """One loop-superstep dispatch (SUPER_MODE == "loop")."""
        import jax.numpy as jnp

        from dprf_tpu.ops.superstep import make_loop_super_step
        cache = getattr(self, "_loop_cache", None)
        if cache is None:
            cache = self._loop_cache = {}
        ls = cache.get(inner)
        if ls is None:
            step, groups = self._make_loop_parts(inner)
            ls = cache[inner] = make_loop_super_step(
                step, inner, self._super_batch(), groups)
        return self._call_fused(("loop", inner), ls, base,
                                jnp.int32(n_valid), *self._table_args)

    def _wide_step(self, sbatch: int):
        cache = getattr(self, "_wide_cache", None)
        if cache is None:
            cache = self._wide_cache = {}
        step = cache.get(sbatch)
        if step is None:
            step = cache[sbatch] = self._make_step(sbatch)
        return step

    def _wide_dispatch(self, sbatch: int, base, n_valid):
        """One wide dispatch (SUPER_MODE == "wide")."""
        import jax.numpy as jnp
        step = self._wide_step(sbatch)
        return self._call_fused(("wide", id(step)), step, base,
                                jnp.int32(n_valid))

    def _super_dispatch(self, inner: int, xs, n_valid):
        """One scan super dispatch (SUPER_MODE == "scan").  Super
        programs compile lazily at the first big unit."""
        import jax.numpy as jnp
        ss = self._super_step(inner)
        return self._call_fused(("scan", id(ss)), ss, jnp.asarray(xs),
                                jnp.int32(n_valid))

    def submit(self, unit: WorkUnit) -> PendingUnit:
        """Enqueue ALL device work for the unit and return a
        PendingUnit.  Large units go out as super-step dispatches --
        one fused program covering up to SUPER_CAP batches -- so the
        per-dispatch overhead (argument transfers + enqueue) is paid
        once per ~10^9 candidates instead of once per batch; the
        remainder uses the per-batch step.  The unit-level hit flag is
        accumulated ON DEVICE across both kinds, so a hitless unit
        costs exactly one scalar readback."""
        import jax.numpy as jnp
        queued = []
        flag = None
        pos = unit.start
        mode = self.SUPER_MODE
        while True:
            # _super_inner's max_inner(stride) budget bounds the wide
            # program's inner*stride lanes to int32 as well -- every
            # worker using THIS submit has _super_batch() == stride
            inner = self._super_inner((unit.end - pos) // self.stride)
            if inner < 2:
                break
            sstride = inner * self.stride
            if mode in ("loop", "wide"):
                # a loop result decodes exactly like a wide one
                # (window-relative buffers)
                base = jnp.asarray(self.gen.digits(pos), dtype=jnp.int32)
                if mode == "loop":
                    result = self._loop_dispatch(inner, base, sstride)
                else:
                    result = self._wide_dispatch(sstride, base, sstride)
                f = self._batch_flag(result)
                flag = f if flag is None else flag + f
                queued.append((mode, (pos, sstride), result))
                pos += sstride
                continue
            digits = np.stack([
                np.asarray(self.gen.digits(pos + i * self.stride),
                           dtype=np.int32) for i in range(inner)])
            f, outs = self._super_dispatch(inner, digits, sstride)
            flag = f if flag is None else flag + f
            queued.append(("scan", pos, outs))
            pos += sstride
        for bstart in range(pos, unit.end, self.stride):
            n_valid = min(self.stride, unit.end - bstart)
            base = jnp.asarray(self.gen.digits(bstart), dtype=jnp.int32)
            result = self.step(base, jnp.int32(n_valid))
            # scalar adds ride the stream behind their batches; a
            # per-batch count fetch would sync the host to every batch
            f = self._batch_flag(result)
            flag = f if flag is None else flag + f
            queued.append(("batch", bstart, result))
        if flag is not None and hasattr(flag, "copy_to_host_async"):
            flag.copy_to_host_async()
        return PendingUnit(self, unit, queued, flag)

    def process(self, unit: WorkUnit) -> list[Hit]:
        return self.submit(unit).resolve()

    process._submit_based = True   # safe to pipeline via submit()

    @staticmethod
    def _super_rows(result, start: int, window: int, decode_row):
        """Stacked super-step outputs -> per-row decode at start + i *
        window.  Each row is exactly one per-batch step output tuple,
        so overflow/rescan semantics stay at one-batch granularity."""
        arrs = [np.asarray(a) for a in result]
        hits: list[Hit] = []
        for i in range(arrs[0].shape[0]):
            hits.extend(decode_row(start + i * window,
                                   tuple(a[i] for a in arrs)))
        return hits

    def _decode_queued(self, kind: str, start, result,
                       unit: WorkUnit) -> list[Hit]:
        """One queued dispatch -> Hit records; super rows and wide
        windows decode through the SAME _batch_hits path as plain
        batches (wide entries carry their window explicitly)."""
        if kind == "batch":
            return self._batch_hits(start, result, unit)
        if kind in ("loop", "wide"):
            pos, window = start
            return self._batch_hits(pos, result, unit, window=window)
        return self._super_rows(
            result, start, self.stride,
            lambda bstart, row: self._batch_hits(bstart, row, unit))

    def _decode_lanes(self, bstart: int, lanes_np, tpos_np,
                      unit: WorkUnit) -> list[Hit]:
        """Hit-buffer arrays (any shape; lane -1 = unused slot) -> Hit
        records: the lanes the device confirmed, in slot order, then
        the verified maybes.

        Probe-table steps emit an OUT-OF-RANGE target pos for lanes
        the device did not verify exactly (the in-kernel bitmap, the
        degraded host-verify layout, or a sharded survivor-buffer
        overflow): those lanes are Bloom survivors, not confirmed
        hits, and resolve in ONE oracle call (_verify_probe_lanes) --
        false positives drop."""
        lanes_np, tpos_np = np.ravel(lanes_np), np.ravel(tpos_np)
        valid = lanes_np >= 0
        # Python ints: a keyspace index may pass int64
        gidxs = [bstart + lane for lane in lanes_np[valid].tolist()]
        if not self.multi:
            return [Hit(0, g, self.gen.candidate(g)) for g in gidxs]
        tpos = tpos_np[valid]
        sure = ((tpos >= 0) & (tpos < len(self._order))).tolist()
        hits = [Hit(int(self._order[tp]), g, self.gen.candidate(g))
                for g, tp, ok in zip(gidxs, tpos.tolist(), sure) if ok]
        hits.extend(self._verify_probe_lanes(
            [g for g, ok in zip(gidxs, sure) if not ok], unit))
        return hits

    def _verify_probe_lanes(self, gidxs: Sequence[int],
                            unit: WorkUnit) -> list[Hit]:
        """The one verifier of maybe lanes: their plaintexts hashed in
        ONE oracle call, each digest looked up in the target map; the
        hits in lane order, false positives dropped."""
        if not gidxs:
            return []
        if self.oracle is None:
            raise RuntimeError(
                "unverified probe-table survivor and no oracle engine "
                "to resolve it with")
        self.verify_counts["lanes"] += len(gidxs)
        self.verify_counts["batches"] += 1
        hits = []
        with get_tracer().station("oracle", unit=unit.unit_id):
            plains = [self.gen.candidate(g) for g in gidxs]
            for g, plain, digest in zip(gidxs, plains,
                                        self.oracle.hash_batch(plains)):
                ti = self._digest_map.get(digest)
                if ti is not None:
                    hits.append(Hit(ti, g, plain))
        return hits

    def _setup_tile_reprobe(self, twords, sub: int,
                            probe_fp: Optional[float] = None) -> None:
        """Multi-target kernel workers: build the device re-probe of a
        collided tile from the step's own kernel body, at the step's
        tile and probe geometry.  Steps from ops/pallas_ext (engines
        outside CORES) have no such body and keep the host rescan."""
        from dprf_tpu.ops.pallas_mask import CORES, make_tile_reprobe
        if self.engine.name in CORES:
            self._reprobe = make_tile_reprobe(
                self.engine.name, self.gen, twords, sub,
                self.TILE_LANES, probe_fp)

    def _reprobe_tiles(self, starts, unit: WorkUnit) -> None:
        """Dispatch the device re-probe of each collided tile (a tile
        of self._tile candidates from `start`, clipped to the unit)
        and hand the pending entries to the unit being decoded
        (PendingUnit.resolve_head).  Enqueue only: they are read back
        by PendingUnit.resolve_tiles, after the window's single maybes
        are verified and, in Coordinator.run, after the next unit is
        submitted."""
        import jax.numpy as jnp
        if not starts:
            return
        pending = []
        with get_tracer().station("reprobe", unit=unit.unit_id):
            for start in starts:
                end = min(start + self._tile, unit.end)
                if end <= start:
                    continue
                out = None
                if self._reprobe is not None:
                    # coverage note: the tile is swept a second time,
                    # on the device -- deliberate re-coverage
                    coverage.note("rescan", start, end,
                                  unit=unit.unit_id, kind="device")
                    out = self._reprobe(
                        jnp.asarray(self.gen.digits(start),
                                    dtype=jnp.int32),
                        jnp.int32(end - start))
                pending.append((start, end, out))
        if pending:
            self._reprobes.append(pending)

    def _tile_hits(self, tiles: list, unit: WorkUnit) -> list[Hit]:
        """Read the re-probes back, in ONE transfer for the whole unit:
        the maybe lanes of a window's collided tiles go to the verifier
        together, in one oracle call a window.  A lane that fails the
        probe bitmap cannot be a target (the bitmap has no false
        negatives), so that is the tile's exact answer.  A re-probe
        that disagrees with the kernel (fewer than the two lanes that
        made the tile collided) or overflowed its buffer, and a step
        with no re-probe, take the exact host rescan of the tile."""
        import jax
        tracer = get_tracer()
        hits: list[Hit] = []
        with tracer.station("reprobe_wait", unit=unit.unit_id):
            outs = jax.device_get([[out for _, _, out in window]
                                   for window in tiles])
        for window, window_outs in zip(tiles, outs):
            maybes: list[int] = []
            for (start, end, _), out in zip(window, window_outs):
                if out is not None and 2 <= out[0] <= out[1].shape[0]:
                    self.verify_counts["tiles"] += 1
                    lanes = np.asarray(out[1])
                    maybes.extend(start + lane
                                  for lane in lanes[lanes >= 0].tolist())
                    continue
                self.verify_counts["host_tiles"] += 1
                coverage.note("rescan", start, end, unit=unit.unit_id,
                              kind="host")
                with tracer.station("oracle", unit=unit.unit_id):
                    hits.extend(CpuWorker(self.oracle, self.gen,
                                          self.targets)
                                .process(WorkUnit(-1, start, end - start)))
            hits.extend(self._verify_probe_lanes(maybes, unit))
        return hits

    def _rescan(self, bstart: int, unit: WorkUnit,
                window: int = 0) -> list[Hit]:
        """Exact host rescan of one overflowed dispatch window
        (pathological case: more hits than the device hit buffer
        holds).  window defaults to one batch stride; wide dispatches
        pass their full window."""
        if self.oracle is None:
            raise RuntimeError(
                f"hit buffer overflow (> {self.hit_capacity}) and no "
                "oracle engine to rescan with; raise hit_capacity")
        end = min(bstart + (window or self.stride), unit.end)
        # coverage note (ISSUE 19): the exact rescan RE-sweeps this
        # range -- the audit trail must show the second pass was
        # deliberate, not a double-lease
        coverage.note("rescan", bstart, end, unit=unit.unit_id)
        sub = WorkUnit(-1, bstart, end - bstart)
        with get_tracer().station("oracle", unit=unit.unit_id):
            return CpuWorker(self.oracle, self.gen,
                             self.targets).process(sub)

    def _batch_hits(self, bstart: int, result, unit: WorkUnit,
                    window: int = 0) -> list[Hit]:
        count, lanes, tpos = result
        count = int(count)
        if count == 0:
            return []
        # capacity is the buffer the step was BUILT with (wide steps
        # scale it), not the worker's nominal hit_capacity
        if count > lanes.shape[0]:
            if window > self.stride:
                return self._redrive_wide(bstart, window, unit)
            return self._rescan(bstart, unit, window)
        return self._decode_lanes(bstart, np.asarray(lanes),
                                  np.asarray(tpos), unit)

    def _redrive_wide(self, bstart: int, window: int,
                      unit: WorkUnit) -> list[Hit]:
        """An overflowed wide window re-runs through the per-batch
        DEVICE step, so exact-rescan granularity stays one stride.
        The in-kernel collision sentinel (count = capacity + 1 on any
        two-hit tile) makes wide 'overflow' far more likely than real
        buffer exhaustion; a whole-window host rescan of 10^8+
        candidates here would stall the job for hours."""
        import jax.numpy as jnp
        hits: list[Hit] = []
        end = min(bstart + window, unit.end)
        # coverage note (ISSUE 19): this window re-runs per-batch on
        # device -- deliberate re-coverage, visible to the auditor
        coverage.note("redrive", bstart, end, unit=unit.unit_id)
        for bs in range(bstart, end, self.stride):
            nv = min(self.stride, end - bs)
            base = jnp.asarray(self.gen.digits(bs), dtype=jnp.int32)
            hits.extend(self._batch_hits(
                bs, self.step(base, jnp.int32(nv)), unit))
        return hits


class WordlistWorkerBase(MaskWorkerBase):
    """Wordlist-specific hit decoding + rescan shared by the single-
    device and sharded wordlist workers.  Subclasses set
    ``self.word_batch`` (words per step, = the step's flat-lane stride
    divisor) before using these."""

    ATTACK = "wordlist"

    def warmup_args(self) -> tuple:
        """Wordlist steps take (word-window start, n_valid words) --
        both scalars -- not a digit vector."""
        import jax.numpy as jnp
        return (jnp.int32(0), jnp.int32(0))

    def _collect_word_hits(self, lanes_np, tpos_np, ws: int,
                           unit: WorkUnit, lane_wb: int = 0) -> list[Hit]:
        """Flat rule-major step lanes -> in-unit Hit records."""
        R = self.gen.n_rules
        hits: list[Hit] = []
        maybes: list[int] = []
        for lane, tp in zip(lanes_np, tpos_np):
            if lane < 0:
                continue
            gidx = wordlist_lane_to_gidx(int(lane), ws,
                                         lane_wb or self.word_batch, R)
            if not unit.start <= gidx < unit.end:
                continue
            if self.multi and not 0 <= int(tp) < len(self._order):
                # probe-table survivor the device did not verify
                # exactly (host-verify layout / survivor overflow):
                # the window's maybes go to the oracle in one call
                maybes.append(gidx)
                continue
            ti = int(self._order[int(tp)]) if self.multi else 0
            hits.append(Hit(ti, gidx, self.gen.candidate(gidx)))
        hits.extend(self._verify_probe_lanes(maybes, unit))
        return hits

    def _rescan_words(self, ws: int, nw: int, unit: WorkUnit) -> list[Hit]:
        if self.oracle is None:
            raise RuntimeError(
                f"hit buffer overflow (> {self.hit_capacity}) and no "
                "oracle engine to rescan with; raise hit_capacity")
        R = self.gen.n_rules
        start = max(unit.start, ws * R)
        end = min(unit.end, (ws + nw) * R)
        # coverage note (ISSUE 19): exact host re-sweep of the
        # overflowed word window, in candidate-index coordinates
        coverage.note("rescan", start, end, unit=unit.unit_id)
        sub = WorkUnit(-1, start, end - start)
        with get_tracer().station("oracle", unit=unit.unit_id):
            return CpuWorker(self.oracle, self.gen,
                             self.targets).process(sub)


class DeviceWordlistWorker(WordlistWorkerBase):
    """Fused-pipeline worker for wordlist+rules attacks (config 3).

    Units are keyspace index ranges over words x rules (index = word *
    n_rules + rule).  The step covers whole words, so a unit whose
    boundaries are not rule-aligned is processed over the covering word
    range with out-of-unit hits filtered — correct for any unit size,
    though the CLI aligns unit_size to n_rules so nothing is rehashed.
    """

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int = 1 << 18, hit_capacity: int = 64,
                 oracle: Optional[HashEngine] = None):
        from dprf_tpu.ops.rules_pipeline import make_wordlist_crack_step

        tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                  oracle, probe_ok=True)
        self.word_batch = max(1, batch // gen.n_rules)
        self.stride = self.word_batch * gen.n_rules
        self.step = make_wordlist_crack_step(
            engine, gen, tgt, self.word_batch, hit_capacity,
            widen_utf16=getattr(engine, "widen_utf16", False))

    def _super_batch(self) -> int:
        return self.word_batch

    def submit(self, unit: WorkUnit) -> PendingUnit:
        """Word-window analogue of MaskWorkerBase.submit: the step
        argument is a window start (scalar), n_valid counts WORDS, and
        super dispatches cover runs of full word windows."""
        import jax.numpy as jnp

        from dprf_tpu.ops.superstep import max_inner
        w_start, w_end = word_cover_range(unit, self.gen.n_rules)
        w_end = min(w_end, self.gen.n_words)
        queued = []
        flag = None
        ws = w_start
        wide = self.SUPER_MODE == "wide"
        while True:
            inner = self._super_inner((w_end - ws) // self.word_batch)
            if wide:
                # the wide program carries inner * stride rule-expanded
                # LANES; _super_inner budgeted per-word windows only
                inner = min(inner, max_inner(self.stride, self.SUPER_CAP))
            if inner < 2:
                break
            nw = inner * self.word_batch
            if wide:
                result = self._wide_dispatch(nw, jnp.int32(ws), nw)
                f = self._batch_flag(result)
                flag = f if flag is None else flag + f
                queued.append(("wwide", (ws, nw), result))
                ws += nw
                continue
            w0s = (np.arange(inner, dtype=np.int32) * self.word_batch
                   + np.int32(ws))
            f, outs = self._super_dispatch(inner, w0s,
                                           inner * self.word_batch)
            flag = f if flag is None else flag + f
            queued.append(("wsuper", ws, outs))
            ws += inner * self.word_batch
        while ws < w_end:
            nw = min(self.word_batch, w_end - ws)
            result = self.step(jnp.int32(ws), jnp.int32(nw))
            # device-accumulated unit flag; see MaskWorkerBase.submit
            f = self._batch_flag(result)
            flag = f if flag is None else flag + f
            queued.append(("wbatch", (ws, nw), result))
            ws += nw
        if flag is not None and hasattr(flag, "copy_to_host_async"):
            flag.copy_to_host_async()
        return PendingUnit(self, unit, queued, flag)

    def process(self, unit: WorkUnit) -> list[Hit]:
        return self.submit(unit).resolve()

    process._submit_based = True   # safe to pipeline via submit()

    def _window_hits(self, ws: int, nw: int, result, unit: WorkUnit,
                     lane_wb: int = 0) -> list[Hit]:
        """lane_wb: word-batch stride the step's flat lanes were built
        with (lane = r * lane_wb + b) -- self.word_batch for plain
        windows, the full window for wide dispatches."""
        count, lanes, tpos = result
        count = int(count)
        if count == 0:
            return []
        if count > lanes.shape[0]:
            if nw > self.word_batch:
                return self._redrive_wide_words(ws, nw, unit)
            return self._rescan_words(ws, nw, unit)
        return self._collect_word_hits(
            np.asarray(lanes), np.asarray(tpos), ws, unit,
            lane_wb or self.word_batch)

    def _redrive_wide_words(self, ws: int, nw: int,
                            unit: WorkUnit) -> list[Hit]:
        """Overflowed wide word window -> per-batch device windows (see
        MaskWorkerBase._redrive_wide: the rules kernel's collision
        sentinel fires on any two-hit cell, so wide overflow must not
        mean a whole-window host rescan)."""
        import jax.numpy as jnp
        hits: list[Hit] = []
        end = ws + nw
        # coverage note (ISSUE 19): candidate-index coordinates of the
        # word window going back through per-batch dispatch
        R = self.gen.n_rules
        coverage.note("redrive", max(unit.start, ws * R),
                      min(unit.end, end * R), unit=unit.unit_id)
        w = ws
        while w < end:
            n = min(self.word_batch, end - w)
            hits.extend(self._window_hits(
                w, n, self.step(jnp.int32(w), jnp.int32(n)), unit))
            w += n
        return hits

    def _decode_queued(self, kind: str, start, result,
                       unit: WorkUnit) -> list[Hit]:
        if kind == "wbatch":
            ws, nw = start
            return self._window_hits(ws, nw, result, unit)
        if kind == "wwide":
            ws, nw = start
            return self._window_hits(ws, nw, result, unit, lane_wb=nw)
        if kind == "wsuper":
            return self._super_rows(
                result, start, self.word_batch,
                lambda ws, row: self._window_hits(
                    ws, self.word_batch, row, unit))
        return super()._decode_queued(kind, start, result, unit)


class PallasWordlistWorker(DeviceWordlistWorker):
    """Wordlist+rules worker over the in-VMEM rule-interpreter kernel
    (ops/pallas_rules.py) -- config 3's fast path.  Single target,
    exact in-kernel compare; the step keeps DeviceWordlistWorker's
    (w0, n_valid_words) -> (count, lanes, tpos) contract with
    rule-major flat lanes for ANY w0 (units need not be tile-aligned),
    so process/hit decode/rescan are inherited unchanged."""

    SUPER_MODE = "wide"

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int = 1 << 18, hit_capacity: int = 64,
                 oracle: Optional[HashEngine] = None,
                 interpret: bool = False):
        from dprf_tpu.ops.pallas_rules import TILE_W, make_rules_crack_step

        tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                  oracle)
        if self.multi:
            raise ValueError("rules kernel is single-target")
        word_batch = max(TILE_W,
                         (batch // max(1, gen.n_rules) // TILE_W)
                         * TILE_W)
        self._tgt_words = np.asarray(tgt)
        self._interpret = interpret
        self.step = make_rules_crack_step(
            engine.name, gen, self._tgt_words, word_batch,
            hit_capacity, interpret=interpret)
        self.word_batch = self.step.word_batch
        self.stride = self.word_batch * gen.n_rules

    def _make_step(self, n_words: int):
        """Rules-kernel step over an n_words window (wide dispatches:
        n_words = inner * word_batch, already a TILE_W multiple), with
        the hit buffer scaled to keep per-word capacity constant.

        All wide sizes share ONE device copy of the packed wordlist:
        a build whose window fits the current copy's padding reuses
        it; a larger one rebuilds with more padding, replaces the
        shared copy, AND evicts cached steps still closing over the
        old one -- so HBM holds at most the per-batch step's copy
        plus one wide copy, never one per cached size."""
        from dprf_tpu.ops.pallas_rules import make_rules_crack_step
        from dprf_tpu.ops.superstep import window_capacity
        cap = window_capacity(self.hit_capacity,
                              n_words // self.word_batch)
        old = getattr(self, "_wide_shared", None)
        step = make_rules_crack_step(
            self.engine.name, self.gen, self._tgt_words, n_words,
            cap, interpret=self._interpret, shared_words=old)
        if old is not None and step.words4 is not old[0]:
            # evict IN PLACE: _wide_step holds a reference to the dict
            cache = getattr(self, "_wide_cache", {})
            for k in [k for k, v in cache.items()
                      if getattr(v, "words4", None) is not step.words4]:
                del cache[k]
        self._wide_shared = (step.words4, step.lens3)
        return step


class PallasMaskWorker(MaskWorkerBase):
    """Mask worker over the hand-written Pallas kernels
    (ops/pallas_mask.py) -- the fast path where the whole
    decode->hash->compare->reduce chain stays in VMEM.

    Single target: exact in-kernel compare; tile collisions surface as
    count > hit_capacity, which reuses the exact-rescan fallback path.

    Multi target (config 2's 1k-hash list): the kernel's compare is
    the blocked-probe bitmap (ops/pallas_mask.kernel_probe_rows, sized
    by DPRF_PALLAS_PROBE_FP); a window's single-maybe lanes are
    verified here in ONE oracle call against the target digest map
    (_verify_probe_lanes), and each collided tile (>= 2 maybes,
    including any tile with two real hits) is re-probed on the device
    (_reprobe_tiles: the kernel's body over that one tile, returning
    its maybe lanes), all of which are verified in one more call.
    `verify=` counts the lanes and the calls.  The exact host rescan of a
    tile's whole TILE-candidate range remains for a re-probe that
    disagrees with the kernel or overflows TILE_LANES, and for the
    ops/pallas_ext steps (engines outside CORES), which have no
    re-probe; `verify=` on the job's `ran` line counts each.

    Bulk list (>= DPRF_TARGETS_PROBE_MIN digests, an engine in CORES):
    the probe table of dprf_tpu/targets/ lives in HBM and no tile can
    hold it, so the kernel's body ends at the digest words and a probe
    stage of the same program (ops/pallas_mask.
    make_pallas_bulk_crack_step) looks each up in the bitmap and
    verifies the survivors exactly against the sorted table: the step
    returns true hits, as a DeviceMaskWorker's does, and the table is
    an argument of every dispatch, never a constant of the program.
    `verify=` counts `survivors` (lanes the bitmap passed) and `exact`
    (hits the device confirmed).
    """

    RESCAN_CAPACITY = 16
    SUPER_MODE = "loop"

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int = 1 << 18, hit_capacity: int = 64,
                 oracle: Optional[HashEngine] = None,
                 interpret: bool = False,
                 sub: Optional[int] = None):
        from dprf_tpu.ops.pallas_mask import CORES, SUB

        tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                  oracle, probe_ok=engine.name in CORES)
        if engine.name not in CORES:
            # pallas_ext steps (nested double-hash, mysql41) have no
            # offset argument, so no loop program: they fuse wide
            self.SUPER_MODE = "wide"
        # sub: sublanes per kernel tile (the `dprf tune` tile rung);
        # default is the DPRF_PALLAS_SUB knob
        self._sub = SUB if sub is None else sub
        tile = self._sub * 128
        batch = max(tile, (batch // tile) * tile)
        self.batch = self.stride = batch
        self._tile = tile
        self._interpret = interpret
        if self.probe_table is not None:
            from dprf_tpu.targets import probe as probe_mod
            self._survivors = probe_mod.survivor_cap(self.probe_table, batch)
            self._table_args = self.probe_table.device_args()
            self.verify_counts.update(survivors=0, exact=0)
        elif self.multi:
            if oracle is None:
                raise ValueError("multi-target pallas worker needs an "
                                 "oracle engine to verify probe maybes")
            dt = "<u4" if engine.little_endian else ">u4"
            self._twords = np.stack([np.frombuffer(t.digest, dtype=dt)
                                     .astype(np.uint32)
                                     for t in self.targets])
            self._digest_map = {t.digest: i
                                for i, t in enumerate(self.targets)}
            self._setup_tile_reprobe(self._twords, self._sub)
        else:
            self._twords = np.asarray(tgt)
        self.step = self._make_step(batch)

    def _make_step(self, batch: int):
        """Kernel step at `batch` lanes; wide steps (batch a multiple
        of self.batch) scale the hit/rescan buffers so per-candidate
        capacity matches the per-batch path, capped to keep the
        reduce buffers small."""
        from dprf_tpu.ops.pallas_mask import (make_pallas_mask_crack_step,
                                              make_pallas_multi_crack_step)
        from dprf_tpu.ops.superstep import window_capacity
        scale = max(1, batch // self.batch)
        cap = window_capacity(self.hit_capacity, scale)
        if self.probe_table is not None:
            return self._with_table(self._bulk_step(batch, cap))
        if self.multi:
            rcap = max(self.RESCAN_CAPACITY,
                       min(self.RESCAN_CAPACITY * scale, 256))
            return make_pallas_multi_crack_step(
                self.engine.name, self.gen, self._twords, batch, cap,
                rcap, interpret=self._interpret, sub=self._sub)
        return make_pallas_mask_crack_step(
            self.engine.name, self.gen, self._twords, batch, cap,
            interpret=self._interpret, sub=self._sub)

    def _bulk_step(self, batch: int, cap: int, with_offset: bool = False):
        from dprf_tpu.ops.pallas_mask import make_pallas_bulk_crack_step
        return make_pallas_bulk_crack_step(
            self.engine.name, self.gen, self.probe_table.geometry, batch,
            cap, self._survivors, interpret=self._interpret,
            with_offset=with_offset, sub=self._sub)

    def _with_table(self, step):
        """The bulk step under the workers' (base digits, n_valid)
        contract: the list's table rides behind as arguments."""
        table = self._table_args

        def bound(*args):
            return step(*args, *table)

        bound.lower = lambda *args: step.lower(*args, *table)
        return bound

    def _make_loop_parts(self, inner: int):
        """Offset-aware per-batch kernel step + accumulation groups
        for the loop superstep: ONE compiled kernel invoked `inner`
        times per dispatch, with hits folding into window-relative
        device buffers.

        The step is built at the per-batch lane count but with the
        WINDOW hit capacities (wide's cap-scaling policy), so the
        in-kernel collision sentinel -- count = capacity + 1 -- lands
        past the window buffer too and the wide-path overflow redrive
        applies unchanged."""
        from dprf_tpu.ops.pallas_mask import (make_pallas_mask_crack_step,
                                              make_pallas_multi_crack_step)
        from dprf_tpu.ops.superstep import window_capacity
        cap = window_capacity(self.hit_capacity, inner)
        grid = self.batch // self._tile
        if self.probe_table is not None:
            # true hits and their table positions globalize by the
            # batch stride; the bitmap's survivors are a count alone
            return (self._bulk_step(self.batch, cap, with_offset=True),
                    ((0, 1, 2, self.batch, cap), (3, None, None, 0, 0)))
        if self.multi:
            rcap = max(self.RESCAN_CAPACITY,
                       min(self.RESCAN_CAPACITY * inner, 256))
            step = make_pallas_multi_crack_step(
                self.engine.name, self.gen, self._twords, self.batch,
                cap, rcap, interpret=self._interpret,
                with_offset=True, sub=self._sub)
            # maybe lanes globalize by the batch stride, collided
            # tiles by the per-batch grid length
            return step, ((0, 1, None, self.batch, cap),
                          (2, 3, None, grid, rcap))
        step = make_pallas_mask_crack_step(
            self.engine.name, self.gen, self._twords, self.batch, cap,
            interpret=self._interpret, with_offset=True, sub=self._sub)
        return step, ((0, 1, 2, self.batch, cap),)

    def _batch_flag(self, result):
        if not self.multi:
            return result[0]
        if self.probe_table is not None:
            # hits, and the survivors `verify=` counts: every unit of a
            # bulk list is read back
            return result[0] + result[3]
        return result[0] + result[2]   # single maybes + collided tiles

    def _batch_hits(self, bstart: int, result, unit: WorkUnit,
                    window: int = 0) -> list[Hit]:
        if not self.multi:
            return super()._batch_hits(bstart, result, unit, window)
        if self.probe_table is not None:
            import jax
            count, lanes, tpos, n_maybe = jax.device_get(result)
            if count <= lanes.shape[0]:   # else redriven, counted there
                self.verify_counts["survivors"] += int(n_maybe)
                if self.probe_table.table is not None:
                    self.verify_counts["exact"] += int(count)
            return super()._batch_hits(bstart, (count, lanes, tpos),
                                       unit, window)
        n_single, lanes, n_collided, ctiles = result
        n_single, n_collided = int(n_single), int(n_collided)
        if n_single == 0 and n_collided == 0:
            return []
        if n_single > lanes.shape[0] or n_collided > ctiles.shape[0]:
            if window > self.stride:
                return self._redrive_wide(bstart, window, unit)
            return self._rescan(bstart, unit, window)  # pathological
        # the re-probes go out first and are read back with the
        # unit's (PendingUnit.resolve_tiles): the single maybes are
        # verified while they run
        self._reprobe_tiles(
            [bstart + int(t) * self._tile for t in np.asarray(ctiles)
             if t >= 0], unit)
        # one oracle call verifies the window's probe maybes exactly
        # (and resolves their target indices); false positives drop
        lanes = np.asarray(lanes)
        return self._verify_probe_lanes(
            [bstart + lane for lane in lanes[lanes >= 0].tolist()], unit)


class DeviceCombinatorWorker(MaskWorkerBase):
    """Fused-pipeline worker for combinator / hybrid attacks: same
    (base_digits, n_valid) step contract as the mask workers (the
    combinator keyspace is a 2-digit mixed-radix system)."""

    ATTACK = "combinator"

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int = 1 << 18, hit_capacity: int = 64,
                 oracle: Optional[HashEngine] = None):
        from dprf_tpu.ops.combine import make_combinator_crack_step

        tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                  oracle, probe_ok=True)
        self.batch = self.stride = batch
        self.step = make_combinator_crack_step(
            engine, gen, tgt, batch, hit_capacity,
            widen_utf16=getattr(engine, "widen_utf16", False))


class DeviceMaskWorker(MaskWorkerBase):
    """Fused-pipeline worker for mask attacks on fast (unsalted) hashes.

    Bulk target lists (>= DPRF_TARGETS_PROBE_MIN) swap the replicated
    compare table for the probe table (dprf_tpu/targets/): the step
    builder understands a ProbeTable, so probe_ok is set here."""

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int = 1 << 18, hit_capacity: int = 64,
                 oracle: Optional[HashEngine] = None):
        from dprf_tpu.ops.pipeline import make_mask_crack_step

        tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                  oracle, probe_ok=True)
        self.batch = self.stride = batch
        self.step = make_mask_crack_step(
            engine, gen, tgt, batch, hit_capacity,
            widen_utf16=getattr(engine, "widen_utf16", False))

