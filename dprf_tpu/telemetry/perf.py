"""Performance attribution (ISSUE 9): per-phase sweep accounting,
roofline distance, and scaling-efficiency metrics.

The metrics layer says how fast the fleet sweeps and the trace layer
says which unit ran where -- but neither can say WHERE a sweep's time
goes.  This module splits the worker hot path into PHASES:

  generate   host-side candidate material (mixed-radix digits, word
             windows) for one dispatch
  h2d        host->device transfer of the step arguments
  device     the fused crack step itself (dispatch + device compute)
  d2h        device->host result fetch + hit decode
  verify     CPU-oracle re-hash of reported hits (coordinator side)

recorded two ways: ``phase`` child spans under the unit's ``sweep``
span (so Perfetto shows the breakdown per unit) and a
``dprf_phase_seconds{phase,engine,job}`` histogram (so ``/metrics``
and ``dprf report`` show fleet-wide p50/p95 per phase).

Honest phase timing needs ``block_until_ready`` boundaries between
the phases -- exactly the host syncs the retrace analyzer forbids on
the steady-state path, because they drain the device stream.  So
attribution is SAMPLED and OPT-IN: ``DPRF_PERF_SAMPLE=N`` routes one
unit in N through ``probe_pending`` -- a serial, synced sweep of that
one unit, behind an emptied pipeline -- while every other unit runs
the normal pipelined submit.  Unset (the default, 0) no unit leaves
the fused dispatch: the probe perturbs what it times (on a TPU v5e a
probed unit of 2^28 md5 candidates takes 290 ms, a fused one 54; at
N=16 that was a fifth to a third of three benchmark cells, PERF.md
PR 31), and every unit's ``submit``/``wait``/``decode`` seconds are
on the job's ``ran host=`` line and in a device trace from the
stations (telemetry/trace.py), unsampled and unsynced.  Only the
``verify`` phase is recorded without the knob.  ``probe_pending`` is
declared in the hot-path modules' ``PERF_PROBE`` tables, the retrace
analyzer's explicit exemption list for deliberately-syncing sampled
probes (a declaration, not a suppression comment).

The probed sweep produces exactly the hits the normal path would:
the phase loop is the per-batch step contract
(``MaskWorkerBase.submit`` without super/wide fusion), decoded
through the worker's own ``_batch_hits``/``_window_hits``.  Workers
with a custom serial ``process`` (per-salt blocks, per-target steps)
are probed coarsely: their whole ``process`` is one ``device`` phase,
because re-implementing their sweep here would risk wrong hits.

Also here, because bench and the live fleet must share one model:

  - the per-engine ROOFLINE: ops/candidate over the chip's 3-6e12
    int32 ops/s band -> ``roofline_band_hs(engine)`` and the
    ``dprf_roofline_frac{engine}`` gauge (EWMA-smoothed per-unit
    throughput / the band ceiling).  ISSUE 13: the op model is
    XLA-DERIVED (telemetry/programs.py analyzed flops per candidate,
    covering every engine that compiles a step); the hand table
    survives as a cross-check, with analyzed-vs-hand drift published
    as ``dprf_roofline_model_divergence{engine}``;
  - multichip scaling: ``dprf_scaling_efficiency{engine}`` and
    ``dprf_per_chip_rate_hs{engine}`` published by bench's scaling
    mode.
"""

from __future__ import annotations

import time
from typing import Optional

from dprf_tpu.telemetry import get_registry
from dprf_tpu.telemetry.trace import get_tracer, new_span_id
from dprf_tpu.utils import env as envreg

#: attribution phases, in hot-path order; the ONE declaration site for
#: the ``dprf_phase_seconds`` phase label values
PHASES = ("generate", "h2d", "device", "d2h", "verify")

#: sampling cadence knob: probe every Nth unit (0, the default: none)
SAMPLE_ENV = "DPRF_PERF_SAMPLE"

#: EWMA smoothing for the live roofline gauge (one unit's elapsed is
#: noisy; the gauge should read like a rate, not a jitter plot)
ROOFLINE_ALPHA = 0.3

#: int32 issue band (ops/s) of ONE chip, keyed by jax's device_kind.
#: A guess, not a measurement: 1024 lanes x ~1.5 GHz x 2-4 int32
#: ops/lane/cycle (the VPU issue width is unpublished); measuring the
#: peak is ROADMAP S4.  A kind that is not in this table has no
#: roofline: nothing is published for it, never another chip's band.
CHIP_INT_OPS_BANDS = {"TPU v5 lite": (3.0e12, 6.0e12)}


def local_device_kind() -> str:
    """device_kind of this process's first JAX device.  It initialises
    the backend, so only processes that run device work may call it
    (the local crack loop, bench, a worker's profile analysis) --
    never the serve coordinator, which learns each worker's kind from
    its heartbeat."""
    import jax
    return jax.devices()[0].device_kind


#: HAND roofline models (decode + pack + rounds + compare op counts)
#: -- DEMOTED to a cross-check by ISSUE 13: the live model is
#: the XLA-derived one (telemetry/programs.py: optimized-HLO flops per
#: candidate, captured at every compile site), which covers EVERY
#: engine that compiles a step.  These five hand values remain only to
#: sanity-check the analyzed numbers (divergence beyond
#: MODEL_DIVERGENCE_MAX publishes dprf_roofline_model_divergence) and
#: as the fallback when analysis never ran in this process.
OPS_PER_CANDIDATE = {
    "md5": 800,        # 64 rounds ~10 ops + decode/pack/compare
    "ntlm": 600,       # MD4: 48 rounds (+ utf16 widen in pack)
    "md4": 600,
    "sha1": 1000,      # 80 rounds
    "sha256": 2000,    # 64 heavier rounds
    "sha3-256": 10200,  # 24 rounds x ~426 uint32 ops (keccak model)
}


def sample_every() -> int:
    """The probe cadence: every Nth unit runs the synced phase sweep;
    0 (the knob's declared default, utils/env.py: the one default)
    means no unit does."""
    return max(0, envreg.get_int(SAMPLE_ENV))


def phase_histogram(registry=None):
    """``dprf_phase_seconds`` -- the ONE declaration site (the metrics
    analyzer enforces single-site declarations)."""
    return get_registry(registry).histogram(
        "dprf_phase_seconds",
        "seconds per attribution phase of a sampled sweep "
        "(generate/h2d/device/d2h from probed units; verify from "
        "every hit verification)",
        labelnames=("phase", "engine", "job"))


def worker_engine(worker) -> str:
    return getattr(getattr(worker, "engine", None), "name", "unknown")


class PerfSampler:
    """Per-loop sampling state + the publication surface the probed
    sweep records into.  One per run loop (local Coordinator /
    remote worker_loop); ``take()`` answers "is THIS unit the sampled
    one" on the configured cadence (unit 1, N+1, 2N+1, ...; never,
    at the default cadence of 0)."""

    __slots__ = ("every", "hist", "tracer", "_n")

    def __init__(self, registry=None, recorder=None,
                 every: Optional[int] = None):
        self.every = sample_every() if every is None else max(0, every)
        self.hist = phase_histogram(registry)
        self.tracer = get_tracer(recorder)
        self._n = 0

    def take(self) -> bool:
        if self.every <= 0:
            return False
        self._n += 1
        return (self._n - 1) % self.every == 0

    def observe_verify(self, seconds: float, engine: str = "unknown",
                       job: str = "j0") -> None:
        """The verify phase is real work on every hit batch (no forced
        sync needed), so it is recorded unsampled."""
        self.hist.observe(seconds, phase="verify", engine=engine,
                          job=str(job))


class _ProbedUnit:
    """Resolved result of a probed sweep: quacks like PendingUnit
    (``resolve()``), carries the phase breakdown and the spans a
    remote worker ships with its complete report.  ``sweep_span`` is
    the pre-allocated span id the caller must record the unit's sweep
    span under, so the phase spans parent onto it.

    ``cands``/``batches`` (ISSUE 19 satellite): how many candidates
    the probed sweep covered, over how many dispatches.  A fused
    (loop-superstep / coarse) probe books its whole window as ONE
    ``device`` sample while the per-batch probe books one unit of many
    small dispatches -- so raw phase seconds are not comparable across
    ``--impl`` variants.  The counts ride the phase spans and let
    `dprf report` normalize to per-candidate phase cost."""

    __slots__ = ("hits", "phases", "phase_spans", "sweep_span",
                 "cands", "batches")

    def __init__(self, hits, phases, phase_spans, sweep_span,
                 cands=0, batches=0):
        self.hits = hits
        self.phases = phases
        self.phase_spans = phase_spans
        self.sweep_span = sweep_span
        self.cands = cands
        self.batches = batches

    def resolve(self):
        return self.hits


def drain_backlog(queue) -> None:
    """Block until every already-queued pipeline entry's device work
    is done (its accumulated unit flag is ready), WITHOUT resolving
    anything -- called right before a sampled probe so the probe's
    first sync boundary attributes its own unit's work, not the
    stream backlog the pipeline deliberately keeps full.  Entries
    without a flag (serial workers' already-resolved units) need no
    drain."""
    for entry in queue:
        flag = getattr(entry[1], "flag", None)
        if flag is not None:
            _block(flag)


def _block(x) -> None:
    try:
        import jax
        jax.block_until_ready(x)
    except (ImportError, AttributeError, TypeError):
        bur = getattr(x, "block_until_ready", None)
        if bur is not None:
            bur()


def _probe_strategy(worker) -> str:
    """Which instrumented sweep is SAFE for this worker.  Only the two
    standard submit loops are re-implemented here; any class with its
    own ``process`` (per-salt blocks, per-target steps, CPU oracle)
    keeps its override and is probed coarsely."""
    from dprf_tpu.parallel import worker as pw
    from dprf_tpu.runtime import worker as rw
    proc = getattr(type(worker), "process", None)
    if proc is rw.DeviceWordlistWorker.process:
        return "wordlist"
    if proc is rw.MaskWorkerBase.process:
        return "digit"
    if proc is pw.ShardedMaskWorker.process:
        # same per-batch (base_digits, n_valid) contract + _batch_hits
        # decode; probing it per stride makes the sharded path's ~zero
        # h2d visible in the phase report
        return "digit"
    return "coarse"


def _probe_digit(worker, unit) -> tuple:
    """Per-batch (base_digits, n_valid) contract with forced sync
    boundaries between phases -- MaskWorkerBase.submit minus the
    super/wide fusion, decoded through the worker's own _batch_hits
    so a probed unit yields exactly the production hits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    t = {"generate": 0.0, "h2d": 0.0, "device": 0.0, "d2h": 0.0}
    hits: list = []
    batches = 0
    perf = time.perf_counter
    tracer = get_tracer()
    for bstart in range(unit.start, unit.end, worker.stride):
        n_valid = min(worker.stride, unit.end - bstart)
        t0 = perf()
        digits = np.asarray(worker.gen.digits(bstart), dtype=np.int32)
        t1 = perf()
        t["generate"] += t1 - t0
        base = jax.device_put(digits)
        _block(base)
        nv = jnp.int32(n_valid)
        _block(nv)
        t2 = perf()
        t["h2d"] += t2 - t1
        result = worker.step(base, nv)
        _block(result)
        t3 = perf()
        t["device"] += t3 - t2
        with tracer.station("decode", unit=unit.unit_id):
            hits.extend(worker._batch_hits(bstart, result, unit))
        t["d2h"] += perf() - t3
        batches += 1
    return t, hits, unit.length, batches


def _probe_wordlist(worker, unit) -> tuple:
    """Word-window contract ((w0, n_valid_words) scalars): candidate
    generation happens ON DEVICE via the rule interpreter, so the
    generate phase is folded into ``device`` and h2d is the scalar
    argument transfer."""
    import jax.numpy as jnp

    from dprf_tpu.runtime.worker import word_cover_range
    t = {"generate": 0.0, "h2d": 0.0, "device": 0.0, "d2h": 0.0}
    hits: list = []
    batches = 0
    perf = time.perf_counter
    tracer = get_tracer()
    w_start, w_end = word_cover_range(unit, worker.gen.n_rules)
    w_end = min(w_end, worker.gen.n_words)
    ws = w_start
    while ws < w_end:
        nw = min(worker.word_batch, w_end - ws)
        t0 = perf()
        w0 = jnp.int32(ws)
        nv = jnp.int32(nw)
        _block((w0, nv))
        t1 = perf()
        t["h2d"] += t1 - t0
        result = worker.step(w0, nv)
        _block(result)
        t2 = perf()
        t["device"] += t2 - t1
        with tracer.station("decode", unit=unit.unit_id):
            hits.extend(worker._window_hits(ws, nw, result, unit))
        t["d2h"] += perf() - t2
        ws += nw
        batches += 1
    # the sweep covers whole word windows; out-of-unit hits are
    # filtered, but the device DID hash the covering lanes
    return t, hits, (w_end - w_start) * worker.gen.n_rules, batches


def _probe_coarse(worker, unit) -> tuple:
    """Fallback for workers with their own serial ``process``: one
    honest total under ``device`` beats a wrong re-implementation of
    a per-salt sweep.  A fused (loop-superstep) process books the
    WHOLE unit as one device sample, so the candidate count riding
    the probe is what keeps its phase cost comparable to the
    per-batch probes (per-candidate normalization in `dprf
    report`)."""
    t0 = time.perf_counter()
    hits = worker.process(unit)
    return {"device": time.perf_counter() - t0}, hits, unit.length, 1


def probe_phases(worker, unit) -> dict:
    """Phase breakdown of one synced sweep, no publication -- the
    bench-side entry (``dprf bench`` reports it as ``phases``)."""
    strategy = _probe_strategy(worker)
    if strategy == "wordlist":
        phases, _, _, _ = _probe_wordlist(worker, unit)
    elif strategy == "digit":
        phases, _, _, _ = _probe_digit(worker, unit)
    else:
        phases, _, _, _ = _probe_coarse(worker, unit)
    return phases


def probe_pending(worker, unit, sampler: PerfSampler,
                  trace: Optional[str] = None) -> _ProbedUnit:
    """The SAMPLED unit's sweep: serial, with block_until_ready
    boundaries between phases (this is the helper the hot-path
    modules declare in ``PERF_PROBE`` -- the syncs are the point).
    Records one ``phase`` span per phase (parented on the
    pre-allocated sweep span id the caller records the sweep under)
    plus the phase histogram, and returns a resolved PendingUnit
    stand-in carrying the spans for RPC shipping."""
    strategy = _probe_strategy(worker)
    if strategy == "wordlist":
        phases, hits, cands, batches = _probe_wordlist(worker, unit)
    elif strategy == "digit":
        phases, hits, cands, batches = _probe_digit(worker, unit)
    else:
        phases, hits, cands, batches = _probe_coarse(worker, unit)
    # what ran (runtime.worker.describe_worker): a probed unit's
    # dispatches are per-batch and synced, not the production shape
    from dprf_tpu.runtime.worker import count_dispatches
    count_dispatches(getattr(worker, "_worker", worker), "probe",
                     batches)
    sweep_span = new_span_id()
    engine = worker_engine(worker)
    job = getattr(unit, "job_id", "j0")
    spans = []
    ts = time.time() - sum(phases.values())
    for phase in PHASES:
        dur = phases.get(phase)
        if dur is None:
            continue
        sampler.hist.observe(dur, phase=phase, engine=engine,
                             job=str(job))
        # cands/batches ride every phase span (ISSUE 19 satellite):
        # `dprf report` divides phase seconds by candidates probed, so
        # a coarse fused probe (whole window = ONE device sample) and
        # the per-batch probes stay comparable across --impl variants
        ev = sampler.tracer.record(
            "phase", dur=dur, ts=ts, trace=trace, parent=sweep_span,
            phase=phase, unit=unit.unit_id, job=job, engine=engine,
            cands=cands, batches=batches)
        ts += dur
        if ev is not None:
            spans.append(ev)
    return _ProbedUnit(hits, phases, spans, sweep_span,
                       cands=cands, batches=batches)


# ---------------------------------------------------------------------------
# roofline model (shared by bench and the live fleet)

#: analyzed-vs-hand ratio beyond which the cross-check alarms (the
#: dprf_roofline_model_divergence gauge carries the ratio either way;
#: this is the level the README documents as "one of the models is
#: wrong")
MODEL_DIVERGENCE_MAX = 2.0


def _divergence_gauge(registry=None):
    return get_registry(registry).gauge(
        "dprf_roofline_model_divergence",
        "max(analyzed, hand) / min(analyzed, hand) ops-per-candidate "
        "ratio between the XLA-derived roofline model and the hand "
        "table (cross-check engines only; > 2 means one model is "
        "wrong)", labelnames=("engine",))


#: profiler-measured device seconds per candidate, per engine -- the
#: LAST resort of the roofline model chain.  Programs whose optimized
#: HLO reports no flop count (gather/bitwise-only pipelines like the
#: probe-table step) never produce an analyzed value, and new kernels
#: have no hand entry; a measured capture window still lets them
#: publish dprf_roofline_frac instead of dropping off the plane.
_MEASURED_SPC: dict = {}


def record_measured_cost(engine: str, seconds_per_candidate: float,
                         registry=None) -> None:
    """Record a profiler-measured device-seconds/candidate observation
    (telemetry/profiler.py's trace analysis calls this for every
    engine a capture window attributed device time to).  Published as
    a gauge so the fallback model is inspectable on /metrics."""
    if not seconds_per_candidate or seconds_per_candidate <= 0:
        return
    _MEASURED_SPC[engine] = float(seconds_per_candidate)
    get_registry(registry).gauge(
        "dprf_measured_spc",
        "profiler-measured device seconds per candidate (roofline "
        "fallback model for programs with no analyzed flop count and "
        "no hand entry)", labelnames=("engine",)).set(
            seconds_per_candidate, engine=engine)


def ops_per_candidate(engine: str, registry=None) -> Optional[float]:
    """The engine's roofline op model: the XLA-DERIVED value
    (telemetry/programs.py: optimized flops / candidates per dispatch)
    when a compiled program was analyzed in this process, else the
    hand table.  When analyzed AND hand exist the divergence ratio is
    published so a drifted hand model (or a mis-captured program)
    surfaces on /metrics instead of silently skewing every roofline
    fraction.  None when the engine compiled nothing here and has no
    hand entry (roofline_band_hs then falls back to a
    profiler-measured device-s/candidate, ``record_measured_cost``)."""
    from dprf_tpu.telemetry import programs as programs_mod
    analyzed = programs_mod.analyzed_ops_per_candidate(engine)
    hand = OPS_PER_CANDIDATE.get(engine)
    if analyzed and hand:
        ratio = max(analyzed, hand) / min(analyzed, hand)
        _divergence_gauge(registry).set(ratio, engine=engine)
    return analyzed or hand


def roofline_band_hs(engine: str,
                     device_kind: Optional[str]) -> Optional[tuple]:
    """(lo, hi) H/s ceiling band for an engine on one chip of
    ``device_kind``, or None when the kind is not in
    CHIP_INT_OPS_BANDS or the engine has no cost model.  The analyzed
    model wins (see ops_per_candidate); md5's 4-8 GH/s hand band
    applies only on the hand-model fallback, so the committed
    trajectory stays readable next to the derived one.  An engine
    with neither gets the measured-cost band: its profiler-measured
    device time per candidate IS the ceiling's reciprocal (the
    fraction of the measured rate itself is <= 1 by construction)."""
    chip = CHIP_INT_OPS_BANDS.get(device_kind)
    if chip is None:
        return None
    lo, hi = chip
    ops = ops_per_candidate(engine)
    if not ops:
        spc = _MEASURED_SPC.get(engine)
        return (lo / hi / spc, 1.0 / spc) if spc else None
    from dprf_tpu.telemetry import programs as programs_mod
    if engine == "md5" and not \
            programs_mod.analyzed_ops_per_candidate(engine):
        return (4.0e9, 8.0e9)
    return (lo / ops, hi / ops)


def roofline_fraction(engine: str, rate_hs: float,
                      device_kind: Optional[str]) -> Optional[float]:
    """Conservative fraction of the roofline band (vs the HI ceiling);
    None when the chip kind or the engine has no model or the rate is
    not positive."""
    band = roofline_band_hs(engine, device_kind)
    if band is None or not rate_hs or rate_hs <= 0:
        return None
    return rate_hs / band[1]


def analyzed_roofline_fraction(engine: str, rate_hs: float,
                               device_kind: Optional[str]
                               ) -> Optional[float]:
    """Roofline fraction from the XLA-DERIVED model ALONE (no hand
    fallback): what bench reports as ``analyzed_roofline`` so the
    trajectory can tell a compiler-derived fraction from a hand-table
    one.  None when no program of this engine was analyzed here, or
    the chip kind has no band."""
    from dprf_tpu.telemetry import programs as programs_mod
    chip = CHIP_INT_OPS_BANDS.get(device_kind)
    ops = programs_mod.analyzed_ops_per_candidate(engine)
    if chip is None or not ops or not rate_hs or rate_hs <= 0:
        return None
    return rate_hs / (chip[1] / ops)


def _roofline_gauge(registry=None):
    return get_registry(registry).gauge(
        "dprf_roofline_frac",
        "EWMA-smoothed fraction of the per-engine int32 roofline "
        "ceiling the observed throughput reaches (conservative: vs "
        "the band's upper bound)", labelnames=("engine",))


def publish_roofline(engine: str, rate_hs: float,
                     device_kind: Optional[str],
                     registry=None) -> Optional[float]:
    """Fold one throughput observation of a ``device_kind`` chip into
    the live roofline gauge (EWMA against the gauge's current value,
    so per-unit jitter reads as a rate).  Returns the smoothed
    fraction, or None -- and publishes nothing -- when the kind has
    no band or the engine no op model."""
    frac = roofline_fraction(engine, rate_hs, device_kind)
    if frac is None:
        return None
    g = _roofline_gauge(registry)
    cur = g.value(engine=engine)
    smoothed = frac if cur == 0 else cur + ROOFLINE_ALPHA * (frac - cur)
    g.set(smoothed, engine=engine)
    return smoothed


def roofline_snapshot(registry=None) -> dict:
    """{engine: smoothed fraction} from the live gauge (the ``dprf
    top`` header and op_trace_tail status read this)."""
    m = get_registry(registry).get("dprf_roofline_frac")
    if m is None:
        return {}
    return {v["labels"].get("engine", "?"): v["value"]
            for v in m.snapshot_values() if v["value"] > 0}


def publish_scaling(engine: str, per_chip_hs: float, efficiency: float,
                    n_devices: int, device_kind: Optional[str],
                    registry=None) -> None:
    """Multichip bench publication: per-chip H/s and the 1->N scaling
    efficiency, next to the roofline gauge -- ONE declaration site for
    both gauges."""
    m = get_registry(registry)
    m.gauge("dprf_per_chip_rate_hs",
            "per-chip throughput of the last multichip scaling bench",
            labelnames=("engine",)).set(per_chip_hs, engine=engine)
    m.gauge("dprf_scaling_efficiency",
            "rate_N / (N * rate_1) of the last multichip scaling "
            "bench", labelnames=("engine",)).set(efficiency,
                                                 engine=engine)
    publish_roofline(engine, per_chip_hs, device_kind,
                     registry=registry)
