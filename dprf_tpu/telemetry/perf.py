"""Performance attribution (ISSUE 9): the verify phase's histogram,
roofline distance, and scaling-efficiency metrics.

Where a unit's time goes is the stations' to say
(telemetry/trace.py ``STATIONS``): every unit's ``submit`` / ``wait`` /
``decode`` / ``verify`` / ``complete`` seconds are on the job's ``ran
host=`` line and, while a device trace runs, events of the host's
plane beside the device's programs -- unsampled and unsynced.  What
stays here of the per-phase accounting is
``dprf_phase_seconds{phase="verify",engine,job}``: the CPU oracle's
re-hash of a unit's reported hits, observed by both coordinators on
every hit batch.

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

from typing import Optional

from dprf_tpu.telemetry import get_registry

#: the ONE declaration site for the ``dprf_phase_seconds`` phase label
#: values
PHASES = ("verify",)

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


def phase_histogram(registry=None):
    """``dprf_phase_seconds`` -- the ONE declaration site (the metrics
    analyzer enforces single-site declarations)."""
    return get_registry(registry).histogram(
        "dprf_phase_seconds",
        "seconds per attribution phase of a unit (verify: the CPU "
        "oracle's re-hash of a unit's reported hits, from every hit "
        "verification)",
        labelnames=("phase", "engine", "job"))


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
