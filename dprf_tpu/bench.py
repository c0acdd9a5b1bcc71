"""Benchmark mode: candidates/sec through the fused crack pipeline.

Measures the exact production path (decode -> pack -> digest -> compare
-> compact) with an unmatchable target, so the number is what a real
job sustains, not a stripped-down kernel.
"""

from __future__ import annotations

import time
from typing import Optional

import jax

from dprf_tpu import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.ops.pipeline import make_mask_crack_step, target_words


def _publish(result: dict, mode: str) -> dict:
    """Every bench run reports through the SAME registry the runtime
    publishes into (ISSUE 1): a scrape or telemetry snapshot taken
    during/after a bench shows what was measured, at what rate, with
    how much compile time -- machine-checkable, not stdout-only.
    Compile metrics are NOT re-observed here: the compile site itself
    publishes (compile_observer in run_bench / worker warmup), and a
    second observation would double every dprf_compile_seconds count
    and hit/miss counter a report like tools/compile_report.py sums."""
    from dprf_tpu.telemetry import DEFAULT as metrics
    from dprf_tpu.telemetry import perf as perf_mod
    labels = dict(engine=result.get("engine", "?"),
                  impl=result.get("impl", mode),
                  device=result.get("device", "?"), mode=mode)
    metrics.gauge("dprf_bench_rate_hs",
                  "last measured bench rate (or efficiency fraction "
                  "for mode=scaling)",
                  labelnames=("engine", "impl", "device", "mode")
                  ).set(result["value"], **labels)
    metrics.counter("dprf_bench_runs_total", "bench invocations",
                    labelnames=("mode",)).inc(mode=mode)
    if mode == "scaling":
        # multichip accounting: per-chip H/s + scaling efficiency
        # next to the roofline gauge (ISSUE 9)
        perf_mod.publish_scaling(result.get("engine", "?"),
                                 float(result.get("per_chip") or 0.0),
                                 float(result["value"]),
                                 int(result.get("n_devices") or 1),
                                 perf_mod.local_device_kind(),
                                 registry=metrics)
    elif result.get("device") not in (None, "cpu"):
        # roofline distance exists only for a chip kind with a band
        # (telemetry/perf.CHIP_INT_OPS_BANDS); the JSON carries the
        # raw fraction, the gauge the smoothed one
        kind = perf_mod.local_device_kind()
        frac = perf_mod.roofline_fraction(result.get("engine", "?"),
                                          result["value"], kind)
        if frac is not None:
            result.setdefault("roofline_frac", round(frac, 4))
            perf_mod.publish_roofline(result["engine"],
                                      result["value"], kind,
                                      registry=metrics)
    return result


def _compile_fields(cache: str, seconds: float, warm_s=None) -> dict:
    """The machine-checkable compile-cost fields every bench result
    carries (ISSUE 3): the classification, the cold-compile cost when
    THIS run paid it, and the warm (cache-served) cost when measured.
    A hit run cannot know its cold cost, so compile_cold_s is None
    there rather than a made-up number.  ONE derivation site: both
    bench modes' JSON must keep the same field contract."""
    out = {"compile_cache": cache,
           "compile_cold_s": (round(seconds, 3)
                              if cache in ("miss", "off") else None),
           "compile_warm_s": (round(seconds, 3)
                              if cache == "hit" else None)}
    if warm_s is not None:
        out["compile_warm_s"] = round(warm_s, 3)
    return out


def _introspection_fields(engine: str, rate: float) -> dict:
    """Device-introspection fields every bench result carries (ISSUE
    13): the run's peak device-memory footprint -- the allocator's
    measured high-water mark where the backend has one, else the
    largest analyzed program footprint, tagged by ``peak_hbm_source``
    -- and the roofline fraction from the XLA-derived op model alone.
    The regression sentinel gates ``peak_hbm_bytes`` alongside
    throughput (perfreport/compare.py); records measured before ISSUE
    13 lack the field and gate as no-baseline, never as a crash."""
    from dprf_tpu.telemetry import devstats
    from dprf_tpu.telemetry import perf as perf_mod
    from dprf_tpu.telemetry import programs as programs_mod
    programs_mod.analyze_pending()    # outside every timed window
    devstats.poll()
    peak, source = devstats.peak_hbm_bytes()
    frac = perf_mod.analyzed_roofline_fraction(
        engine, rate, perf_mod.local_device_kind())
    if frac is None and rate > 0 \
            and perf_mod.ops_per_candidate(engine) is None:
        # roofline-fallback seeding: engines whose optimized HLO
        # reports no flop count (gather/bitwise-only pipelines) and
        # have no hand entry would otherwise publish NO roofline at
        # all -- seed the measured-cost model from this bench's own
        # steady-state rate so the live fleet gets a dprf_roofline_frac
        # gauge (a later profiler capture window overwrites it with a
        # device-attributed measurement)
        perf_mod.record_measured_cost(engine, 1.0 / rate)
    return {"peak_hbm_bytes": peak,
            "peak_hbm_source": source,
            "analyzed_roofline": round(frac, 4) if frac else None}


def _tuned_or(batch, engine: str, device: str, fallback: int,
              attack: str = "mask", extras=None) -> tuple:
    """Bench-side ``--batch auto``: (resolved batch, tuned flag).  An
    explicit integer is pinned; "auto"/None warm-starts from the tuning
    cache written by ``dprf tune`` (environment-validated -- a stale
    entry reads as a miss) and otherwise uses `fallback`.  Every bench
    result carries the flag, so a reported rate is attributable to a
    tuned or a default batch -- machine-checkable, like `fresh`.
    extras: key dimensions beyond (engine, device, attack) -- see
    tune.lookup_tuned_batch."""
    if batch not in (None, "auto"):
        return int(batch), False
    from dprf_tpu.tune import lookup_tuned_batch
    b = lookup_tuned_batch(engine, attack=attack, device=device,
                           extras=extras)
    if b:
        return b, True
    return fallback, False


def calibrated_inner(probe_rate: float, batch: int,
                     target_s: float = 5.0, cap: int = 1 << 20) -> int:
    """Inner-loop length so one dispatch computes ~target_s of work.
    The cap only guards against a nonsense probe; fori_loop length does
    not affect compile time (the loop is not unrolled)."""
    want = max(1, int(probe_rate * target_s / batch))
    return min(cap, 1 << (want.bit_length() - 1))


def make_looped_step(step, inner: int):
    """Wrap a (base_digits, n_valid) crack step in a device-side
    fori_loop of `inner` iterations, returning only two accumulated
    scalars.  One host dispatch then covers inner*batch candidates, so
    the measurement is of the device and not of the per-dispatch host
    overhead.  The base
    digits are perturbed per iteration (the decoders renormalize any
    digit overflow) and both step outputs feed the carry, so XLA can
    neither hoist the body out of the loop nor dead-code the hit
    compaction."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run(base, nv):
        def body(i, carry):
            c, l = carry
            out = step(base.at[-1].add(i), nv)
            return c + out[0].astype(jnp.int32), \
                l + out[1].sum().astype(jnp.int32)
        return lax.fori_loop(0, inner, body,
                             (jnp.int32(0), jnp.int32(0)))

    return run


def _build_mask_step(engine: str, eng, gen, impl: str, batch: int,
                     fake: bytes) -> tuple:
    """Step selection for run_bench (the same selection a real job
    makes); returns (step, use_pallas, tile-aligned batch).  Factored
    out so a second same-shape build can measure the warm
    (cache-served) compile cost."""
    use_pallas = False
    step = None
    rate = getattr(eng, "_rate", None)
    if rate is not None:
        # keccak family: its own sponge steps (the generic MD
        # pipeline's framing does not apply)
        import numpy as np

        from dprf_tpu.engines.device.sha3 import make_keccak_mask_step
        from dprf_tpu.ops.pallas_keccak import (
            SUBK, keccak_kernel_eligible, make_pallas_keccak_crack_step)
        tw = np.frombuffer(fake, ">u4").astype(np.uint32)
        from dprf_tpu.ops.pallas_mask import pallas_mode
        # auto honors the DPRF_PALLAS kill-switch via pallas_mode()
        kernel_on = (impl == "pallas" or pallas_mode() is not None)
        if (impl != "xla" and kernel_on
                and keccak_kernel_eligible(gen, 1, rate)):
            tile = SUBK * 128
            batch = max(tile, (batch // tile) * tile)
            step = make_pallas_keccak_crack_step(
                gen, tw, batch, eng._pad_byte, rate,
                eng.digest_size)
            use_pallas = True
        elif impl == "pallas":
            raise ValueError(
                "--impl pallas: keccak kernel not eligible -- it "
                "requires a real TPU backend, a mask the "
                "arithmetic charset decode supports, and a "
                f"candidate <= {rate - 1} bytes (rate {rate})")
        else:
            step = make_keccak_mask_step(
                gen, tw, batch, eng._pad_byte, rate=rate,
                out_bytes=eng.digest_size)
    elif impl != "xla":
        from dprf_tpu.ops import pallas_mask
        eligible = pallas_mask.kernel_eligible(engine, gen, 1)
        if impl == "pallas" and not eligible:
            raise ValueError(
                "--impl pallas requires a kernel-capable engine "
                f"({', '.join(sorted(pallas_mask.CORES))}) and a mask "
                "the arithmetic charset decode supports")
        mode = ({"interpret": jax.default_backend() != "tpu"}
                if impl == "pallas" else pallas_mask.pallas_mode())
        if eligible and mode is not None:
            batch = max(pallas_mask.TILE,
                        (batch // pallas_mask.TILE) * pallas_mask.TILE)
            import numpy as np
            dt = "<u4" if eng.little_endian else ">u4"
            step = pallas_mask.make_pallas_mask_crack_step(
                engine, gen,
                np.frombuffer(fake, dtype=dt).astype(np.uint32),
                batch, **mode)
            use_pallas = True
    if step is None:
        step = make_mask_crack_step(
            eng, gen, target_words(fake, eng.little_endian), batch,
            widen_utf16=getattr(eng, "widen_utf16", False))
    return step, use_pallas, batch


def _round_phases(phases: dict) -> dict:
    return {k: round(v, 6) for k, v in phases.items()}


def _step_phases(gen, step, batch: int) -> dict:
    """Per-phase breakdown of ONE per-batch step dispatch with forced
    sync boundaries: generate / h2d / device / d2h.  One
    dispatch outside the timed window -- the syncs that make the
    attribution honest must never touch the measured loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    t = {}
    t0 = time.perf_counter()
    digits = np.asarray(gen.digits(0), dtype=np.int32)
    t1 = time.perf_counter()
    t["generate"] = t1 - t0
    base = jax.device_put(digits)
    nv = jnp.int32(batch)
    jax.block_until_ready((base, nv))
    t2 = time.perf_counter()
    t["h2d"] = t2 - t1
    out = step(base, nv)
    jax.block_until_ready(out)
    t3 = time.perf_counter()
    t["device"] = t3 - t2
    if isinstance(out, (tuple, list)):
        for x in out:
            np.asarray(x)
    else:
        np.asarray(out)
    t["d2h"] = time.perf_counter() - t3
    return _round_phases(t)


def _timed_aot_compile(fn, *args):
    """Seconds to lower+compile `fn` at these args WITHOUT dispatching
    (None when the step cannot AOT-lower).  With the persistent cache
    populated by the run that just measured, this is the warm compile
    cost a same-shape job pays."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    t0 = time.perf_counter()
    lower(*args).compile()
    return time.perf_counter() - t0


def run_bench(engine: str = "md5", device: str = "jax",
              mask: str = "?a?a?a?a?a?a?a?a", batch="auto",
              seconds: float = 5.0, impl: str = "auto",
              inner: int = 1, log=None) -> dict:
    """impl: "xla" forces the generic fused pipeline, "pallas" forces
    the hand-written kernel (MD5 only), "auto" = pallas on TPU when
    eligible -- the same selection a real job makes.

    batch: an int pins the batch; "auto" (default) consumes the tuning
    cache (`dprf tune`) and falls back to 1<<20.  The result reports
    `tuned` accordingly.

    inner > 1 loops the step on device (see make_looped_step) and
    measures the chip with the per-dispatch host overhead amortized;
    inner = 1 measures the per-dispatch production path."""
    batch, tuned = _tuned_or(batch, engine, device, 1 << 20,
                             extras={"hit_cap": 64})
    gen = MaskGenerator(mask)
    # CPU-oracle path has no jit at all; the jax path overwrites
    compile_fields: dict = {"compile_cache": "off",
                            "compile_cold_s": None,
                            "compile_warm_s": None}
    # An all-0xFF digest can't be produced by these hash functions'
    # outputs for in-keyspace candidates (and a false hit would only add
    # one buffer readback anyway).
    if device == "jax":
        from dprf_tpu import compilecache
        compilecache.enable(log=log)
        eng = get_engine(engine, device="jax")
        fake = bytes([0xFF]) * eng.digest_size
        step, use_pallas, batch = _build_mask_step(engine, eng, gen,
                                                   impl, batch, fake)
        import jax.numpy as jnp

        fn = make_looped_step(step, inner) if inner > 1 else step

        def run_batch(i):
            base = jnp.asarray(gen.digits((i * batch) % max(
                gen.keyspace - batch, 1)), dtype=jnp.int32)
            return fn(base, jnp.int32(batch))

        from dprf_tpu.compilecache import compile_observer
        from dprf_tpu.utils.sync import hard_sync

        # Warmup / compile -- observed, classified hit/miss/off against
        # the persistent compilation cache.  Argument materialization
        # happens before the observer opens (it can write tiny cache
        # entries of its own).
        base0 = jnp.asarray(gen.digits(0), dtype=jnp.int32)
        t0 = time.perf_counter()
        with compile_observer(engine) as obs:
            hard_sync(fn(base0, jnp.int32(batch)))
        compile_s = time.perf_counter() - t0
        # Warm cost: a second same-shape build now loads the cached
        # executable; AOT (no dispatch), so the field is pure compile.
        warm_s = None
        if compilecache.enabled():
            step2, _, _ = _build_mask_step(engine, eng, gen, impl,
                                           batch, fake)
            fn2 = make_looped_step(step2, inner) if inner > 1 else step2
            warm_s = _timed_aot_compile(fn2, base0, jnp.int32(batch))
        compile_fields = _compile_fields(obs.cache, obs.seconds, warm_s)
        # program-registry capture (ISSUE 13): bench compiles outside
        # the worker factories, so it registers its step itself;
        # analysis runs in _introspection_fields after the timed loop
        from dprf_tpu.telemetry import programs as programs_mod
        programs_mod.register_program(engine, "mask", batch, step=step,
                                      args=(base0, jnp.int32(batch)))
        # per-phase attribution of one production dispatch (outside
        # the timed window; the step is already compiled)
        phases = _step_phases(gen, step, batch)
        if log:
            log.info("bench compiled", seconds=f"{compile_s:.1f}",
                     cache=obs.cache)
        # Timed with BOUNDED queue depth, synced by hard_sync (a value
        # read back to the host -- see utils/sync.py) so the wall-time
        # window reflects sustained throughput rather than enqueue
        # speed: an unbounded async queue would count dispatches that
        # have not run yet.
        n, t0 = 0, time.perf_counter()
        depth = 1 if inner > 1 else 8
        while time.perf_counter() - t0 < seconds:
            last = None
            for _ in range(depth):
                last = run_batch(n)
                n += 1
            hard_sync(last)
        elapsed = time.perf_counter() - t0
    else:
        eng = get_engine(engine, device="cpu")
        n, elapsed = 0, 0.0
        chunk = min(batch, 1 << 14)
        # coarse phase split for the oracle path: generation vs
        # hashing of one chunk (no device, so no h2d/d2h)
        tp = time.perf_counter()
        cands = [c for c in gen.candidates(0, chunk) if c is not None]
        tg = time.perf_counter()
        eng.hash_batch(cands)
        phases = _round_phases({"generate": tg - tp,
                                "device": time.perf_counter() - tg})
        # fresh candidates per iteration: a real job pays generation too,
        # and re-hashing one hot-cached chunk would inflate the number
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            start = (n * chunk) % max(gen.keyspace - chunk, 1)
            eng.hash_batch(gen.candidates(start, chunk))
            n += 1
        elapsed = time.perf_counter() - t0
        batch = chunk
        compile_s = 0.0
        use_pallas = False

    rate = n * batch * max(1, inner if device == "jax" else 1) / elapsed
    platform = jax.devices()[0].platform if device == "jax" else "cpu"
    return _publish({
        "metric": f"{engine} candidates/sec/chip",
        "value": rate,
        "unit": "H/s",
        "engine": engine,
        "impl": "pallas" if use_pallas else "xla",
        "device": platform,
        "mask": mask,
        "batch": batch,
        "tuned": tuned,
        "batches": n,
        "inner": inner,
        "elapsed_s": round(elapsed, 3),
        "compile_s": round(compile_s, 1),
        "phases": phases,
        **compile_fields,
        **_introspection_fields(engine, rate),
    }, mode="bench")


def run_targets_sweep(engine: str = "md5", mask: str = "?a?a?a?a?a?a",
                      sizes=(1_000, 10_000, 100_000, 1_000_000),
                      batch="auto", seconds: float = 3.0,
                      log=None) -> dict:
    """Target-set-size sweep through the probe-table step (ISSUE 16):
    the per-candidate cost of cracking against N digests must stay
    FLAT as N grows 10^3 -> 10^6 (10^7-ready on real silicon -- the
    sizes knob; the CPU backend caps at 10^6 to keep CI honest).

    Each size builds its device-resident probe table (blocked Bloom +
    sorted exact-verify buckets, dprf_tpu/targets/probe.py) from
    synthetic unmatchable digests and times the SAME fused mask step
    a real bulk job dispatches.  ``value`` is the H/s at the LARGEST
    size, so the gated trajectory number dips if the table ever stops
    being O(1) per candidate; ``flat_ratio`` (cost at max N / cost at
    min N) is the direct flatness assertion CI checks against 1.3x.
    """
    import jax.numpy as jnp
    import numpy as np

    from dprf_tpu import compilecache
    from dprf_tpu.compilecache import compile_observer
    from dprf_tpu.targets import build_probe_table
    from dprf_tpu.telemetry import programs as programs_mod
    from dprf_tpu.utils.sync import hard_sync

    batch, tuned = _tuned_or(batch, engine, "jax", 1 << 18,
                             extras={"hit_cap": 64})
    compilecache.enable(log=log)
    gen = MaskGenerator(mask)
    eng = get_engine(engine, device="jax")
    sizes = sorted(int(s) for s in sizes)
    rng = np.random.default_rng(0x7A17)

    per_size = []
    compile_fields: dict = {}
    for n_targets in sizes:
        # synthetic random digests: unmatchable in practice, and the
        # probe step's cost does not depend on whether probes hit
        words = rng.integers(0, 2**32, size=(n_targets,
                                             eng.digest_size // 4),
                             dtype=np.uint32)
        digests = [w.tobytes() for w in words]
        ptable = build_probe_table(
            digests, little_endian=eng.little_endian, log=log)
        step = make_mask_crack_step(
            eng, gen, ptable, batch,
            widen_utf16=getattr(eng, "widen_utf16", False))
        base0 = jnp.asarray(gen.digits(0), dtype=jnp.int32)
        t0 = time.perf_counter()
        with compile_observer(engine) as obs:
            hard_sync(step(base0, jnp.int32(batch)))
        compile_s = time.perf_counter() - t0
        if n_targets == sizes[-1]:
            # registry capture for the largest table's program (the
            # one a 10^6-target job runs); analysis happens in
            # _introspection_fields after the timed windows
            programs_mod.register_program(
                engine, "mask+probe", batch, step=step,
                args=(base0, jnp.int32(batch)))
            compile_fields = _compile_fields(obs.cache, obs.seconds)
        if log:
            log.info("targets sweep compiled", targets=n_targets,
                     mode=ptable.mode, table_mb=round(
                         ptable.nbytes / 2**20, 3),
                     seconds=f"{compile_s:.1f}", cache=obs.cache)
        n, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            last = None
            for _ in range(8):       # bounded queue depth
                base = jnp.asarray(gen.digits(
                    (n * batch) % max(gen.keyspace - batch, 1)),
                    dtype=jnp.int32)
                last = step(base, jnp.int32(batch))
                n += 1
            hard_sync(last)
        elapsed = time.perf_counter() - t0
        rate = n * batch / elapsed
        per_size.append({
            "targets": n_targets,
            "rate_hs": rate,
            "s_per_cand": 1.0 / rate,
            "mode": ptable.mode,
            "table_bytes": ptable.nbytes,
            "fp_est": ptable.fp_est,
            "compile_s": round(compile_s, 1),
        })

    flat_ratio = (per_size[-1]["s_per_cand"]
                  / per_size[0]["s_per_cand"])
    rate_max = per_size[-1]["rate_hs"]
    platform = jax.devices()[0].platform
    return _publish({
        "metric": (f"{engine} probe-table H/s at "
                   f"{sizes[-1]:.0e} targets"),
        "value": rate_max,
        "unit": "H/s",
        "engine": engine,
        "mask": mask,
        "device": platform,
        "batch": batch,
        "tuned": tuned,
        "sizes": sizes,
        "per_size": per_size,
        # per-candidate flatness: the O(1) claim, machine-checkable
        "flat_ratio": round(flat_ratio, 4),
        **compile_fields,
        **_introspection_fields(engine, rate_max),
    }, mode="targets")


def _ttfh_first_hit(order, worker, keyspace: int, unit_size: int):
    """Drive a fresh Dispatcher + worker until the first hit: returns
    (candidates_tried, wall_seconds).  Candidate counting is exact --
    units are leased low-start-first, and the hit's position within
    its unit comes back through the order's own point map, so the
    number measures the DISPATCH order, not the sweep chunking."""
    from dprf_tpu.runtime.dispatcher import Dispatcher
    from dprf_tpu.runtime.worker import submit_or_process

    disp = Dispatcher(keyspace, unit_size, order=order)
    tested = 0
    t0 = time.perf_counter()
    while True:
        unit = disp.lease()
        if unit is None:
            raise RuntimeError(
                "ttfh: keyspace exhausted without a hit -- planted "
                "targets unreachable (bijection or oracle broken)")
        hits = submit_or_process(worker, unit).resolve()
        disp.complete(unit.unit_id)
        if hits:
            pos = min((order.index_to_rank(h.cand_index)
                       if order is not None else h.cand_index)
                      for h in hits) - unit.start
            return tested + pos + 1, time.perf_counter() - t0
        tested += unit.length


def _ttfh_steady_rate(worker, start: int, n_units: int,
                      unit_size: int) -> float:
    """Equal-work steady-state H/s: sweep n_units fixed spans (no
    early exit) through the worker's process path.  Ordered and
    linear runs get the SAME numeric spans, so the delta is exactly
    the rank->index decode + run-decomposition overhead."""
    from dprf_tpu.runtime.worker import submit_or_process
    from dprf_tpu.runtime.workunit import WorkUnit

    t0 = time.perf_counter()
    for u in range(n_units):
        submit_or_process(worker, WorkUnit(
            -(u + 1), start + u * unit_size, unit_size)).resolve()
    return n_units * unit_size / (time.perf_counter() - t0)


def run_ttfh(engine: str = "md5", mask: str = "?a?a?a?a?a?a?a?a",
             plants: int = 4, split: int = 2, log=None) -> dict:
    """Time-to-first-hit: rank-ordered vs linear dispatch (ISSUE 20).

    Plants passwords at KNOWN Markov ranks -- prefix digit vectors
    with a small frequency-level sum but a nonzero leading level,
    the shape real passwords take once charsets are frequency-
    reordered (probable everywhere, top-probable nowhere) -- then
    cracks the same job twice through the real Dispatcher + oracle
    worker path: once leasing low RANKS first (MarkovOrder +
    OrderedWorker), once in plain index order.  ``value`` is the
    candidates-to-first-hit SPEEDUP (linear / ordered, higher
    better); ``penalty`` is the steady-state H/s cost of rank
    decoding, from equal-work sweeps over a mid-rank region (where
    blocks scatter in index space -- near rank 0 the runs coalesce
    and would flatter the decode).  CPU-oracle by design: the
    ordering win is a dispatch property, not a backend property, so
    CI gates it without silicon.
    """
    from dprf_tpu.generators.order import MarkovOrder
    from dprf_tpu.runtime.worker import CpuWorker, OrderedWorker

    oracle = get_engine(engine, device="cpu")
    if oracle.salted:
        raise ValueError(
            "ttfh bench plants bare digests; use an unsalted engine")
    gen = MaskGenerator(mask)
    if gen.keyspace > (1 << 25) or len(gen.radices) <= split:
        # the linear sweep must REACH its first hit in CI time: the
        # bench-wide ?a^8 default is a device-scale keyspace, so the
        # ttfh mode substitutes an oracle-scale mask
        mask = "?l?l?l?l?l"
        gen = MaskGenerator(mask)
        if log:
            log.info("ttfh: substituting oracle-scale mask", mask=mask)
    order = MarkovOrder(gen.radices, split=split)
    block = order.block
    r1 = gen.radices[1] if split > 1 else 1

    # plants: leading level 1+i (never 0 -- a level-0 start is found
    # instantly in BOTH orders), small second level, low suffix
    # offset.  Known ranks by construction: plant 0 sits in prefix
    # block 2 of rank order but block 1*r1 of index order.
    plants = max(1, min(int(plants), 8))
    plant_indices = []
    for i in range(plants):
        d0 = min(1 + i, gen.radices[0] - 1)
        d1 = (3 * i) % min(4, r1) if split > 1 else 0
        pidx = d0 * r1 + d1 if split > 1 else d0
        for r in gen.radices[2:split]:
            pidx *= r
        plant_indices.append(pidx * block + (1237 * (i + 1)) % block)
    plains = [gen.candidate(ix) for ix in plant_indices]
    targets = [oracle.parse_target(d.hex())
               for d in oracle.hash_batch(plains)]

    unit_size = 2 * block
    linear_worker = CpuWorker(oracle, gen, targets)
    ordered_worker = OrderedWorker(CpuWorker(oracle, gen, targets),
                                   order)
    cands_lin, wall_lin = _ttfh_first_hit(None, linear_worker,
                                          gen.keyspace, unit_size)
    cands_ord, wall_ord = _ttfh_first_hit(order, ordered_worker,
                                          gen.keyspace, unit_size)
    speedup = cands_lin / cands_ord
    if log:
        log.info("ttfh first hit", ordered=cands_ord, linear=cands_lin,
                 speedup=f"{speedup:.1f}x")

    steady_units = 6
    steady_start = min(20 * unit_size,
                       gen.keyspace - steady_units * unit_size)
    hs_lin = _ttfh_steady_rate(linear_worker, steady_start,
                               steady_units, unit_size)
    hs_ord = _ttfh_steady_rate(ordered_worker, steady_start,
                               steady_units, unit_size)
    penalty = max(0.0, 1.0 - hs_ord / hs_lin)

    return _publish({
        "metric": (f"{engine} candidates-to-first-hit speedup, "
                   "markov rank order vs linear"),
        "value": round(speedup, 4),
        "unit": "x",
        "engine": engine,
        "mask": mask,
        "device": "cpu",
        "plants": plants,
        "planted": [{"index": ix, "rank": order.index_to_rank(ix)}
                    for ix in plant_indices],
        "split": order.split,
        "block": order.block,
        "unit_size": unit_size,
        "ordered": {"candidates_to_first_hit": cands_ord,
                    "first_hit_s": round(wall_ord, 4),
                    "steady_hs": round(hs_ord, 1)},
        "linear": {"candidates_to_first_hit": cands_lin,
                   "first_hit_s": round(wall_lin, 4),
                   "steady_hs": round(hs_lin, 1)},
        # steady-state H/s cost of rank decoding (acceptance: <0.10)
        "penalty": round(penalty, 4),
    }, mode="ttfh")


def run_scaling(engine: str = "md5", mask: str = "?a?a?a?a?a?a?a?a",
                n_devices: int = 8, batch_per_device="auto",
                seconds: float = 5.0, inner: int = 8,
                impl: str = "auto", ablate: bool = False,
                log=None) -> dict:
    """Scaling-efficiency mode over the ONE sharded runtime
    (parallel/sharded.py): superstep dispatches -- candidates
    generated on device per shard, device-resident hit buffer, one
    collective round per dispatch -- measured three ways:

      * ``rate_ndev``: aggregate H/s of the N-device mesh runtime;
      * ``rate_independent``: aggregate H/s of N INDEPENDENT
        single-device runtimes driven concurrently on the SAME
        devices (the paper's embarrassingly-parallel ideal: no mesh,
        no collectives -- what a HashKitty-style per-node fleet
        would sustain);
      * ``rate_1chip``: one device alone (the classic baseline).

    ``efficiency`` (= ``value``, the gated number and the
    ``dprf_scaling_efficiency`` gauge) is rate_ndev /
    rate_independent: the fraction of embarrassingly-parallel
    throughput the single sharded runtime sustains.  On isolated real
    chips the independent baseline IS ``N * rate_1chip``, so this
    reduces to the classic rate_N / (N * rate_1); on a VIRTUAL
    (shared-core) mesh the independent baseline contends for the same
    host cores the mesh does, so the ratio isolates the runtime's
    sharding overhead from core contention.  The classic unloaded
    ratio still rides along as ``efficiency_strict`` (meaningless on
    a virtual mesh, where it is bounded by cores/N; the note says
    so).

    ``inner`` batches fuse into each superstep dispatch (1 = the
    per-batch compat program).  The per-dispatch phase split rides
    along as ``phases``: with on-device generation, ``h2d`` is one
    digit vector per window and its share should read ~0.

    ``impl``: "xla" pins the generic sharded pipeline, "pallas" pins
    the fused Pallas shard-compute (kernel bodies generate + hash +
    compare per shard -- parallel/sharded.make_sharded_kernel_mask_step),
    "auto" takes the kernel when this backend/engine is eligible.
    ``ablate`` adds a per-batch (inner=1) mesh window after the main
    measurement and reports ``superstep_speedup`` -- the ISSUE 18
    dispatch-fusion ablation, measured on the same devices in the same
    process.
    """
    import jax
    import jax.numpy as jnp

    from dprf_tpu.ops import pallas_mask
    from dprf_tpu.parallel.mesh import make_mesh
    from dprf_tpu.parallel.sharded import (make_sharded_kernel_mask_step,
                                           make_sharded_mask_step)

    batch_per_device, tuned = _tuned_or(batch_per_device, engine, "jax",
                                        1 << 20,
                                        extras={"hit_cap": 64})
    from dprf_tpu import compilecache
    compilecache.enable(log=log)
    gen = MaskGenerator(mask)
    eng = get_engine(engine, device="jax")
    fake = bytes([0xFF]) * eng.digest_size   # unmatchable (see run_bench)
    tgt = target_words(fake, eng.little_endian)
    devices = jax.devices()
    if len(devices) < n_devices:
        raise ValueError(f"requested {n_devices} devices, only "
                         f"{len(devices)} present")
    inner = max(1, int(inner))
    widen = getattr(eng, "widen_utf16", False)

    kmode = pallas_mask.pallas_mode()
    eligible = (kmode is not None and engine in pallas_mask.CORES
                and pallas_mask.kernel_eligible(engine, gen, 1))
    if impl == "pallas" and not eligible:
        raise ValueError(
            "--impl pallas: sharded kernel compute not available here "
            "(needs a kernel-capable engine and DPRF_PALLAS on/auto-TPU)")
    use_kernel = impl == "pallas" or (impl == "auto" and eligible)
    if use_kernel:
        # shard batches are tile-quantized on the kernel path
        tile = pallas_mask.SUB * 128
        batch_per_device = max(tile,
                               (batch_per_device // tile) * tile)

    from dprf_tpu.utils.sync import hard_sync

    def build(devs, inner_n=None):
        inner_n = inner if inner_n is None else inner_n
        m = make_mesh(devices=list(devs))
        if use_kernel:
            step = make_sharded_kernel_mask_step(
                engine, gen, tgt, m, batch_per_device,
                interpret=bool(kmode.get("interpret", False)))
        else:
            step = make_sharded_mask_step(
                eng, gen, tgt, m, batch_per_device, widen_utf16=widen)
        fn = step.superstep(inner_n) if inner_n > 1 else step
        return fn, step.super_batch * inner_n

    def dispatch(fn, span, k):
        base = jnp.asarray(
            gen.digits((k * span) % max(gen.keyspace - span, 1)),
            dtype=jnp.int32)
        return fn(base, jnp.int32(span))

    def warm(builds, label: str) -> float:
        t0 = time.perf_counter()
        for fn, span in builds:
            hard_sync(dispatch(fn, span, 0))
        compile_s = time.perf_counter() - t0
        if log:
            log.info("scaling bench compiled", what=label,
                     runtimes=len(builds), seconds=f"{compile_s:.1f}")
        return compile_s

    def window(builds, budget: float) -> tuple:
        """One timed window: (candidates swept, elapsed seconds)."""
        k, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < budget:
            lasts = None
            for _ in range(2):       # bounded queue depth per stream
                lasts = [dispatch(fn, span, k) for fn, span in builds]
                k += 1
            for r in lasts:
                hard_sync(r)
        return (k * sum(span for _, span in builds),
                time.perf_counter() - t0)

    mesh_build = build(devices[:n_devices])
    solo_builds = [build([d]) for d in devices[:n_devices]]
    compile_mesh = warm([mesh_build], "mesh")
    compile_ind = warm(solo_builds, "independent")
    # program-registry capture of the mesh program (ISSUE 13); the
    # lower() is a cached trace after warm(), analysis runs after the
    # timed windows in _introspection_fields
    from dprf_tpu.telemetry import programs as programs_mod
    programs_mod.register_program(
        engine, "mask+sharded", mesh_build[1], step=mesh_build[0],
        args=(jnp.asarray(gen.digits(0), dtype=jnp.int32),
              jnp.int32(mesh_build[1])))
    # the mesh and independent windows ALTERNATE (3 rounds each) so
    # slow drift on the host -- thermal throttling, background load on
    # a shared box -- hits both sides of the efficiency ratio equally
    # instead of whichever happened to run second
    totals = {"mesh": [0.0, 0.0], "independent": [0.0, 0.0]}
    budget = max(0.5, seconds / 3.0)
    for _ in range(3):
        for label, builds in (("mesh", [mesh_build]),
                              ("independent", solo_builds)):
            w, t = window(builds, budget)
            totals[label][0] += w
            totals[label][1] += t
    many = {"rate": totals["mesh"][0] / totals["mesh"][1],
            "compile_s": round(compile_mesh, 1)}
    independent = {"rate": (totals["independent"][0]
                            / totals["independent"][1]),
                   "compile_s": round(compile_ind, 1)}
    w, t = window(solo_builds[:1], budget)
    one = {"rate": w / t}
    # superstep-vs-per-batch ablation (same devices, same process):
    # the fusion win of draining `inner` batches per collective round
    perbatch_rate = None
    if ablate and inner > 1:
        pb_build = build(devices[:n_devices], inner_n=1)
        warm([pb_build], "per-batch")
        w, t = window([pb_build], budget)
        perbatch_rate = w / t
    # per-dispatch phase attribution of the mesh runtime (outside the
    # timed windows, compiled already): with on-device generation the
    # h2d phase is one tiny digit-vector transfer per window
    phases = _step_phases(gen, mesh_build[0], mesh_build[1])
    total_s = sum(phases.values()) or 1.0

    platform = jax.devices()[0].platform
    eff_raw = many["rate"] / independent["rate"] if independent["rate"] \
        else 0.0
    # efficiency is a fraction of the ideal by definition: a raw ratio
    # above 1 means the INDEPENDENT baseline paid overhead the mesh
    # avoided (e.g. 8 oversubscribed dispatch streams on a shared-core
    # virtual mesh), not superlinear scaling -- clamp the gated value
    # so the committed trajectory stays comparable round to round, and
    # keep the raw ratio alongside.
    eff = min(1.0, eff_raw)
    out = {
        "metric": f"{engine} scaling efficiency 1->{n_devices}",
        "value": eff,
        "unit": "fraction",
        "engine": engine,
        "mask": mask,
        "n_devices": n_devices,
        "batch_per_device": batch_per_device,
        "tuned": tuned,
        "inner": inner,
        "superstep": inner > 1,
        "impl": "pallas" if use_kernel else "xla",
        "baseline": "independent",
        "rate_1chip": one["rate"],
        "rate_ndev": many["rate"],
        "rate_independent": independent["rate"],
        "per_chip": many["rate"] / n_devices,
        "efficiency": eff,
        "efficiency_raw": eff_raw,
        "efficiency_strict": (many["rate"] / (n_devices * one["rate"])
                              if one["rate"] else 0.0),
        "phases": phases,
        "h2d_share": round(phases.get("h2d", 0.0) / total_s, 6),
        "device": platform,
        # roofline is a PER-CHIP quantity: the aggregate mesh rate
        # against the single-chip ceiling would read ~n_devices-fold
        # over unity
        **_introspection_fields(engine, many["rate"] / n_devices),
    }
    if perbatch_rate:
        out["rate_ndev_perbatch"] = perbatch_rate
        out["superstep_speedup"] = round(many["rate"] / perbatch_rate, 4)
    if platform != "tpu":
        out["note"] = (
            "virtual CPU mesh: the 'devices' share the host cores, so "
            "efficiency_strict is bounded by cores/N and only the "
            "independent-baseline efficiency (the contention-fair "
            "form of the same ratio) is meaningful off-TPU")
    return _publish(out, mode="scaling")


# ---------------------------------------------------------------------------
# the five BASELINE.json acceptance workloads, measured through the
# REAL worker paths (engine.make_*_worker + worker.process), so the
# number includes candidate generation, compare, and hit readback --
# what a job sustains, not a stripped kernel.

def _unmatchable(engine) -> str:
    """A parseable target line no in-keyspace candidate can produce."""
    return "ff" * engine.digest_size


def _fake_bcrypt_line(cost: int) -> str:
    from dprf_tpu.engines.cpu.bcrypt import b64_encode
    salt = bytes(range(16))
    digest = bytes((7 * i + 3) % 256 for i in range(23))
    return (f"$2b${cost:02d}$" + b64_encode(salt)[:22]
            + b64_encode(digest)[:31])


def _fake_pmkid_line() -> str:
    pmkid = bytes((5 * i + 1) % 256 for i in range(16))
    return f"{pmkid.hex()}*0a1b2c3d4e5f*a0b1c2d3e4f5*{b'benchnet'.hex()}"


def uniform_digest_lines(n: int, nbytes: int, seed: int = 1) -> list:
    """n uniformly random digests as hex lines, from the seed: what a
    real hash list looks like to a prefilter (and, at 2^-128 a line,
    unmatchable).  Lines with structure -- multiples of one constant,
    say -- leave most of a prefilter's bits unset and hide what its
    false positives cost the host."""
    import random
    rng = random.Random(seed)
    return ["%0*x" % (2 * nbytes, rng.getrandbits(8 * nbytes))
            for _ in range(n)]


def _synthetic_words(n: int, length: int = 8) -> list:
    """Deterministic pseudo-wordlist (no RNG, no file I/O)."""
    alpha = b"abcdefghijklmnopqrstuvwxyz"
    out = []
    x = 12345
    for _ in range(n):
        x = (1103515245 * x + 12345) & 0x7FFFFFFF
        out.append(bytes(alpha[(x >> (3 * j)) % 26] for j in range(length)))
    return out


def _config_job(n: int, bcrypt_cost: int):
    """config number -> (engine_name, attack, generator, target lines)."""
    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.rules.parser import load_rules

    if n == 1:     # MD5 single-hash, 6-char lowercase mask
        return "md5", "mask", MaskGenerator("?l?l?l?l?l?l"), None
    if n == 2:     # NTLM 1k-hash list, 7-char ?a mask, multi-target
        return ("ntlm", "mask", MaskGenerator("?a?a?a?a?a?a?a"),
                uniform_digest_lines(1000, 16))
    if n == 3:     # SHA-256 wordlist + best64, on-device rule expansion
        # 1M words x 64 rules = a 67M keyspace, big enough that a
        # multi-stride unit amortizes per-dispatch overhead (see
        # unit_strides).
        # max_len 24 is the MINIMUM that keeps every best64 expansion
        # of the 8-byte words identical to the 55-byte default
        # (computed against rules/cpu.py: two rules grow to 24 bytes
        # mid-rule before truncating) while keeping per-position rule
        # cost proportional to real candidate lengths.
        gen = WordlistRulesGenerator(_synthetic_words(1 << 20),
                                     load_rules("best64"), max_len=24)
        return "sha256", "wordlist", gen, None
    if n == 4:     # bcrypt wordlist, memory-hard path
        gen = WordlistRulesGenerator(_synthetic_words(1 << 12))
        return "bcrypt", "wordlist", gen, [_fake_bcrypt_line(bcrypt_cost)]
    if n == 5:     # WPA2-PMKID iterated-KDF sweep (8-char passphrases)
        return "wpa2-pmkid", "mask", MaskGenerator("?l?l?l?l?l?l?l?l"), \
            [_fake_pmkid_line()]
    raise ValueError(f"unknown config {n} (1-5)")


def run_config(config: int, device: str = "jax", seconds: float = 5.0,
               batch="auto", bcrypt_cost: int = 12,
               unit_strides: int = 1, log=None) -> dict:
    """Measure one acceptance workload end to end.  Returns the same
    JSON shape as run_bench, plus the config number.

    unit_strides: worker batches per WorkUnit.  Real jobs get units
    from the Dispatcher that span MANY device batches, and the worker
    pipelines their dispatches before reading hits back -- a
    one-stride unit measures one dispatch and one readback, not the
    chip.  Pass enough strides for a few seconds of compute per
    process() call to reproduce the production shape."""
    import time as _time

    from dprf_tpu.runtime.worker import CpuWorker
    from dprf_tpu.runtime.workunit import WorkUnit

    engine_name, attack, gen, lines = _config_job(config, bcrypt_cost)
    batch, tuned = _tuned_or(batch, engine_name, device, 1 << 18,
                             attack=attack,
                             extras={"hit_cap": 64,
                                     **({"rules_n": gen.n_rules}
                                        if attack == "wordlist" else {})})
    oracle = get_engine(engine_name, device="cpu")
    targets = [oracle.parse_target(s)
               for s in (lines or [_unmatchable(oracle)])]
    from dprf_tpu import compilecache
    if device == "jax":
        compilecache.enable(log=log)
        eng = get_engine(engine_name, device="jax")
        maker = ("make_mask_worker" if attack == "mask"
                 else "make_wordlist_worker")
        worker = getattr(eng, maker)(gen, targets, batch=batch,
                                     hit_capacity=64, oracle=oracle)
        stride = worker.stride
    else:
        worker = CpuWorker(oracle, gen, targets)
        stride = min(1 << 12, gen.keyspace)

    unit_len = stride * max(1, unit_strides)
    # warmup/compile on a FULL unit so the super-step program (workers
    # fuse many batches into one dispatch for multi-stride units) is
    # compiled outside the timed window, not inside it.  Device workers
    # warm their per-batch step FIRST (a zero-work dispatch through the
    # observer gives a clean hit/miss classification); the full-unit
    # prime is then classified by cache-entry delta alone -- its wall
    # time is mostly real hashing, which must not read as a cold
    # compile.  The CPU-oracle path has no jit at all: always "off".
    t0 = _time.perf_counter()
    if device == "jax":
        if not getattr(worker, "_warmed", False):
            worker.warmup()
        before = compilecache.entry_count()
        worker.process(WorkUnit(-1, 0, min(unit_len, gen.keyspace)))
        prime = compilecache.classify_delta(before,
                                            compilecache.entry_count())
        # any cold compile anywhere in the fixed cost -- step warmup or
        # super/wide program build during the prime -- means this run
        # paid one
        wc = getattr(worker, "compile_cache", "off")
        compile_cache = "miss" if "miss" in (wc, prime) else wc
    else:
        worker.process(WorkUnit(-1, 0, min(unit_len, gen.keyspace)))
        compile_cache = "off"
    compile_s = _time.perf_counter() - t0
    if log:
        log.info("config compiled", config=config,
                 seconds=f"{compile_s:.1f}", cache=compile_cache)

    from dprf_tpu.runtime.worker import submit_or_process

    tested = 0
    start = 0
    pending: list = []
    t0 = _time.perf_counter()
    # depth-2 submit/resolve pipeline -- the production Coordinator
    # shape -- so a unit's flag readback overlaps the next unit's
    # compute instead of serializing with it.
    # Always submit FULL-size units (wrapping to 0 early rather than
    # issuing a keyspace-tail remnant): an odd-sized tail unit would
    # pick super-step inner sizes the warmup never compiled, putting a
    # multi-second jit inside the timed window.
    length = min(unit_len, gen.keyspace)
    while True:
        in_window = _time.perf_counter() - t0 < seconds
        if in_window:
            if gen.keyspace - start < length:
                start = 0
            pending.append((length, submit_or_process(
                worker, WorkUnit(-1, start, length))))
            start += length
        if not pending:
            break
        if len(pending) >= 2 or not in_window:
            ulen, p = pending.pop(0)
            p.resolve()
            tested += ulen
    elapsed = _time.perf_counter() - t0

    import jax as _jax

    from dprf_tpu.runtime.worker import describe_worker
    platform = (_jax.devices()[0].platform if device == "jax" else "cpu")
    ran = describe_worker(worker)
    return _publish({
        "metric": f"config{config} {engine_name} candidates/sec/chip",
        # what ran: a record must name its path, not only its rate
        "worker": ran["worker"],
        "interpret": ran["interpret"],
        "dispatch": ran["dispatch"],
        "value": tested / elapsed,
        "unit": "H/s",
        "config": config,
        "engine": engine_name,
        "attack": attack,
        "targets": len(targets),
        "device": platform,
        "batch": batch,
        "tuned": tuned,
        "unit_strides": max(1, unit_strides),
        "tested": tested,
        "elapsed_s": round(elapsed, 3),
        "compile_s": round(compile_s, 1),
        **_compile_fields(compile_cache, compile_s),
        **_introspection_fields(engine_name, tested / elapsed),
    }, mode="config")
