"""Multi-chip workers: drive the unified sharded runtime over WorkUnits.

Shares all target setup and hit decoding with
runtime.worker.DeviceMaskWorker via MaskWorkerBase; the differences are
the runtime-built sharded step (parallel/sharded.py) and that each
dispatch covers an ``n_dev * batch_per_device`` super-batch whose hit
buffers come back per shard.

Large units go out as **sharded supersteps**: one dispatch fuses up to
``DPRF_SHARD_SUPER_CAP`` batches, generating candidates ON DEVICE per
shard from ``base + shard offset`` (the host ships one digit vector per
window, not per batch -- per-sweep h2d collapses to ~0) and
accumulating hits in a device-resident buffer with ONE collective round
per window.  Hit lanes are window-relative, so ``window start + lane``
is the keyspace index exactly as in the single-device path; hits drain
to host only at unit boundaries through the standard PendingUnit flag,
keeping the UnitPipeline submit/resolve contract intact.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from dprf_tpu.engines.base import HashEngine, Target
from dprf_tpu.runtime.worker import (Hit, MaskWorkerBase, PendingUnit,
                                     WordlistWorkerBase,
                                     word_cover_range)
from dprf_tpu.runtime.workunit import WorkUnit
from dprf_tpu.telemetry import coverage

#: `dprf check` retrace analyzer: the sharded per-window dispatch
#: loops.  Everything submit() enqueues rides the device stream; a
#: host sync or a retrace inside them stalls every unit of every job.
HOT_PATHS = ("ShardedMaskWorker.submit", "ShardedWordlistWorker.submit")


def shard_super_cap(default: int = 256) -> int:
    """Batches fused per sharded superstep dispatch (power-of-two
    clamp; the int32 window budget of ops/superstep.max_inner still
    applies on top).  ONE resolution site for the knob."""
    from dprf_tpu.utils import env as envreg
    n = max(2, envreg.get_int("DPRF_SHARD_SUPER_CAP", int(default)))
    return 1 << (n.bit_length() - 1)


class _ShardedSuperstepMixin:
    """Ahead-of-time compile shared by the sharded workers (one
    prewarm path)."""

    def _aot_chunks(self) -> int:
        """Per-batch chunks this job's whole keyspace could fill --
        what _super_inner sizes the steady-state window against."""
        raise NotImplementedError

    def aot_compile(self) -> None:
        """Prewarm BOTH sharded programs: the per-batch step and the
        capped superstep -- the program steady-state big units
        actually dispatch (``_super_inner`` saturates at the cap), so
        a fleet image covers the hot path, not just the remainder.
        Skipped when the job's keyspace is too small to ever fill a
        superstep window (the program would never run)."""
        super().aot_compile()
        inner = self._super_inner(self._aot_chunks())
        if inner < 2:
            return
        ss = self.step.superstep(inner)
        lower = getattr(ss, "lower", None)
        if lower is None:
            return
        from dprf_tpu.compilecache import compile_observer
        args = self.warmup_args()
        lowered = lower(*args)
        with compile_observer(getattr(self.engine, "name",
                                      "unknown")) as obs:
            compiled = lowered.compile()
        self.xla_compile_seconds = (
            getattr(self, "xla_compile_seconds", 0.0) + obs.seconds)
        self.compile_seconds = (
            getattr(self, "compile_seconds", 0.0) + obs.seconds)
        if obs.cache == "miss":
            self.compile_cache = "miss"
        # the superstep's own program record (telemetry/programs.py):
        # one dispatch covers inner * stride candidates, so its
        # per-candidate costs show what the fusion amortizes
        from dprf_tpu.telemetry import programs as programs_mod
        programs_mod.register_program(
            getattr(self.engine, "name", "unknown"),
            self.ATTACK + "+super", inner * self.stride,
            compiled=compiled, lowered=lowered)


class ShardedMaskWorker(_ShardedSuperstepMixin, MaskWorkerBase):
    """Fused-pipeline worker spread over a device mesh.

    Bulk target lists (>= DPRF_TARGETS_PROBE_MIN) swap the replicated
    compare table for the probe table (dprf_tpu/targets/): the sharded
    step builder carries it as replicated device state through
    supersteps, so probe_ok is set here.

    ``kernel`` (a dict of ops/pallas_mask options: ``sub``,
    ``interpret``, ``probe_fp``; an empty dict takes every default)
    swaps the XLA compute for the FUSED PALLAS KERNEL per shard
    (parallel/sharded.make_sharded_kernel_mask_step): candidates
    generate, hash, and compare(+probe) in VMEM, the host ships one
    digit vector per superstep window.  Multi-target kernel hits come
    back SENTINEL-tagged (in-kernel blocked-probe survivors: one
    oracle hash each; a tile with 2+ survivors is re-probed on the
    device for its survivors, MaskWorkerBase._reprobe_tiles, and
    rescanned whole on the host only where that disagrees), so an
    oracle engine is required to verify them."""

    def __init__(self, engine, gen, targets: Sequence[Target], mesh,
                 batch_per_device: int = 1 << 18, hit_capacity: int = 64,
                 oracle: Optional[HashEngine] = None,
                 kernel: Optional[dict] = None):
        from dprf_tpu.parallel.sharded import (
            make_sharded_kernel_mask_step, make_sharded_mask_step)

        if kernel is None:
            tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                      oracle, probe_ok=True)
            self.mesh = mesh
            self.step = make_sharded_mask_step(
                engine, gen, tgt, mesh, batch_per_device, hit_capacity,
                widen_utf16=getattr(engine, "widen_utf16", False))
        else:
            from dprf_tpu.ops.pallas_mask import SUB

            # the kernel compares against raw target words (exact or
            # blocked-probe), never the XLA table/probe structures
            tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                      oracle)
            self.ATTACK = self.ATTACK + "+kernel"
            if self.multi:
                if oracle is None:
                    raise ValueError(
                        "sharded kernel compute with multiple targets "
                        "needs an oracle engine to verify probe "
                        "survivors")
                dt = "<u4" if engine.little_endian else ">u4"
                twords = np.stack([np.frombuffer(t.digest, dtype=dt)
                                   .astype(np.uint32)
                                   for t in self.targets])
                self._digest_map = {t.digest: i
                                    for i, t in enumerate(self.targets)}
            else:
                twords = np.asarray(tgt)
            sub = kernel.get("sub") or SUB
            self._interpret = bool(kernel.get("interpret", False))
            tile = self._tile = sub * 128
            if self.multi:
                self._setup_tile_reprobe(twords, sub,
                                         kernel.get("probe_fp"))
            batch_per_device = max(tile,
                                   (batch_per_device // tile) * tile)
            self.mesh = mesh
            self.step = make_sharded_kernel_mask_step(
                engine.name, gen, twords, mesh, batch_per_device,
                hit_capacity, sub=sub, interpret=self._interpret,
                probe_fp=kernel.get("probe_fp"))
        self.super_batch = self.stride = self.step.super_batch
        #: instance override of MaskWorkerBase.SUPER_CAP: the sharded
        #: superstep has its own fusion knob
        self.SUPER_CAP = shard_super_cap()

    def submit(self, unit: WorkUnit) -> PendingUnit:
        """Enqueue ALL sharded device work for the unit and return a
        PendingUnit.  Full power-of-two windows go out as superstep
        dispatches (one digit vector + one dispatch + one collective
        round per window); the remainder uses the per-batch step.  The
        unit-level hit flag accumulates ON DEVICE across both kinds,
        so a hitless unit costs exactly one scalar readback."""
        import jax.numpy as jnp
        queued = []
        flag = None
        pos = unit.start
        while True:
            inner = self._super_inner((unit.end - pos) // self.stride)
            if inner < 2:
                break
            window = inner * self.stride
            base = jnp.asarray(self.gen.digits(pos), dtype=jnp.int32)
            result = self._call_fused(
                ("sshard", inner), self.step.superstep(inner), base,
                jnp.int32(window))
            f = self._batch_flag(result)
            flag = f if flag is None else flag + f
            queued.append(("sshard", (pos, window), result))
            # coverage note (ISSUE 19): superstep windows must tile
            # the unit exactly -- one cheap note per multi-million-
            # candidate window lets the auditor check that
            coverage.note("window", pos, pos + window,
                          unit=unit.unit_id, kind="sshard")
            pos += window
        for bstart in range(pos, unit.end, self.stride):
            n_valid = min(self.stride, unit.end - bstart)
            base = jnp.asarray(self.gen.digits(bstart), dtype=jnp.int32)
            result = self.step(base, jnp.int32(n_valid))
            f = self._batch_flag(result)
            flag = f if flag is None else flag + f
            queued.append(("batch", bstart, result))
            coverage.note("window", bstart, bstart + n_valid,
                          unit=unit.unit_id, kind="batch")
        if flag is not None and hasattr(flag, "copy_to_host_async"):
            flag.copy_to_host_async()
        if queued and not hasattr(self, "out_devices"):
            #: ids of the devices that hold the sharded step's output
            #: buffers (describe_worker): one per mesh device, or the
            #: mesh is not what ran
            self.out_devices = sorted(
                s.device.id for s in queued[0][2][1].addressable_shards)
        return PendingUnit(self, unit, queued, flag)

    def process(self, unit: WorkUnit) -> list[Hit]:
        return self.submit(unit).resolve()

    process._submit_based = True   # safe to pipeline via submit()

    def _aot_chunks(self) -> int:
        return self.gen.keyspace // self.stride

    def _decode_queued(self, kind: str, start, result,
                       unit: WorkUnit) -> list[Hit]:
        if kind == "sshard":
            pos, window = start
            return self._batch_hits(pos, result, unit, window=window)
        return super()._decode_queued(kind, start, result, unit)

    def _batch_hits(self, bstart: int, result, unit: WorkUnit,
                    window: int = 0) -> list[Hit]:
        total, counts, lanes, tpos = result
        if int(total) == 0:
            return []
        counts_np = np.asarray(counts)
        # Check every shard BEFORE decoding any: an overflowed shard's
        # buffer is truncated, so mixing a redrive with per-shard
        # decoded hits would double-report the non-overflowed shards.
        # Capacity is the step's built per-shard buffer width.  An
        # overflowed superstep window redrives through the per-batch
        # DEVICE step (the inherited _redrive_wide loop), so exact-
        # rescan granularity stays one super-batch stride.
        if (counts_np > lanes.shape[-1]).any():
            if window > self.stride:
                return self._redrive_wide(bstart, window, unit)
            return self._rescan(bstart, unit, window)
        lanes_np = np.asarray(lanes)
        tpos_np = np.asarray(tpos)
        # kernel multi-target compute: payload n_targets + 1 marks a
        # COLLIDED tile (2+ probe survivors, one reportable lane) by
        # its first lane -- re-probe exactly that tile for its
        # survivors (dispatched first, read back last: the other
        # lanes are verified while the re-probes wait on the device)
        tile = getattr(self.step, "tile", 0) if self.multi else 0
        rescan = (tpos_np == len(self.targets) + 1) & (lanes_np >= 0) \
            if tile else np.zeros_like(lanes_np, bool)
        tiles = self._reprobe_tiles(
            [bstart + lane for lane in lanes_np[rescan].tolist()], unit)
        # every shard's lanes are window-relative: the window's maybes,
        # all shards together, go to the oracle in one call
        hits = self._decode_lanes(bstart, np.where(rescan, -1, lanes_np),
                                  tpos_np, unit)
        hits.extend(self._tile_hits(tiles, unit))
        return hits


class ShardedCombinatorWorker(ShardedMaskWorker):
    """Combinator / hybrid attack spread over a device mesh: the
    runtime-built combinator step with ShardedMaskWorker's submit and
    hit decoding (same base_digits/n_valid contract -- the combinator
    keyspace is a 2-digit mixed-radix system)."""

    def __init__(self, engine, gen, targets: Sequence[Target], mesh,
                 batch_per_device: int = 1 << 18, hit_capacity: int = 64,
                 oracle: Optional[HashEngine] = None):
        from dprf_tpu.ops.combine import (
            make_sharded_combinator_crack_step)

        tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                  oracle, probe_ok=True)
        self.mesh = mesh
        self.step = make_sharded_combinator_crack_step(
            engine, gen, tgt, mesh, batch_per_device, hit_capacity,
            widen_utf16=getattr(engine, "widen_utf16", False))
        self.super_batch = self.stride = self.step.super_batch
        self.SUPER_CAP = shard_super_cap()


class ShardedWordlistWorker(_ShardedSuperstepMixin, WordlistWorkerBase):
    """Wordlist+rules attack spread over a device mesh.

    Each per-batch dispatch covers ``n_dev * word_batch_per_device``
    words; chip c expands+hashes its contiguous word slice locally (the
    packed wordlist is replicated to every chip's HBM once per job),
    and supersteps fuse many word windows per dispatch with the word
    cursor advancing ON DEVICE.  Hit lanes come back as window-relative
    keyspace offsets (relative to ``w0 * n_rules``), so the decode is
    ``w0 * n_rules + lane``.
    """

    def __init__(self, engine, gen, targets: Sequence[Target], mesh,
                 word_batch_per_device: int = 1 << 14,
                 hit_capacity: int = 64,
                 oracle: Optional[HashEngine] = None):
        from dprf_tpu.ops.rules_pipeline import (
            make_sharded_wordlist_crack_step)

        tgt = self._setup_targets(engine, gen, targets, hit_capacity,
                                  oracle, probe_ok=True)
        self.mesh = mesh
        self.step = make_sharded_wordlist_crack_step(
            engine, gen, tgt, mesh, word_batch_per_device, hit_capacity,
            widen_utf16=getattr(engine, "widen_utf16", False))
        self.word_batch = self.super_words = self.step.super_words
        self.stride = self.super_words * gen.n_rules
        self.SUPER_CAP = shard_super_cap()

    def submit(self, unit: WorkUnit) -> PendingUnit:
        """Word-window analogue of ShardedMaskWorker.submit: full
        power-of-two runs of word windows fuse into superstep
        dispatches; the remainder uses per-window dispatches.  The
        unit-level hit flag is accumulated on device, so a hitless
        unit costs one scalar readback and the worker pipelines
        through submit_or_process like the single-device paths."""
        import jax.numpy as jnp
        w_start, w_end = word_cover_range(unit, self.gen.n_rules)
        w_end = min(w_end, self.gen.n_words)
        queued = []
        flag = None
        ws = w_start
        while True:
            inner = self._super_inner((w_end - ws) // self.super_words)
            if inner < 2:
                break
            nw = inner * self.super_words
            result = self._call_fused(
                ("wshard", inner), self.step.superstep(inner),
                jnp.int32(ws), jnp.int32(nw))
            f = self._batch_flag(result)
            flag = f if flag is None else flag + f
            queued.append(("wshard", (ws, nw), result))
            # coverage note (ISSUE 19): word-window tiling evidence,
            # in candidate-index coordinates
            coverage.note("window", ws * self.gen.n_rules,
                          (ws + nw) * self.gen.n_rules,
                          unit=unit.unit_id, kind="wshard")
            ws += nw
        while ws < w_end:
            nw = min(self.super_words, w_end - ws)
            if nw <= 0:
                break
            result = self.step(jnp.int32(ws), jnp.int32(nw))
            # device-accumulated unit flag; see MaskWorkerBase.submit
            f = self._batch_flag(result)
            flag = f if flag is None else flag + f
            queued.append(("wshard", (ws, nw), result))
            coverage.note("window", ws * self.gen.n_rules,
                          (ws + nw) * self.gen.n_rules,
                          unit=unit.unit_id, kind="wwindow")
            ws += nw
        if flag is not None and hasattr(flag, "copy_to_host_async"):
            flag.copy_to_host_async()
        return PendingUnit(self, unit, queued, flag)

    def process(self, unit: WorkUnit) -> list[Hit]:
        return self.submit(unit).resolve()

    process._submit_based = True   # safe to pipeline via submit()

    def _super_inner(self, remaining_chunks: int) -> int:
        """Like MaskWorkerBase._super_inner, but budgeted on the
        rule-expanded lane stride (window-relative keyspace offsets
        must stay int32, and a window covers words * n_rules lanes)."""
        from dprf_tpu.ops.superstep import max_inner
        from dprf_tpu.utils import env as envreg
        if not envreg.get_bool("DPRF_SUPERSTEP"):
            return 0
        cap = max_inner(self.stride, self.SUPER_CAP)
        if remaining_chunks < self.SUPER_MIN or cap < self.SUPER_MIN:
            return 0
        return min(cap, 1 << (remaining_chunks.bit_length() - 1))

    def _aot_chunks(self) -> int:
        return self.gen.n_words // self.super_words

    def _decode_queued(self, kind: str, start, result,
                       unit: WorkUnit) -> list[Hit]:
        if kind != "wshard":
            return super()._decode_queued(kind, start, result, unit)
        ws, nw = start
        total, counts, lanes, tpos = result
        if int(total) == 0:
            return []
        if (np.asarray(counts) > lanes.shape[-1]).any():
            if nw > self.super_words:
                return self._redrive_sharded_words(ws, nw, unit)
            return self._rescan_words(ws, nw, unit)
        R = self.gen.n_rules
        base = ws * R
        hits: list[Hit] = []
        maybes: list[int] = []
        for lane, tp in zip(np.asarray(lanes).ravel(),
                            np.asarray(tpos).ravel()):
            if lane < 0:
                continue
            gidx = base + int(lane)
            if not unit.start <= gidx < unit.end:
                continue
            if self.multi and not 0 <= int(tp) < len(self._order):
                # probe-table survivor left unverified on device (see
                # sharded.probe_lane_compare): the window's maybes go
                # to the oracle in one call
                maybes.append(gidx)
                continue
            ti = int(self._order[int(tp)]) if self.multi else 0
            hits.append(Hit(ti, gidx, self.gen.candidate(gidx)))
        hits.extend(self._verify_probe_lanes(maybes, unit))
        return hits

    def _redrive_sharded_words(self, ws: int, nw: int,
                               unit: WorkUnit) -> list[Hit]:
        """Overflowed superstep word window -> per-window device
        redrive (exact-rescan granularity stays one super-batch)."""
        import jax.numpy as jnp
        hits: list[Hit] = []
        end = ws + nw
        # coverage note (ISSUE 19): the overflowed superstep window
        # goes back through per-window dispatch -- deliberate
        # re-coverage, in candidate-index coordinates
        R = self.gen.n_rules
        coverage.note("redrive", max(unit.start, ws * R),
                      min(unit.end, end * R), unit=unit.unit_id)
        w = ws
        while w < end:
            n = min(self.super_words, end - w)
            hits.extend(self._decode_queued(
                "wshard", (w, n),
                self.step(jnp.int32(w), jnp.int32(n)), unit))
            w += n
        return hits
