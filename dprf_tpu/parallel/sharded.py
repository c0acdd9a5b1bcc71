"""ONE mesh-native sharded runtime: every multi-chip crack step is the
same ``shard_map`` program over the 1-D ``candidates`` mesh axis, built
here from a per-shard *compute* callback.

The runtime owns everything that used to be copy-pasted across the
per-engine ``make_sharded_*`` factories (mask / combinator / wordlist /
per-target-salted): the ``lax.axis_index`` lane-slice bookkeeping, hit
compaction, lane globalization, and the collective round.  An engine
contributes ONLY its math -- a ``compute(offset, *step_args) ->
(found, payload)`` callback over its shard's lane slice -- and gets two
programs back:

* the **per-batch step** (``step(*args)``), keeping the historical
  ``(total, counts[n_dev], lanes[n_dev, cap], tpos[n_dev, cap])``
  contract with replicated hit buffers (multi-host addressable); and
* the **superstep** (``step.superstep(inner)``), the tentpole program:
  ONE dispatch covers ``inner`` consecutive batches.  Candidates are
  generated **on device** per shard from ``base + shard offset`` (the
  only host->device traffic is the tiny base argument -- a digit
  vector or a scalar window start -- so the packed candidate tensor
  never materializes on host and the per-sweep ``h2d`` phase collapses
  to ~0), hits accumulate in a **device-resident buffer** carried
  through the loop, as wide as the window asks
  (``ops/superstep.window_capacity(hit_capacity, inner)``: the one
  width policy of every fused window, one chip or many), and exactly
  ONE ``psum`` + ``all_gather`` round runs per superstep instead of
  one per batch.

Hit-buffer lane values are *window-relative*: the keyspace offset of
the hit inside the dispatched window (for wordlist steps, relative to
``w0 * n_rules``).  A window is bounded to int32 by the callers'
``ops/superstep.max_inner`` budget, so huge keyspaces never force
64-bit lane math on device; the host adds the unit base.  A shard
whose window collects more hits than the window's buffer holds
reports a count over the buffer's width (the buffer truncates, the
count does not), and the workers redrive the window through the
per-batch program -- same overflow discipline as the wide/scan paths.
One stride still folds through ``hit_capacity`` slots, so a stride
whose own count exceeds THAT width (more matches than slots, or a
compute's ``hit_capacity + 1`` collision sentinel) pushes the window's
count past the window's width: nothing is dropped without the count
saying so.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops.superstep import window_capacity
from dprf_tpu.parallel.mesh import SHARD_AXIS


def _append_hits(carry, found, payload, rel, capacity: int,
                 true_count=None):
    """Fold one shard-batch's matches into the device-resident hit
    buffer carried across a superstep.  ``rel`` maps each local lane
    to its window-relative value.  The stride compacts to ``capacity``
    slots (the per-batch width) and those scatter into the carry,
    which is as wide as the WINDOW's buffer: the fold costs the same
    whatever the window's width.  Slots past the carry's end drop (the
    count keeps the truth, so overflow is detectable on drain).

    ``true_count`` overrides the compacted count when the compute
    itself is the authority -- the TILE-compute (kernel) contract,
    where a per-tile collision inflates the count past ``capacity``.

    A stride whose own count exceeds ``capacity`` was truncated here
    (or holds a collision the compute could not report), so it pushes
    the window's count past the carry's width and the drain path
    redrives the window exactly."""
    count, lanes_buf, pay_buf = carry
    width = lanes_buf.shape[0]
    c, lanes, pay = cmp_ops.compact_hits(found, payload, capacity)
    ok = lanes >= 0
    rel_lanes = jnp.where(ok, jnp.take(rel, jnp.maximum(lanes, 0)), -1)
    slots = jnp.where(ok, count + jnp.arange(capacity, dtype=jnp.int32),
                      width)
    lanes_buf = lanes_buf.at[slots].set(rel_lanes, mode="drop")
    pay_buf = pay_buf.at[slots].set(pay, mode="drop")
    c = c if true_count is None else true_count
    c = jnp.where(c > capacity, jnp.maximum(c, width + 1), c)
    return count + c, lanes_buf, pay_buf


def make_sharded_step(compute: Callable, mesh, span_per_shard: int,
                      n_args: int, hit_capacity: int = 64,
                      globalize: Optional[Callable] = None):
    """Build the unified sharded step from a per-shard compute.

    compute(offset, *step_args) -> (found bool[K], payload int32[K]):
    the engine's whole per-shard pipeline (decode -> digest -> compare,
    **including validity masking against its n_valid argument**) over
    the lane block starting at window-relative offset ``offset``
    (int32, traced; in span units -- keyspace lanes for mask-style
    steps, words for wordlist steps).

    A compute may instead return the TILE-compute 4-tuple
    ``(found bool[G], payload int32[G], rel int32[G], count int32)``
    (the fused Pallas kernel contract, ops/pallas_mask.
    make_shard_mask_compute): ``rel`` carries each element's
    window-relative lane directly (the kernel reports one hit lane
    per grid cell, not per lane) and ``count`` is the authoritative
    hit count -- inflated past ``hit_capacity`` when a tile held more
    hits than it can report, which ``_append_hits`` carries past the
    window's width and so into the workers' existing overflow
    redrive.  The arity is inspected at trace time, so legacy 2-tuple
    computes are untouched.

    span_per_shard: span units one shard covers per batch; one step
    call covers ``n_dev * span_per_shard`` (``step.super_span``).

    globalize(local_lane, offset) -> window-relative lane value stored
    in the hit buffer (default ``offset + local_lane``; the wordlist
    step maps its rule-major flat lanes to keyspace offsets here).

    Returns the jitted per-batch step with attributes ``super_span``,
    ``hit_capacity`` (the per-batch step's buffer width a shard),
    ``n_devices`` and ``superstep(inner)`` (cached jitted superstep
    programs -- one per power-of-two ``inner``, each with a buffer
    ``window_capacity(hit_capacity, inner)`` wide a shard; decoders
    read the built width from the buffers' shape).
    """
    n_dev = mesh.devices.size
    span_step = n_dev * span_per_shard
    if globalize is None:
        def globalize(lane, offset):
            return lane + offset

    def _program(inner: int):
        width = window_capacity(hit_capacity, inner)

        def shard_fn(*args):
            dev = lax.axis_index(SHARD_AXIS)
            init = (jnp.int32(0),
                    jnp.full((width,), -1, jnp.int32),
                    jnp.full((width,), -1, jnp.int32))

            def body(i, carry):
                offset = (i * span_step
                          + dev * span_per_shard).astype(jnp.int32)
                out = compute(offset, *args)
                if len(out) == 4:          # TILE-compute (kernel) path
                    found, payload, rel, true_count = out
                else:
                    found, payload = out
                    lanes = jnp.arange(found.shape[0], dtype=jnp.int32)
                    rel = globalize(lanes, offset)
                    true_count = None
                return _append_hits(carry, found, payload, rel,
                                    hit_capacity,
                                    true_count=true_count)

            if inner == 1:
                count, lanes, payload = body(jnp.int32(0), init)
            else:
                count, lanes, payload = lax.fori_loop(0, inner, body,
                                                      init)
            # the ONE collective round of the dispatch: a scalar psum
            # for the unit flag plus ONE all_gather of a shard's count
            # and both buffers as one vector (three gathers of a
            # window-wide buffer come out of the compiler as three
            # collectives; one vector stays one all-reduce with the
            # psum), so the outputs are REPLICATED -- on a multi-host
            # mesh every process reads the full buffers from its local
            # devices (per-shard outputs would only be addressable on
            # the owning host).
            every = lax.all_gather(
                jnp.concatenate([count[None], lanes, payload]),
                SHARD_AXIS)
            return (lax.psum(count, SHARD_AXIS)[None], every[:, 0],
                    every[:, 1:1 + width], every[:, 1 + width:])

        sharded = shard_map(
            shard_fn, mesh=mesh, in_specs=(P(),) * n_args,
            out_specs=(P(), P(), P(), P()), check_vma=False)

        @jax.jit
        def step(*args):
            total, counts, lanes, payload = sharded(*args)
            return total[0], counts, lanes, payload

        return step

    step = _program(1)
    programs = {1: step}

    def superstep(inner: int):
        """The fused program covering ``inner`` consecutive batches in
        one dispatch (one collective round, device-resident hit
        accumulation).  Cached per inner -- callers pick power-of-two
        sizes so the compile count stays log-bounded."""
        p = programs.get(inner)
        if p is None:
            p = programs[inner] = _program(inner)
        return p

    step.superstep = superstep
    step.super_span = span_step
    step.hit_capacity = hit_capacity
    step.n_devices = n_dev
    return step


# ---------------------------------------------------------------------------
# compute builders: the per-family math the runtime wraps.  Wordlist
# and combinator computes live next to their single-device twins
# (ops/rules_pipeline.py, ops/combine.py); these two cover every
# digest_candidates engine and the whole per-target salted family.

def probe_lane_compare(targets, n_lanes: int):
    """Shared probe-table verify stage for sharded computes: build
    ``fn(digest, maybe) -> (found, tpos)`` over an ``n_lanes``-lane
    digest block, where ``maybe`` is the (validity-masked) Bloom
    survivor mask.  Used by the mask, wordlist, and combinator
    computes so the survivor-compaction / sentinel discipline exists
    exactly once.

    Device layout: survivors compact into a fixed buffer, their
    digests re-gather and verify exactly against the sorted table; a
    survivor overflow could hide a real hit past the buffer, so THAT
    batch degrades to sentinel-tagged maybes.  Host-verify layout
    (no exact table on device): every survivor goes back
    sentinel-tagged (tpos == num_targets, out of range) and the
    workers resolve each with one oracle hash."""
    survivors = 0
    if targets.table is not None:
        from dprf_tpu.targets import probe as probe_mod
        survivors = probe_mod.survivor_cap(targets, n_lanes)
    sentinel = targets.num_targets

    def fn(digest, maybe):
        if targets.table is None:
            return maybe, jnp.full((n_lanes,), sentinel, jnp.int32)
        n_maybe = maybe.sum(dtype=jnp.int32)
        slot = jnp.cumsum(maybe.astype(jnp.int32)) - 1
        slot = jnp.where(maybe, slot, survivors)
        surv = jnp.full((survivors,), -1, jnp.int32).at[slot].set(
            jnp.arange(n_lanes, dtype=jnp.int32), mode="drop")
        found_s, tpos_s = cmp_ops.compare_multi(
            digest[jnp.maximum(surv, 0)], targets.table)
        found_s = found_s & (surv >= 0)
        back = jnp.where(surv >= 0, surv, n_lanes)
        verified = jnp.zeros((n_lanes,), bool).at[back].set(
            found_s, mode="drop")
        tpos = jnp.zeros((n_lanes,), jnp.int32).at[back].set(
            tpos_s, mode="drop")
        overflow = n_maybe > survivors
        found = jnp.where(overflow, maybe, verified)
        tpos = jnp.where(overflow,
                         jnp.full((n_lanes,), sentinel, jnp.int32),
                         tpos)
        return found, tpos

    return fn


def make_sharded_kernel_mask_step(engine_name: str, gen,
                                  target_words, mesh,
                                  batch_per_device: int,
                                  hit_capacity: int = 64,
                                  sub=None, interpret: bool = False,
                                  probe_fp: Optional[float] = None):
    """Mask attack with the FUSED PALLAS KERNEL as the per-shard
    compute: the whole decode -> hash -> compare(+probe) chain runs
    in VMEM per shard, and the sharded superstep drives it with
    on-device generation from ``base + shard/window offset``.

    Same step/superstep contract as make_sharded_mask_step; the hit
    payload is tpos 0 (single target) or the SENTINEL num_targets
    (multi target -- every kernel-probe survivor is host-verified
    with one oracle hash, see ops/pallas_mask.make_shard_mask_compute).
    batch_per_device must be tile-aligned (check_batch enforces)."""
    from dprf_tpu.ops import pallas_mask

    compute = pallas_mask.make_shard_mask_compute(
        engine_name, gen, target_words, batch_per_device, hit_capacity,
        sub=sub, interpret=interpret, probe_fp=probe_fp)
    step = make_sharded_step(compute, mesh, batch_per_device, 2,
                             hit_capacity=hit_capacity)
    step.super_batch = step.super_span
    step.tile = compute.tile
    return step


def make_sharded_mask_step(engine, gen, targets, mesh,
                           batch_per_device: int, hit_capacity: int = 64,
                           widen_utf16: bool = False):
    """Mask attack through the unified runtime: any engine exposing
    ``digest_candidates`` (single- or multi-target).

    step(base_digits int32[L], n_valid int32) ->
        (total, counts[n_dev], lanes[n_dev, cap], tpos[n_dev, cap])
    with window-relative lanes; ``step.superstep(inner)`` fuses inner
    batches per dispatch (on-device generation via ``decode_batch``'s
    traced lane_offset -- no host digits per batch, no reshard).

    Bulk lists arrive as a ``targets.probe.ProbeTable``: its Bloom
    bitmap and exact-verify buckets are closure constants of the
    shard function, so they ride through every superstep as
    REPLICATED device state (no per-dispatch transfer).  Lanes the
    device cannot verify exactly -- the host-verify layout, or a
    survivor-buffer overflow -- come back with target pos ==
    num_targets (out of range), which the workers' lane decode
    resolves with one oracle hash each.
    """
    from dprf_tpu.targets import probe as probe_mod

    flat = gen.flat_charsets
    length = gen.length
    B = batch_per_device
    multi = isinstance(targets, cmp_ops.TargetTable)
    probe = isinstance(targets, probe_mod.ProbeTable)
    _probe_compute = probe_lane_compare(targets, B) if probe else None

    def compute(offset, base_digits, n_valid):
        cand = gen.decode_batch(base_digits, flat, B, lane_offset=offset)
        if widen_utf16:
            cand = jnp.reshape(
                jnp.stack([cand, jnp.zeros_like(cand)], axis=-1),
                (B, 2 * length))
            digest = engine.digest_candidates(cand, 2 * length)
        else:
            digest = engine.digest_candidates(cand, length)
        lane = offset + jnp.arange(B, dtype=jnp.int32)
        if probe:
            return _probe_compute(
                digest, probe_mod.bloom_maybe(digest, targets)
                & (lane < n_valid))
        if multi:
            found, tpos = cmp_ops.compare_multi(digest, targets)
        else:
            found = cmp_ops.compare_single(digest, targets)
            tpos = jnp.zeros((B,), jnp.int32)
        return found & (lane < n_valid), tpos

    step = make_sharded_step(compute, mesh, B, 2,
                             hit_capacity=hit_capacity)
    step.super_batch = step.super_span
    return step


def make_sharded_pertarget_step(gen, mesh, batch_per_device: int,
                                digest_fn, n_params: int,
                                hit_capacity: int = 64):
    """Per-target-sweep engines (phpass / crypt family / pbkdf2 /
    mscache / hmac / salted / krb5 style) through the unified runtime:
    ``digest_fn(cand, lens, *params)`` computes the digest words; the
    LAST step argument is the target word vector.

    step(base_digits, n_valid, *params, target) ->
        (total, counts[n_dev], lanes[n_dev, cap], _)
    """
    flat = gen.flat_charsets
    length = gen.length
    B = batch_per_device

    def compute(offset, base_digits, n_valid, *args):
        *params, target = args
        cand = gen.decode_batch(base_digits, flat, B, lane_offset=offset)
        lens = jnp.full((B,), length, jnp.int32)
        digest = digest_fn(cand, lens, *params)
        lane = offset + jnp.arange(B, dtype=jnp.int32)
        found = cmp_ops.compare_single(digest, target) & (lane < n_valid)
        return found, jnp.zeros((B,), jnp.int32)

    step = make_sharded_step(compute, mesh, B, 3 + n_params,
                             hit_capacity=hit_capacity)
    step.super_batch = step.super_span
    return step
