"""WPA2-PMKID device engine: the iterated-KDF path (benchmark config 5).

Unlike the fast unsalted engines, PMKID digests depend on per-target
parameters (essid as the PBKDF2 salt; AP/STA MACs in the PMKID
message).  The fused step exploits the job structure: the PMK depends
only on (passphrase, essid), so targets are grouped by essid and the
4096-iteration PBKDF2 runs once per unique essid per candidate; each
target then costs only one extra HMAC (4 compressions) and a 4-word
compare.

A typical PMKID job has one essid and a handful of targets, so the cost
is ~16.4k SHA-1 compressions per candidate -- the low-throughput path
by design.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from dprf_tpu.engines import register
from dprf_tpu.engines.base import Target
from dprf_tpu.engines.cpu.engines import Pmkid2Engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops import pack as pack_ops
from dprf_tpu.ops.hmac_sha1 import pbkdf2_sha1_pmk, pmkid_from_pmk
from dprf_tpu.runtime.worker import DeviceMaskWorker


@register("wpa2-pmkid", device="jax")
@register("pmkid", device="jax")
class JaxPmkidEngine(Pmkid2Engine):
    """Device PMKID engine.  Inherits the CPU engine's target parsing
    (hashcat 16800 lines), oracle hash_batch, and the `iterations`
    count (one shared definition, so oracle and device KDF can never
    silently diverge); adds the device batch computation and the
    fused-worker factories the CLI uses."""

    def pmk_packed(self, key_words: jnp.ndarray, essid: bytes) -> jnp.ndarray:
        """uint32[B, 16] zero-padded passphrase blocks -> uint32[B, 8] PMK."""
        return pbkdf2_sha1_pmk(key_words, essid, self.iterations)

    def pmkid_packed(self, pmk_words: jnp.ndarray,
                     target: Target) -> jnp.ndarray:
        return pmkid_from_pmk(pmk_words, target.params["mac_ap"],
                              target.params["mac_sta"])

    def make_mask_worker(self, gen, targets, batch: int, hit_capacity: int,
                         oracle=None):
        # PBKDF2 is ~16k compressions/candidate; a huge batch only adds
        # latency per step, so cap it well below fast-hash batch sizes.
        worker = maybe_pallas_pmkid_worker(self, gen, targets,
                                           batch=min(batch, 1 << 15),
                                           hit_capacity=hit_capacity,
                                           oracle=oracle)
        if worker is not None:
            return worker
        return PmkidDeviceWorker(self, gen, targets,
                                 batch=min(batch, 1 << 14),
                                 hit_capacity=hit_capacity, oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        """Config 5's pod-scale path: keyspace DP over the mesh."""
        return ShardedPmkidWorker(self, gen, targets, mesh,
                                  batch_per_device=min(batch_per_device,
                                                       1 << 12),
                                  hit_capacity=hit_capacity, oracle=oracle)


def _group_targets(targets: Sequence[Target]):
    """(essid -> target indices, per-target uint32 digest words)."""
    by_essid: dict[bytes, list[int]] = {}
    for i, t in enumerate(targets):
        by_essid.setdefault(t.params["essid"], []).append(i)
    twords = [np.frombuffer(t.digest, dtype=">u4").astype(np.uint32)
              for t in targets]
    return by_essid, twords


def _pmkid_match(engine, targets, by_essid, twords, key, valid):
    """Per-lane match scan, memory FLAT in target count: accumulates a
    match count and the first matching target index per lane instead of
    a [T, B] mask (a 1k-target list at batch 2^14 must not build a
    16M-lane buffer).

    A lane matching >= 2 targets (same passphrase cracking two captures)
    reports only its first target here; the worker resolves the rest
    with the oracle whenever n_multi > 0, so no crack is ever lost.

    Returns (nmatch int32[B], tfirst int32[B])."""
    nmatch = jnp.zeros(valid.shape, jnp.int32)
    tfirst = jnp.full(valid.shape, -1, jnp.int32)
    for essid, tidx in by_essid.items():
        pmk = engine.pmk_packed(key, essid)     # once per essid
        for i in tidx:
            pmkid = engine.pmkid_packed(pmk, targets[i])
            hit = jnp.all(pmkid == jnp.asarray(twords[i]), axis=-1) & valid
            tfirst = jnp.where(hit & (nmatch == 0), jnp.int32(i), tfirst)
            nmatch = nmatch + hit.astype(jnp.int32)
    return nmatch, tfirst


def make_pmkid_crack_step(engine: JaxPmkidEngine, gen: MaskGenerator,
                          targets: Sequence[Target], batch: int,
                          hit_capacity: int = 64):
    """Fused step: index -> passphrase -> PMK (per essid) -> PMKID (per
    target) -> hits.  tpos payload is the ORIGINAL (first-matching)
    target index; n_multi counts lanes matching >= 2 targets.

    step(base_digits, n_valid) -> (count, lanes, tpos, n_multi)."""
    flat = gen.flat_charsets
    length = gen.length
    by_essid, twords = _group_targets(targets)

    @jax.jit
    def step(base_digits: jnp.ndarray, n_valid: jnp.ndarray):
        cand = gen.decode_batch(base_digits, flat, batch)
        key = pack_ops.pack_raw(cand, length, big_endian=True)
        valid = jnp.arange(batch, dtype=jnp.int32) < n_valid
        nmatch, tfirst = _pmkid_match(engine, targets, by_essid, twords,
                                      key, valid)
        count, lanes, tpos = cmp_ops.compact_hits(nmatch > 0, tfirst,
                                                  hit_capacity)
        n_multi = jnp.sum((nmatch > 1).astype(jnp.int32))
        return count, lanes, tpos, n_multi

    return step


def make_sharded_pmkid_crack_step(engine: JaxPmkidEngine,
                                  gen: MaskGenerator,
                                  targets: Sequence[Target], mesh,
                                  batch_per_device: int,
                                  hit_capacity: int = 64):
    """Multi-chip PMKID step (config 5 is the pod-scale sweep): chip c
    owns the lane slice [c*B, (c+1)*B) of each super-batch, runs the
    whole PBKDF2->PMKID->compare chain locally, and psums only the
    scalar hit/multi counts over ICI.

    step(base_digits, n_valid) -> (total, counts[n_dev],
        lanes[n_dev, cap] super-batch-global, tpos[n_dev, cap],
        n_multi_total)."""
    import jax as _jax
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from dprf_tpu.parallel.mesh import SHARD_AXIS

    flat = gen.flat_charsets
    length = gen.length
    by_essid, twords = _group_targets(targets)
    B = batch_per_device

    def shard_fn(base_digits, n_valid):
        dev = lax.axis_index(SHARD_AXIS)
        offset = (dev * B).astype(jnp.int32)
        cand = gen.decode_batch(base_digits, flat, B, lane_offset=offset)
        key = pack_ops.pack_raw(cand, length, big_endian=True)
        lane_global = offset + jnp.arange(B, dtype=jnp.int32)
        valid = lane_global < n_valid
        nmatch, tfirst = _pmkid_match(engine, targets, by_essid, twords,
                                      key, valid)
        count, lanes, tpos = cmp_ops.compact_hits(nmatch > 0, tfirst,
                                                  hit_capacity)
        lanes = jnp.where(lanes >= 0, lanes + offset, lanes)
        total = lax.psum(count, SHARD_AXIS)
        n_multi = lax.psum(jnp.sum((nmatch > 1).astype(jnp.int32)),
                           SHARD_AXIS)
        # replicated hit buffers (see parallel/sharded.py)
        return (total[None],
                lax.all_gather(count, SHARD_AXIS),
                lax.all_gather(lanes, SHARD_AXIS),
                lax.all_gather(tpos, SHARD_AXIS),
                n_multi[None])

    sharded = shard_map(
        shard_fn, mesh=mesh, in_specs=(P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False)

    @_jax.jit
    def step(base_digits: jnp.ndarray, n_valid: jnp.ndarray):
        total, counts, lanes, tpos, n_multi = sharded(base_digits, n_valid)
        return total[0], counts, lanes, tpos, n_multi[0]

    step.super_batch = mesh.devices.size * B
    return step


class PallasPmkidWorker:
    """Per-target PMKID sweep over the fused Pallas PBKDF2 kernel
    (ops/pallas_pbkdf2.py) -- measured 229.9 kH/s at 4096 iterations
    on TPU v5 lite (16,390 SHA-1 compressions a candidate: 3.77 G
    compressions/s) vs 17.4 kH/s through the XLA step.

    The kernel recomputes the PMK per target, so jobs where many
    targets share one ESSID (where the XLA step amortizes the KDF)
    route here only while the per-essid target count stays under the
    kernel's speedup factor -- see maybe_pallas_pmkid_worker.

    Submit-based: every (target, batch) dispatch of a unit is enqueued
    up front with one device-accumulated flag, so UnitPipeline resolves
    a unit behind the next unit's kernel.  What it dispatches is the
    per-batch programs warmup() compiles, one per ESSID length, and
    nothing else: ESSID, PMKID message and digest are ARGUMENTS of the
    program, never constants of it, so every target of one ESSID length
    runs (and finds in the persistent cache) the same compiled
    program."""

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int = 1 << 15, hit_capacity: int = 64,
                 oracle=None):
        from dprf_tpu.ops.pallas_pbkdf2 import (make_pmkid_kernel_step,
                                                target_kernel_args)

        self.engine = engine
        self.gen = gen
        self.targets = list(targets)
        self.hit_capacity = hit_capacity
        self.oracle = oracle
        self._targs = [target_kernel_args(t) for t in self.targets]
        lens = sorted({a[0] for a in self._targs})
        self._steps = {n: make_pmkid_kernel_step(gen, batch, n,
                                                 hit_capacity)
                       for n in lens}
        self.batch = self.stride = next(iter(self._steps.values())).batch
        #: PBKDF2 evaluations dispatched: one a valid lane and target
        #: (the kernel shares no PMK between targets of one ESSID); the
        #: job's `ran` line prints it as `kdf=evals:`
        self.kdf_evals = 0

    #: the steps are always the compiled kernel (describe_worker):
    #: maybe_pallas_pmkid_worker builds this worker on a real chip only
    _interpret = False

    def warmup(self) -> None:
        from dprf_tpu.compilecache import compile_observer
        from dprf_tpu.utils.sync import hard_sync
        base = jnp.asarray(self.gen.digits(0), dtype=jnp.int32)
        by_len = {a[0]: a for a in self._targs}
        with compile_observer(self.engine.name) as obs:
            for n, (el, essid, msg5, tgt) in by_len.items():
                hard_sync(self._steps[n](
                    base, jnp.int32(0), jnp.int32(self.engine.iterations),
                    essid, msg5, tgt))
        self.compile_seconds, self.compile_cache = obs.seconds, obs.cache

    def submit(self, unit):
        from dprf_tpu.runtime.worker import PendingUnit
        iters = jnp.int32(self.engine.iterations)
        batches = [(b, jnp.asarray(self.gen.digits(b), dtype=jnp.int32),
                    min(self.stride, unit.end - b))
                   for b in range(unit.start, unit.end, self.stride)]
        queued, flag = [], None
        for ti, (el, essid, msg5, tgt) in enumerate(self._targs):
            for bstart, base, n_valid in batches:
                result = self._steps[el](base, jnp.int32(n_valid), iters,
                                         essid, msg5, tgt)
                # device-accumulated unit flag: one readback a unit
                flag = result[0] if flag is None else flag + result[0]
                queued.append(("batch", (ti, bstart), result))
                self.kdf_evals += n_valid
        if flag is not None:
            flag.copy_to_host_async()
        return PendingUnit(self, unit, queued, flag)

    def _decode_queued(self, kind: str, start, result, unit) -> list:
        from dprf_tpu.runtime.worker import CpuWorker, Hit
        ti, bstart = start
        count, lanes, _ = result
        count = int(count)
        if count == 0:
            return []
        if count > self.hit_capacity:
            if self.oracle is None:
                raise RuntimeError(
                    "hit buffer overflow and no oracle to "
                    "rescan with; raise hit_capacity")
            end = min(bstart + self.stride, unit.end)
            sub = type(unit)(-1, bstart, end - bstart)
            return [Hit(ti, h.cand_index, h.plaintext)
                    for h in CpuWorker(self.oracle, self.gen,
                                       [self.targets[ti]]).process(sub)]
        gidx = [bstart + int(lane) for lane in np.asarray(lanes)
                if lane >= 0]
        return [Hit(ti, g, self.gen.candidate(g)) for g in gidx]

    def process(self, unit) -> list:
        return self.submit(unit).resolve()

    process._submit_based = True   # safe to pipeline via submit()


def maybe_pallas_pmkid_worker(engine, gen, targets, batch: int,
                              hit_capacity: int, oracle):
    """PallasPmkidWorker when the kernel path wins, else None.

    The kernel is ~9x the XLA step per keyspace sweep but sweeps once
    per TARGET, while the XLA step shares each ESSID's PBKDF2 across
    its targets -- so route to the kernel only while the largest
    same-essid target group stays under the speedup factor."""
    from dprf_tpu.ops.pallas_mask import pallas_mode
    from dprf_tpu.ops.pallas_pbkdf2 import pmkid_kernel_eligible
    from dprf_tpu.utils.logging import DEFAULT as log

    if not targets:
        return None
    # evaluate the routing heuristic BEFORE the backend check so the
    # hermetic suite can exercise it (the mode gate would otherwise
    # shadow it off-TPU)
    lens = [len(t.params["essid"]) for t in targets]
    by_essid, _ = _group_targets(targets)
    max_per_essid = max(len(v) for v in by_essid.values())
    if max_per_essid > 8 or not pmkid_kernel_eligible(gen, lens):
        log.info("pmkid pallas kernel not chosen for this job; "
                 "using the XLA step", targets=len(targets),
                 max_per_essid=max_per_essid)
        return None
    mode = pallas_mode()
    if mode is None or mode.get("interpret", False):
        # TPU-only: the 14 statically-unrolled SHA-1 compressions
        # don't compile on XLA:CPU in reasonable time (the sha256
        # kernel rule)
        return None
    worker = PallasPmkidWorker(engine, gen, targets, batch=batch,
                               hit_capacity=hit_capacity,
                               oracle=oracle)
    worker.warmup()
    return worker


class PmkidDeviceWorker(DeviceMaskWorker):
    """Mask worker over the fused PMKID step (salted multi-target)."""

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int = 1 << 14, hit_capacity: int = 64,
                 oracle=None):
        self._setup_pmkid(engine, gen, targets, hit_capacity, oracle)
        self.batch = self.stride = batch
        self.step = make_pmkid_crack_step(engine, gen, self.targets, batch,
                                          hit_capacity)

    def _setup_pmkid(self, engine, gen, targets, hit_capacity, oracle):
        self.engine = engine
        self.gen = gen
        self.targets = list(targets)
        self.hit_capacity = hit_capacity
        self.oracle = oracle
        # tpos already carries original target indices: identity order.
        self.multi = True
        self._order = np.arange(max(1, len(self.targets)), dtype=np.int64)

    def _resolve_all_targets(self, bstart: int, lanes_np) -> list:
        """Some lane matched >= 2 targets (n_multi > 0): re-check every
        reported lane against EVERY target so the non-first matches are
        not lost.  The expensive PBKDF2 runs once per (lane, essid) --
        the same grouping the device step exploits -- and each target
        then costs one host HMAC, so even 1k targets sharing an essid
        resolve with <= hit_capacity KDF computations."""
        import hashlib as _hl
        import hmac as _hmac

        from dprf_tpu.runtime.worker import Hit
        iters = (self.oracle or self.engine).iterations
        by_essid: dict[bytes, list[int]] = {}
        for i, t in enumerate(self.targets):
            by_essid.setdefault(t.params["essid"], []).append(i)
        hits = []
        for lane in lanes_np:
            if lane < 0:
                continue
            gidx = bstart + int(lane)
            plain = self.gen.candidate(gidx)
            for essid, tidx in by_essid.items():
                pmk = _hl.pbkdf2_hmac("sha1", plain, essid, iters, 32)
                for ti in tidx:
                    t = self.targets[ti]
                    msg = (b"PMK Name" + t.params["mac_ap"]
                           + t.params["mac_sta"])
                    if _hmac.new(pmk, msg, _hl.sha1).digest()[:16] == \
                            t.digest:
                        hits.append(Hit(ti, gidx, plain))
        return hits

    def _batch_hits(self, bstart: int, result, unit,
                    window: int = 0) -> list:
        count, lanes, tpos, n_multi = result
        count = int(count)
        if count == 0:
            return []
        if count > lanes.shape[0]:     # the step's built buffer size
            return self._rescan(bstart, unit, window)
        if int(n_multi):
            return self._resolve_all_targets(bstart, np.asarray(lanes))
        return self._decode_lanes(bstart, np.asarray(lanes),
                                  np.asarray(tpos))


class ShardedPmkidWorker(PmkidDeviceWorker):
    """Multi-chip PMKID worker: the keyspace-DP shard_map step with the
    same hit semantics as the single-chip worker."""

    def __init__(self, engine, gen, targets: Sequence[Target], mesh,
                 batch_per_device: int = 1 << 12, hit_capacity: int = 64,
                 oracle=None):
        self._setup_pmkid(engine, gen, targets, hit_capacity, oracle)
        self.mesh = mesh
        self.batch = self.stride = mesh.devices.size * batch_per_device
        self.step = make_sharded_pmkid_crack_step(
            engine, gen, self.targets, mesh, batch_per_device, hit_capacity)

    def _batch_hits(self, bstart: int, result, unit,
                    window: int = 0) -> list:
        total, counts, lanes, tpos, n_multi = result
        if int(total) == 0:
            return []
        if (np.asarray(counts) > lanes.shape[-1]).any():
            return self._rescan(bstart, unit, window)
        lanes_np = np.asarray(lanes).ravel()
        if int(n_multi):
            return self._resolve_all_targets(bstart, lanes_np)
        return self._decode_lanes(bstart, lanes_np,
                                  np.asarray(tpos).ravel())
