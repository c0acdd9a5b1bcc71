"""Salted fast-hash engines: md5/sha1/sha256/sha512 over $pass.$salt
and $salt.$pass (hashcat modes 10/20, 110/120, 1410/1420, 1710/1720).

Target lines use the hashcat convention ``hexdigest:salt`` (the salt is
the literal bytes after the first colon; ``$HEX[..]`` decodes hex
salts).  Salted sweeps are inherently per-target -- each salt reshapes
the digest of every candidate -- so the workers sweep the keyspace once
per target, exactly like bcrypt's; unlike bcrypt, ONE compiled step
serves every target because the salt is a runtime argument (a fixed
buffer + length), not a trace-time constant.

On device the salt is appended (ps) or prepended (sp) to the candidate
with the same vectorized variable-shift select the combinator decode
uses, then flows through the engines' varlen packing -- no new hash
code at all; the compression functions are the ones every other path
shares.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from dprf_tpu.engines import register
from dprf_tpu.engines.base import Target
from dprf_tpu.engines.cpu.engines import SALT_MAX, parse_salted_line
from dprf_tpu.engines.device.engines import (JaxMd5Engine, JaxSha1Engine,
                                             JaxSha256Engine,
                                             JaxSha512Engine)
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.runtime.worker import (Hit, CpuWorker, word_cover_range,
                                     wordlist_lane_to_gidx)
from dprf_tpu.runtime.workunit import WorkUnit

def _salted_concat(cand, length: int, salt, salt_len, order: str,
                   batch: int, salt_width: int = SALT_MAX):
    """cand uint8[B, L] + salt uint8[salt_width] (salt_len valid) ->
    (bytes uint8[B, L + salt_width], lengths int32[B]).  `salt_width`
    is the engine's static salt-buffer width -- SALT_MAX for the
    generic hexdigest:salt modes, 4 for MSSQL's fixed salt, so widened
    candidates don't pay a 32-byte buffer reservation against the
    single-block limit."""
    width = length + salt_width
    pos = jnp.arange(width, dtype=jnp.int32)[None, :]
    if order == "ps":
        out = jnp.zeros((batch, width), jnp.uint8).at[:, :length].set(cand)
        sidx = jnp.clip(pos - length, 0, salt_width - 1)
        svals = jnp.broadcast_to(salt[None, :], (batch, salt_width))
        out = jnp.where(pos < length, out,
                        jnp.take_along_axis(svals, sidx, axis=1))
    else:
        cpad = jnp.zeros((batch, width), jnp.uint8).at[:, :length].set(cand)
        cidx = jnp.clip(pos - salt_len, 0, width - 1)
        cshift = jnp.take_along_axis(cpad, cidx, axis=1)
        svals = jnp.broadcast_to(
            jnp.pad(salt, (0, width - salt_width))[None, :], (batch, width))
        out = jnp.where(pos < salt_len, svals, cshift)
    return out, jnp.full((batch,), length, jnp.int32) + salt_len


def make_salted_mask_step(engine, gen, batch: int, order: str,
                          hit_capacity: int = 64):
    """step(base_digits, n_valid, salt uint8[SALT_MAX], salt_len int32,
    target uint32[W]) -> (count, lanes, _)."""
    flat = gen.flat_charsets
    length = gen.length
    pre = engine.pre_salt
    mult = engine.length_multiplier
    sw = engine.salt_width

    @jax.jit
    def step(base_digits, n_valid, salt, salt_len, target):
        cand = gen.decode_batch(base_digits, flat, batch)
        if pre is not None:
            cand = pre(cand)
        byts, lengths = _salted_concat(cand, length * mult, salt,
                                       salt_len, order, batch, sw)
        words = engine.pack_varlen(byts, lengths)
        digest = engine.digest_packed(words)
        found = cmp_ops.compare_single(digest, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step


def make_salted_wordlist_step(engine, gen, word_batch: int, order: str,
                              hit_capacity: int = 64):
    """Wordlist(+rules) variant; lanes are flat r*B + b indices."""
    from jax import lax

    from dprf_tpu.ops.rules_pipeline import expand_rules

    B, L = word_batch, gen.max_len
    words_np, lens_np = gen.packed_words(pad_to=B,
                                         min_size=gen.n_words + B - 1)
    words_dev = jnp.asarray(words_np)
    lens_dev = jnp.asarray(lens_np)
    rules = gen.rules
    pre = engine.pre_salt
    mult = engine.length_multiplier
    sw = engine.salt_width

    @jax.jit
    def step(w0, n_valid_words, salt, salt_len, target):
        wslice = lax.dynamic_slice(words_dev, (w0, 0), (B, L))
        lslice = lax.dynamic_slice(lens_dev, (w0,), (B,))
        base_valid = jnp.arange(B, dtype=jnp.int32) < n_valid_words
        cw, cl, cv = expand_rules(rules, wslice, lslice, base_valid, L)
        if pre is not None:
            cw = pre(cw)
            cl = cl * mult
        Le = L * mult
        RB = cw.shape[0]
        width = Le + sw
        pos = jnp.arange(width, dtype=jnp.int32)[None, :]
        if order == "ps":
            out = jnp.zeros((RB, width), jnp.uint8).at[:, :Le].set(cw)
            sidx = jnp.clip(pos - cl[:, None], 0, sw - 1)
            svals = jnp.broadcast_to(salt[None, :], (RB, sw))
            out = jnp.where(pos < cl[:, None], out,
                            jnp.take_along_axis(svals, sidx, axis=1))
        else:
            cpad = jnp.zeros((RB, width), jnp.uint8).at[:, :Le].set(cw)
            cidx = jnp.clip(pos - salt_len, 0, width - 1)
            out = jnp.where(
                pos < salt_len,
                jnp.broadcast_to(jnp.pad(salt, (0, width - sw))[None, :],
                                 (RB, width)),
                jnp.take_along_axis(cpad, cidx, axis=1))
        lengths = cl + salt_len
        words = engine.pack_varlen(out, lengths)
        digest = engine.digest_packed(words)
        found = cmp_ops.compare_single(digest, target) & cv
        return cmp_ops.compact_hits(found, jnp.zeros_like(cl),
                                    hit_capacity)

    return step


def make_sharded_salted_mask_step(engine, gen, mesh, batch_per_device: int,
                                  order: str, hit_capacity: int = 64):
    """Multi-chip salted mask step through the ONE sharded runtime:
    only the salt-concat digest math lives here."""
    from dprf_tpu.parallel.sharded import make_sharded_pertarget_step

    length = gen.length
    pre = engine.pre_salt
    mult = engine.length_multiplier
    sw = engine.salt_width

    def digest_fn(cand, lens, salt, salt_len):
        if pre is not None:
            cand = pre(cand)
        byts, lengths = _salted_concat(cand, length * mult, salt,
                                       salt_len, order, cand.shape[0],
                                       sw)
        return engine.digest_packed(engine.pack_varlen(byts, lengths))

    return make_sharded_pertarget_step(gen, mesh, batch_per_device,
                                       digest_fn, 2, hit_capacity)


class _SaltedWorkerBase:
    """Per-target sweep shared by the salted mask/wordlist workers."""

    #: device salt-buffer width; families whose step consumes a wider
    #: runtime salt (e.g. scrypt's 51-byte PBKDF2 buffer) override it
    SALT_WIDTH = SALT_MAX

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int, hit_capacity: int, oracle):
        self.engine = engine
        self.gen = gen
        self.targets = list(targets)
        self.hit_capacity = hit_capacity
        self.oracle = oracle
        self.batch = batch
        self._targs = self._prep_targets()

    def _prep_targets(self):
        """Per-target device state for _invoke: (salt buffer, salt len,
        digest words).  Families whose per-target state is something
        else entirely (zip2's per-target compiled steps over a 10-byte
        auth digest) override this alongside _invoke."""
        dt = "<u4" if self.engine.little_endian else ">u4"
        width = getattr(self.engine, "salt_width", self.SALT_WIDTH)
        targs = []
        for t in self.targets:
            salt = t.params["salt"]
            if len(salt) > width:
                raise ValueError(
                    f"{self.engine.name}: salt of {len(salt)} bytes "
                    f"exceeds the engine's {width}-byte buffer")
            buf = np.zeros((width,), np.uint8)
            buf[:len(salt)] = np.frombuffer(salt, np.uint8)
            targs.append((
                jnp.asarray(buf), jnp.int32(len(salt)),
                jnp.asarray(np.frombuffer(t.digest, dtype=dt)
                            .astype(np.uint32))))
        return targs

    def _rescan(self, start: int, end: int, ti: int) -> list[Hit]:
        if self.oracle is None:
            raise RuntimeError(
                f"hit buffer overflow (> {self.hit_capacity}) and no "
                "oracle engine to rescan with; raise hit_capacity")
        sub = WorkUnit(-1, start, end - start)
        hits = CpuWorker(self.oracle, self.gen,
                         [self.targets[ti]]).process(sub)
        return [Hit(ti, h.cand_index, h.plaintext) for h in hits]

    def _invoke(self, ti: int, base, n):
        """One step call for target ti -- the override point for worker
        families whose per-target state isn't a (salt, target) pair
        (e.g. JWT's per-target compiled steps)."""
        salt, salt_len, tgt = self._targs[ti]
        return self.step(base, n, salt, salt_len, tgt)

    #: wide fusion bounds (see runtime/worker.py MaskWorkerBase): a
    #: wide-capable subclass overrides _wide_invoke to rebuild its
    #: per-target step at inner*stride lanes -- one device program per
    #: ~100 batches instead of per batch, the same dispatch
    #: amortization the Pallas mask workers use.
    SUPER_CAP = 256
    SUPER_MIN = 8

    def _wide_invoke(self, ti: int, base, sbatch: int, n_valid):
        """Wide step call for target ti, or None when not wide-capable
        (the default: per-batch dispatch only)."""
        return None

    def _wide_inner(self, remaining_strides: int) -> int:
        # env flag + int32 cap are worker-lifetime invariants: resolve
        # once (this runs on every iteration of the per-batch sweep)
        cap = getattr(self, "_wide_cap", None)
        if cap is None:
            from dprf_tpu.ops.superstep import max_inner
            from dprf_tpu.utils import env as envreg
            cap = self._wide_cap = (
                0 if not envreg.get_bool("DPRF_SUPERSTEP")
                else max_inner(self.stride, self.SUPER_CAP))
        if cap < self.SUPER_MIN or \
                remaining_strides < self.SUPER_MIN:
            return 0
        return min(cap, 1 << (remaining_strides.bit_length() - 1))

    def _batch_flag(self, result):
        """Scalar that is nonzero iff this batch needs host attention
        (hits or overflow); override with any extra buffers.  See
        runtime/worker.py MaskWorkerBase._batch_flag."""
        return result[0]

    def _accept(self, ti: int, gidx: int, plain: bytes) -> bool:
        """Final say on a device-reported lane.  Workers whose device
        compare is a narrow prefilter (e.g. zip2's 2-byte password
        verification value) override this with an oracle confirmation
        so ~1/2^16 false maybes never leave the worker."""
        return True


def per_target_setup(worker, engine, gen, targets, batch, hit_capacity,
                     oracle):
    """Shared field setup for worker families whose per-target state is
    a COMPILED STEP (JWT's signing input, office's salt+verifier
    blocks) rather than the (salt, digest words) rows
    _SaltedWorkerBase.__init__ prepares."""
    worker.engine = engine
    worker.gen = gen
    worker.targets = list(targets)
    worker.hit_capacity = hit_capacity
    worker.oracle = oracle
    worker.batch = batch


class PerTargetStepsMixin:
    """_invoke for workers holding one compiled step per target."""

    def _invoke(self, ti: int, base, n):
        return self._steps[ti](base, n)


class SaltedMaskWorker(_SaltedWorkerBase):
    def __init__(self, engine, gen, targets, batch: int = 1 << 18,
                 hit_capacity: int = 64, oracle=None):
        super().__init__(engine, gen, targets, batch, hit_capacity, oracle)
        self.stride = batch
        self.step = make_salted_mask_step(engine, gen, batch,
                                          engine.order, hit_capacity)

    def process(self, unit: WorkUnit) -> list[Hit]:
        hits: list[Hit] = []
        for ti in range(len(self.targets)):
            queued = []
            flag = None
            pos = unit.start
            while pos < unit.end:
                inner = self._wide_inner((unit.end - pos) // self.stride)
                window = inner * self.stride if inner >= 2 else 0
                base = jnp.asarray(self.gen.digits(pos), dtype=jnp.int32)
                result = None
                if window:
                    result = self._wide_invoke(ti, base, window,
                                               jnp.int32(window))
                if result is None:         # per-batch dispatch
                    window = min(self.stride, unit.end - pos)
                    result = self._invoke(ti, base, jnp.int32(window))
                # device-accumulated unit flag: one host readback per
                # (target, unit) when nothing hit -- see
                # runtime/worker.py MaskWorkerBase.process
                f = self._batch_flag(result)
                flag = f if flag is None else flag + f
                queued.append((pos, window, result))
                pos += window
            if flag is None or int(flag) == 0:
                continue
            for bstart, window, result in queued:
                hits.extend(self._entry_hits(ti, bstart, window, result,
                                             unit))
        return hits
    # this sweep overlaps internally (queue-then-decode); an
    # inherited submit() would bypass the override
    process._serial_only = True

    def _entry_hits(self, ti: int, bstart: int, window: int, result,
                    unit: WorkUnit) -> list[Hit]:
        """Decode one dispatch's result; a wide window whose buffer
        overflowed re-drives through the per-batch device step so the
        exact host rescan stays one stride wide."""
        count, lanes, _ = result
        count = int(count)
        if count == 0:
            return []
        if count > lanes.shape[0]:     # the step's BUILT buffer size
            if window > self.stride:
                out: list[Hit] = []
                end = min(bstart + window, unit.end)
                for bs in range(bstart, end, self.stride):
                    nv = min(self.stride, end - bs)
                    base = jnp.asarray(self.gen.digits(bs),
                                       dtype=jnp.int32)
                    out.extend(self._entry_hits(
                        ti, bs, nv, self._invoke(ti, base, jnp.int32(nv)),
                        unit))
                return out
            return self._rescan(
                bstart, min(bstart + self.stride, unit.end), ti)
        hits: list[Hit] = []
        for lane in np.asarray(lanes):
            if lane < 0:
                continue
            gidx = bstart + int(lane)
            plain = self.gen.candidate(gidx)
            if self._accept(ti, gidx, plain):
                hits.append(Hit(ti, gidx, plain))
        return hits


class SaltedWordlistWorker(_SaltedWorkerBase):
    def __init__(self, engine, gen, targets, batch: int = 1 << 18,
                 hit_capacity: int = 64, oracle=None):
        super().__init__(engine, gen, targets, batch, hit_capacity, oracle)
        self.word_batch = max(1, batch // gen.n_rules)
        self.stride = self.word_batch * gen.n_rules
        self.step = make_salted_wordlist_step(engine, gen, self.word_batch,
                                              engine.order, hit_capacity)


    def process(self, unit: WorkUnit) -> list[Hit]:
        R = self.gen.n_rules
        w_start, w_end = word_cover_range(unit, R)
        hits: list[Hit] = []
        for ti in range(len(self.targets)):
            queued = []
            flag = None
            for ws in range(w_start, w_end, self.word_batch):
                nw = min(self.word_batch, w_end - ws, self.gen.n_words - ws)
                if nw <= 0:
                    break
                result = self._invoke(ti, jnp.int32(ws), jnp.int32(nw))
                # device-accumulated unit flag (see mask worker above)
                f = self._batch_flag(result)
                flag = f if flag is None else flag + f
                queued.append((ws, nw, result))
            if flag is None or int(flag) == 0:
                continue
            for ws, nw, (count, lanes, _) in queued:
                count = int(count)
                if count == 0:
                    continue
                if count > self.hit_capacity:
                    start = max(unit.start, ws * R)
                    end = min(unit.end, (ws + nw) * R)
                    hits.extend(self._rescan(start, end, ti))
                    continue
                for lane in np.asarray(lanes):
                    if lane < 0:
                        continue
                    gidx = wordlist_lane_to_gidx(int(lane), ws,
                                                 self.word_batch, R)
                    if not unit.start <= gidx < unit.end:
                        continue
                    plain = self.gen.candidate(gidx)
                    if self._accept(ti, gidx, plain):
                        hits.append(Hit(ti, gidx, plain))
        return hits
    # this sweep overlaps internally (queue-then-decode); an
    # inherited submit() would bypass the override
    process._serial_only = True


class PallasSaltedMaskWorker(SaltedMaskWorker):
    """Salted mask sweep over the extended Pallas kernels
    (ops/pallas_ext.py): the whole decode -> concat-salt -> compress
    -> compare chain stays in VMEM, with the salt bytes and target
    digest as RUNTIME scalars -- one compiled kernel per distinct salt
    LENGTH serves the whole hashlist.  Per-target sweep loop, hit
    contract, rescan, and the unit flag all come from
    SaltedMaskWorker; only _invoke changes."""

    def __init__(self, engine, gen, targets, algo: str,
                 batch: int = 1 << 18, hit_capacity: int = 64,
                 oracle=None, interpret: bool = False):
        from dprf_tpu.ops import pallas_ext
        from dprf_tpu.ops.pallas_mask import SUB

        # NOT _SaltedWorkerBase.__init__: its _prep_targets builds
        # per-target (salt buffer, len, digest) device arrays this
        # worker never reads -- _kargs below is the kernel-format
        # equivalent
        self.engine = engine
        self.gen = gen
        self.targets = list(targets)
        self.hit_capacity = hit_capacity
        self.oracle = oracle
        tile = SUB * 128
        batch = max(tile, (batch // tile) * tile)
        self.stride = self.batch = batch
        self._algo = algo
        self._interpret = interpret
        lens = sorted({len(t.params["salt"]) for t in self.targets})
        self._ksteps = {
            n: pallas_ext.make_salted_crack_step(
                algo, engine.order, gen, batch, n, hit_capacity,
                interpret=interpret)
            for n in lens}
        self._wide_ksteps: dict = {}
        # per-target runtime args: salt bytes as int32, target words
        # bit-cast to int32 (SMEM scalars)
        dt = "<u4" if engine.little_endian else ">u4"
        self._kargs = []
        for t in self.targets:
            salt = t.params["salt"]
            self._kargs.append((
                len(salt),
                jnp.asarray(np.frombuffer(salt, np.uint8)
                            .astype(np.int32)),
                jnp.asarray(np.frombuffer(t.digest, dtype=dt)
                            .astype(np.uint32).view(np.int32))))

    def warmup(self) -> None:
        """One launch per COMPILED KERNEL (distinct salt length), not
        per target -- warmup exists to surface compile failures, and a
        10k-target hashlist shares at most a handful of kernels."""
        from dprf_tpu.utils.sync import hard_sync
        base = jnp.asarray(self.gen.digits(0), dtype=jnp.int32)
        by_len = {n: (salt, tgt) for n, salt, tgt in self._kargs}
        for n, (salt, tgt) in by_len.items():
            hard_sync(self._ksteps[n](base, jnp.int32(0), salt, tgt))

    def _invoke(self, ti: int, base, n):
        slen, salt, tgt = self._kargs[ti]
        return self._ksteps[slen](base, n, salt, tgt)

    def _wide_invoke(self, ti: int, base, sbatch: int, n_valid):
        """Wide kernel step at sbatch lanes, cached per (salt length,
        sbatch) -- salt/target stay RUNTIME scalars, so one wide
        program per salt length serves the whole hashlist, exactly
        like the per-batch kernels.  jit/Mosaic compile lazily, so a
        wide program the compiler refuses raises at its first call,
        with the compiler's message."""
        from dprf_tpu.ops import pallas_ext
        slen, salt, tgt = self._kargs[ti]
        key = (slen, sbatch)
        step = self._wide_ksteps.get(key)
        if step is None:
            from dprf_tpu.ops.superstep import window_capacity
            cap = window_capacity(self.hit_capacity,
                                  sbatch // self.batch)
            step = self._wide_ksteps[key] = \
                pallas_ext.make_salted_crack_step(
                    self._algo, self.engine.order, self.gen,
                    sbatch, slen, cap, interpret=self._interpret)
        return step(base, n_valid, salt, tgt)


#: device base class -> kernel core algo for the extended salted
#: kernels (sha512 has no 32-bit core; engines with pre_salt
#: transforms or length multipliers pack differently)
_KERNEL_ALGOS = ((JaxMd5Engine, "md5"), (JaxSha1Engine, "sha1"),
                 (JaxSha256Engine, "sha256"))


def _kernel_algo(engine) -> str | None:
    if engine.pre_salt is not None or engine.length_multiplier != 1:
        return None
    for base, algo in _KERNEL_ALGOS:
        if isinstance(engine, base):
            return algo
    return None


def maybe_pallas_salted_worker(engine, gen, targets, batch: int,
                               hit_capacity: int, oracle):
    """PallasSaltedMaskWorker when the job is kernel-eligible (warmed,
    so a compile failure raises here), else None -- the factory then
    builds the XLA-step worker.  Mirrors JaxEngineBase's pallas
    selection."""
    from dprf_tpu.ops import pallas_ext
    from dprf_tpu.ops.pallas_mask import pallas_mode
    from dprf_tpu.utils.logging import DEFAULT as log

    mode = pallas_mode()
    if mode is None:
        return None
    algo = _kernel_algo(engine)
    lens = [len(t.params["salt"]) for t in targets]
    if algo is None or not pallas_ext.salted_eligible(
            algo, engine.order, gen, lens):
        log.info("salted pallas kernel not eligible for this job; "
                 "using the XLA pipeline", engine=engine.name,
                 targets=len(targets))
        return None
    worker = PallasSaltedMaskWorker(
        engine, gen, targets, algo, batch=batch,
        hit_capacity=hit_capacity, oracle=oracle,
        interpret=mode.get("interpret", False))
    worker.warmup()
    return worker


class ShardedSaltedMaskWorker(SaltedMaskWorker):
    """SaltedMaskWorker over a device mesh: super-batch strides, the
    per-shard overflow check, super-batch-global lanes."""

    def __init__(self, engine, gen, targets, mesh,
                 batch_per_device: int = 1 << 18, hit_capacity: int = 64,
                 oracle=None):
        _SaltedWorkerBase.__init__(self, engine, gen, targets,
                                   mesh.devices.size * batch_per_device,
                                   hit_capacity, oracle)
        self.mesh = mesh
        self.stride = self.batch
        self.step = make_sharded_salted_mask_step(
            engine, gen, mesh, batch_per_device, engine.order,
            hit_capacity)

    def submit(self, unit: WorkUnit):
        """Submit-based per-target sweep (unified sharded runtime):
        ALL (target, batch) dispatches enqueue up front with one
        device-accumulated flag, so the remote worker loop pipelines
        sharded salted units like the fast-hash paths."""
        from dprf_tpu.runtime.worker import PendingUnit
        queued = []
        flag = None
        for ti in range(len(self.targets)):
            for bstart in range(unit.start, unit.end, self.stride):
                n_valid = min(self.stride, unit.end - bstart)
                base = jnp.asarray(self.gen.digits(bstart),
                                   dtype=jnp.int32)
                result = self._invoke(ti, base, jnp.int32(n_valid))
                # device-accumulated unit flag (total is psum'd)
                f = self._batch_flag(result)
                flag = f if flag is None else flag + f
                queued.append(("salt-shard", (ti, bstart), result))
        if flag is not None and hasattr(flag, "copy_to_host_async"):
            flag.copy_to_host_async()
        return PendingUnit(self, unit, queued, flag)

    def _decode_queued(self, kind: str, start, result,
                       unit: WorkUnit) -> list[Hit]:
        ti, bstart = start
        total, counts, lanes, _ = result
        if int(total) == 0:
            return []
        if (np.asarray(counts) > lanes.shape[-1]).any():
            return self._rescan(
                bstart, min(bstart + self.stride, unit.end), ti)
        hits: list[Hit] = []
        for lane in np.asarray(lanes).ravel():
            if lane < 0:
                continue
            gidx = bstart + int(lane)
            plain = self.gen.candidate(gidx)
            if self._accept(ti, gidx, plain):
                hits.append(Hit(ti, gidx, plain))
        return hits

    def process(self, unit: WorkUnit) -> list[Hit]:
        return self.submit(unit).resolve()

    process._submit_based = True   # safe to pipeline via submit()


class _SaltedDeviceMixin:
    """Device engine for one (algo, order): the base engine's packing
    and digest with the salted worker factories."""

    salted = True
    order: str
    #: optional device transform of the candidate bytes BEFORE the salt
    #: is appended (mssql's UTF-16LE widening); uint8[B, L] ->
    #: uint8[B, length_multiplier * L] with every valid byte mapped to
    #: `length_multiplier` output bytes.
    pre_salt = None
    length_multiplier = 1
    #: static device salt-buffer width; engines with a fixed short salt
    #: (MSSQL: 4 bytes) narrow it so the buffer reservation doesn't
    #: count against the single-block limit.
    salt_width = SALT_MAX
    #: leave headroom for any parseable salt in the single block;
    #: the worker factories additionally check ACTUAL salts.  Set per
    #: class in _register_device from the base engine's block limit.
    max_candidate_len = 55 - SALT_MAX

    def parse_target(self, text: str) -> Target:
        digest, salt = parse_salted_line(text, self.digest_size)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt})

    def make_mask_worker(self, gen, targets, batch: int, hit_capacity: int,
                         oracle=None):
        self._check_lengths(gen.length, targets)
        worker = maybe_pallas_salted_worker(self, gen, targets, batch,
                                            hit_capacity, oracle)
        if worker is not None:
            return worker
        return SaltedMaskWorker(self, gen, targets, batch=batch,
                                hit_capacity=hit_capacity, oracle=oracle)

    def make_wordlist_worker(self, gen, targets, batch: int,
                             hit_capacity: int, oracle=None):
        self._check_lengths(gen.max_len, targets)
        return SaltedWordlistWorker(self, gen, targets, batch=batch,
                                    hit_capacity=hit_capacity,
                                    oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        self._check_lengths(gen.length, targets)
        return ShardedSaltedMaskWorker(self, gen, targets, mesh,
                                       batch_per_device=batch_per_device,
                                       hit_capacity=hit_capacity,
                                       oracle=oracle)

    # the generic unsalted sharded wordlist step must NOT be inherited
    # (it would silently ignore the salt); shadow it so the CLI
    # degrades to the single-chip salted worker with a warning instead
    make_sharded_wordlist_worker = None

    # likewise the generic combinator worker compares unsalted digests
    make_combinator_worker = None
    make_sharded_combinator_worker = None

    def _check_lengths(self, cand_len: int, targets) -> None:
        worst = (cand_len * self.length_multiplier
                 + max(len(t.params["salt"]) for t in targets))
        if worst > self._block_limit:
            raise ValueError(
                f"candidate+salt can reach {worst} bytes, over the "
                f"{self._block_limit}-byte single-block limit; "
                "shorten the mask/words")


def _register_device(base_cls, algo: str):
    for order in ("ps", "sp"):
        name = f"{algo}-{order}"
        cls = type(f"Jax{algo.title()}{order.title()}Engine",
                   (_SaltedDeviceMixin, base_cls),
                   {"name": name, "order": order,
                    "__doc__": (f"Salted {algo}: "
                                + ("$pass.$salt" if order == "ps"
                                   else "$salt.$pass")
                                + " appended on device."),
                    "max_candidate_len":
                        base_cls._block_limit - SALT_MAX})
        register(name, device="jax")(cls)


_register_device(JaxMd5Engine, "md5")
_register_device(JaxSha1Engine, "sha1")
_register_device(JaxSha256Engine, "sha256")
_register_device(JaxSha512Engine, "sha512")


@register("postgres", device="jax")
@register("postgres-md5", device="jax")
class JaxPostgresEngine(_SaltedDeviceMixin, JaxMd5Engine):
    """PostgreSQL MD5 auth (hashcat 12): md5($pass.$username) -- the
    salted-md5 'ps' machinery with postgres's line format."""

    name = "postgres"
    order = "ps"

    def parse_target(self, text: str):
        from dprf_tpu.engines.cpu.engines import PostgresMd5Engine
        return PostgresMd5Engine().parse_target(text)


def _register_ldap_salted():
    """LDAP {SSHA}/{SSHA512}/{SMD5} (hashcat 111/1711): the salted
    'ps' device machinery with the LDAP base64 line format -- parsing
    delegates to the CPU engines (same pattern as postgres)."""
    from dprf_tpu.engines.cpu.engines import (LdapSmd5Engine,
                                              LdapSsha512Engine,
                                              LdapSshaEngine)

    for names, base_cls, cpu_cls in (
            (("ldap-ssha", "ssha"), JaxSha1Engine, LdapSshaEngine),
            (("ldap-ssha512", "ssha512"), JaxSha512Engine,
             LdapSsha512Engine),
            (("ldap-smd5",), JaxMd5Engine, LdapSmd5Engine)):
        def make_parse(cpu_cls):
            def parse_target(self, text: str):
                return cpu_cls().parse_target(text)
            return parse_target

        cls = type(f"Jax{cpu_cls.__name__}",
                   (_SaltedDeviceMixin, base_cls),
                   {"name": names[0], "order": "ps",
                    "__doc__": cpu_cls.__doc__ + " (device)",
                    "parse_target": make_parse(cpu_cls),
                    "max_candidate_len":
                        base_cls._block_limit - SALT_MAX})
        for n in names:
            register(n, device="jax")(cls)


_register_ldap_salted()


class _MssqlDeviceMixin(_SaltedDeviceMixin):
    """MSSQL family: the salted 'ps' machinery with a pre-salt
    UTF-16LE widening of the candidate (and an ASCII uppercase first
    for 2000's case-insensitive digest).  The 4-byte salt is appended
    to the WIDENED bytes, unwidened -- which is why this is a pre-salt
    transform, not the engines' widen_utf16 packing flag (that would
    widen the salt too)."""

    order = "ps"
    length_multiplier = 2
    #: MSSQL salts are exactly 4 bytes; a narrow buffer keeps the
    #: widened candidate + salt inside the single block (2*25+4 <= 55).
    salt_width = 4
    _upper = False

    def pre_salt(self, cand):
        from dprf_tpu.ops import pack as pack_ops
        if self._upper:
            cand = jnp.where((cand >= 97) & (cand <= 122),
                             cand - 32, cand).astype(jnp.uint8)
        return pack_ops.utf16le_widen(cand)


@register("mssql2000", device="jax")
class JaxMssql2000Engine(_MssqlDeviceMixin, JaxSha1Engine):
    """MSSQL 2000 (hashcat 131; device)."""

    name = "mssql2000"
    _upper = True
    max_candidate_len = (55 - 4) // 2

    def parse_target(self, text: str):
        from dprf_tpu.engines.cpu.engines import Mssql2000Engine
        return Mssql2000Engine().parse_target(text)


@register("mssql2005", device="jax")
class JaxMssql2005Engine(_MssqlDeviceMixin, JaxSha1Engine):
    """MSSQL 2005 (hashcat 132; device)."""

    name = "mssql2005"
    max_candidate_len = (55 - 4) // 2

    def parse_target(self, text: str):
        from dprf_tpu.engines.cpu.engines import Mssql2005Engine
        return Mssql2005Engine().parse_target(text)


@register("mssql2012", device="jax")
@register("mssql2014", device="jax")
class JaxMssql2012Engine(_MssqlDeviceMixin, JaxSha512Engine):
    """MSSQL 2012/2014 (hashcat 1731; device)."""

    name = "mssql2012"
    max_candidate_len = (111 - 4) // 2

    def parse_target(self, text: str):
        from dprf_tpu.engines.cpu.engines import Mssql2012Engine
        return Mssql2012Engine().parse_target(text)


@register("oracle11", device="jax")
@register("oracle-11g", device="jax")
class JaxOracle11Engine(_SaltedDeviceMixin, JaxSha1Engine):
    """Oracle 11g (hashcat 112): sha1($pass.$salt) -- the salted-sha1
    'ps' machinery with Oracle's S: line format."""

    name = "oracle11"
    order = "ps"
    #: fixed 10-byte salt -> narrow buffer, longer candidates (45)
    salt_width = 10
    max_candidate_len = 55 - 10

    def parse_target(self, text: str):
        from dprf_tpu.engines.cpu.engines import Oracle11Engine
        return Oracle11Engine().parse_target(text)
