"""Device SHA3 / Keccak family engines (hashcat 17300-18000):
sha3-224/256/384/512 and raw keccak-224/256/384/512, one generalized
single-block sponge with (rate, pad byte, digest width) per variant.

Keccak's sponge padding is its own thing, so these engines do not ride
the Merkle-Damgard packers: the fused step decodes candidates and
feeds raw bytes plus per-lane lengths straight into
ops/keccak.keccak_words (which pads in-kernel).  Multi-target lists
reuse the sorted-table compare the fast MD engines use."""

from __future__ import annotations

from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from dprf_tpu.engines import register
from dprf_tpu.engines.cpu.engines import Keccak256Engine, Sha3_256Engine
from dprf_tpu.engines.device.engines import GenericWorkerFactories
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops.keccak import keccak_words
from dprf_tpu.runtime.worker import (DeviceWordlistWorker,
                                     MaskWorkerBase)


def make_keccak_mask_step(gen, tgt, batch: int, pad_byte: int,
                          hit_capacity: int = 64, rate: int = 136,
                          out_bytes: int = 32):
    """tgt: single-target words uint32[out_bytes//4] (7 for the 224
    variants, 16 for 512) or a multi-target sorted table from
    cmp_ops.make_target_table."""
    flat = gen.flat_charsets
    length = gen.length
    multi = isinstance(tgt, cmp_ops.TargetTable)

    @jax.jit
    def step(base_digits, n_valid):
        cand = gen.decode_batch(base_digits, flat, batch)
        lengths = jnp.full((batch,), length, jnp.int32)
        digest = keccak_words(cand, lengths, pad_byte=pad_byte,
                              rate=rate, out_bytes=out_bytes)
        if multi:
            found, tpos = cmp_ops.compare_multi(digest, tgt)
        else:
            found = cmp_ops.compare_single(digest, jnp.asarray(tgt))
            tpos = jnp.zeros((batch,), jnp.int32)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, tpos, hit_capacity)

    return step


def make_keccak_wordlist_step(gen, tgt, word_batch: int, pad_byte: int,
                              hit_capacity: int = 64, rate: int = 136,
                              out_bytes: int = 32):
    from dprf_tpu.ops.rules_pipeline import expand_rules

    B, L = word_batch, gen.max_len
    words_np, lens_np = gen.packed_words(pad_to=B,
                                         min_size=gen.n_words + B - 1)
    words_dev = jnp.asarray(words_np)
    lens_dev = jnp.asarray(lens_np)
    rules = gen.rules
    multi = isinstance(tgt, cmp_ops.TargetTable)

    @jax.jit
    def step(w0, n_valid_words):
        wslice = lax.dynamic_slice(words_dev, (w0, 0), (B, L))
        lslice = lax.dynamic_slice(lens_dev, (w0,), (B,))
        base_valid = jnp.arange(B, dtype=jnp.int32) < n_valid_words
        cw, cl, cv = expand_rules(rules, wslice, lslice, base_valid, L)
        pos = jnp.arange(cw.shape[1], dtype=jnp.int32)
        cw = jnp.where(pos[None, :] < cl[:, None], cw, 0)  # mask junk
        digest = keccak_words(cw, cl, pad_byte=pad_byte, rate=rate,
                              out_bytes=out_bytes)
        if multi:
            found, tpos = cmp_ops.compare_multi(digest, tgt)
        else:
            found = cmp_ops.compare_single(digest, jnp.asarray(tgt))
            tpos = jnp.zeros_like(cl)
        return cmp_ops.compact_hits(found & cv, tpos, hit_capacity)

    return step


class _KeccakTargetsMixin:
    """Single- or multi-target setup with the sorted-table compare."""

    def _setup_keccak(self, engine, gen, targets, hit_capacity, oracle):
        self.engine = engine
        self.gen = gen
        self.targets = list(targets)
        self.hit_capacity = hit_capacity
        self.oracle = oracle
        digests = [t.digest for t in self.targets]
        self.multi = len(digests) > 1
        if self.multi:
            table = cmp_ops.make_target_table(digests,
                                              little_endian=False)
            self._order = table.order
            return table
        self._order = np.zeros(1, dtype=np.int64)
        return np.frombuffer(digests[0], ">u4").astype(np.uint32)


class KeccakMaskWorker(_KeccakTargetsMixin, MaskWorkerBase):
    def __init__(self, engine, gen, targets, batch: int = 1 << 18,
                 hit_capacity: int = 64, oracle=None):
        tgt = self._setup_keccak(engine, gen, targets, hit_capacity,
                                 oracle)
        self.batch = self.stride = batch
        self.step = make_keccak_mask_step(
            gen, tgt, batch, engine._pad_byte, hit_capacity,
            rate=engine._rate, out_bytes=engine.digest_size)


class PallasKeccakMaskWorker(_KeccakTargetsMixin, MaskWorkerBase):
    """Single-target mask worker over the fused Keccak kernel
    (ops/pallas_keccak.py): the whole decode->sponge->compare chain
    stays in VMEM.  Wide-step capable like the MD kernels."""

    SUPER_MODE = "wide"

    def __init__(self, engine, gen, targets, batch: int = 1 << 18,
                 hit_capacity: int = 64, oracle=None,
                 interpret: bool = False):
        from dprf_tpu.ops.pallas_keccak import SUBK

        tgt = self._setup_keccak(engine, gen, targets, hit_capacity,
                                 oracle)
        if self.multi:
            raise ValueError("keccak kernel is single-target")
        tile = SUBK * 128
        batch = max(tile, (batch // tile) * tile)
        self.batch = self.stride = batch
        self._tgt_words = np.asarray(tgt)
        self._interpret = interpret
        self.step = self._make_step(batch)

    def _make_step(self, batch: int):
        from dprf_tpu.ops.pallas_keccak import (
            make_pallas_keccak_crack_step)
        from dprf_tpu.ops.superstep import window_capacity
        cap = window_capacity(self.hit_capacity, batch // self.batch)
        e = self.engine
        return make_pallas_keccak_crack_step(
            self.gen, self._tgt_words, batch, e._pad_byte,
            e._rate, e.digest_size, cap, interpret=self._interpret)


class KeccakWordlistWorker(_KeccakTargetsMixin, DeviceWordlistWorker):
    def __init__(self, engine, gen, targets, batch: int = 1 << 18,
                 hit_capacity: int = 64, oracle=None):
        tgt = self._setup_keccak(engine, gen, targets, hit_capacity,
                                 oracle)
        self.word_batch = max(1, batch // gen.n_rules)
        self.stride = self.word_batch * gen.n_rules
        self.batch = batch
        self.step = make_keccak_wordlist_step(
            gen, tgt, self.word_batch, engine._pad_byte, hit_capacity,
            rate=engine._rate, out_bytes=engine.digest_size)


class _KeccakDeviceMixin(GenericWorkerFactories):
    little_endian = False
    digest_words = 8
    _pad_byte: int
    _rate = 136

    def digest_candidates(self, cand, lengths):
        """The generic-factory hook (JaxEngineBase.digest_candidates):
        sponge framing instead of MD packing, so the sharded and
        combinator factories serve this family unchanged."""
        if isinstance(lengths, int):
            lengths = jnp.full((cand.shape[0],), lengths, jnp.int32)
        return keccak_words(cand, lengths, pad_byte=self._pad_byte,
                            rate=self._rate, out_bytes=self.digest_size)

    def make_mask_worker(self, gen, targets, batch: int, hit_capacity: int,
                         oracle=None):
        from dprf_tpu.ops.pallas_keccak import keccak_kernel_eligible
        from dprf_tpu.ops.pallas_mask import pallas_mode
        from dprf_tpu.utils.logging import DEFAULT as log
        mode = pallas_mode()
        if mode is not None and not keccak_kernel_eligible(
                gen, len(targets), self._rate):
            # weak-spot visibility, as in engines.py: --impl auto users
            # should be able to tell which path ran without reading
            # result JSON
            log.info("keccak kernel not eligible for this job; "
                     "using the XLA pipeline", engine=self.name,
                     targets=len(targets))
        elif mode is not None:
            w = PallasKeccakMaskWorker(self, gen, targets,
                                       batch=batch,
                                       hit_capacity=hit_capacity,
                                       oracle=oracle, **mode)
            w.warmup()
            return w
        return KeccakMaskWorker(self, gen, targets, batch=batch,
                                hit_capacity=hit_capacity, oracle=oracle)

    def make_wordlist_worker(self, gen, targets, batch: int,
                             hit_capacity: int, oracle=None):
        return KeccakWordlistWorker(self, gen, targets, batch=batch,
                                    hit_capacity=hit_capacity,
                                    oracle=oracle)

    # the generic multi-chip / combinator workers (inherited from
    # GenericWorkerFactories) ride the digest_candidates hook
    # (round 4b: previously None -- --devices N and -a combinator on
    # this family errored out)


@register("sha3-256", device="jax")
@register("sha3", device="jax")
class JaxSha3_256Engine(_KeccakDeviceMixin, Sha3_256Engine):
    """Device SHA3-256 (NIST 0x06 padding)."""

    _pad_byte = 0x06


@register("keccak-256", device="jax")
@register("keccak256", device="jax")
class JaxKeccak256Engine(_KeccakDeviceMixin, Keccak256Engine):
    """Device original Keccak-256 (0x01 padding; Ethereum)."""

    _pad_byte = 0x01


def _register_keccak_device_family():
    """Device sha3-224/384/512 and keccak-224/384/512 on the
    generalized sponge (hashcat 17300/17500/17600/17700/17900/18000);
    the 256 variants are the explicit classes above."""
    from dprf_tpu.engines.cpu.engines import KECCAK_SIZES
    from dprf_tpu.engines import engine_class

    for bits, rate in KECCAK_SIZES:
        for kind, pad in (("sha3", 0x06), ("keccak", 0x01)):
            name = f"{kind}-{bits}"
            cpu_cls = engine_class(name, device="cpu")
            cls = type(f"Jax{kind.title()}{bits}Engine",
                       (_KeccakDeviceMixin, cpu_cls),
                       {"__doc__": cpu_cls.__doc__ + " (device)",
                        "_pad_byte": pad, "_rate": rate,
                        "digest_words": bits // 32})
            register(name, device="jax")(cls)
            if kind == "keccak":
                register(f"keccak{bits}", device="jax")(cls)


_register_keccak_device_family()
