"""Device scrypt engine: the HBM-scale memory-hard path.

ROMix pins V = N x 128r bytes per candidate in HBM (16 MB each at the
common 16384:8:1), so unlike every other engine the batch here is
bounded by device memory: worker construction clamps the batch to
DPRF_SCRYPT_MEM bytes of V (default 4 GiB) and logs when it does.
N, r, p are trace-time constants -- steps are compiled per distinct
parameter tuple and shared by every target using it; the salt stays a
runtime argument.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from dprf_tpu.engines import register
from dprf_tpu.engines.cpu.engines import (PBKDF2_SALT_MAX as SALT_MAX,
                                          ScryptEngine)
from dprf_tpu.engines.device.salted import (SaltedMaskWorker,
                                            SaltedWordlistWorker,
                                            ShardedSaltedMaskWorker,
                                            _SaltedWorkerBase)
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops.hmac import pack_raw_varlen
from dprf_tpu.ops.scrypt import scrypt_dk
from dprf_tpu.utils import env as envreg
from dprf_tpu.utils.logging import DEFAULT as log


def _mem_cap() -> int:
    return envreg.get_int("DPRF_SCRYPT_MEM")


def _clamp_batch(batch: int, targets: Sequence, what: str) -> int:
    """Bound the batch so the largest target's V array fits the cap."""
    worst = max(128 * t.params["r"] * t.params["n"] for t in targets)
    cap = max(8, _mem_cap() // worst)
    if batch > cap:
        log.info(f"scrypt: clamping {what} to fit ROMix memory",
                 requested=batch, clamped=cap,
                 v_bytes_per_candidate=worst)
        return cap
    return batch


def make_scrypt_mask_step(gen, batch: int, n: int, r: int, p: int,
                          hit_capacity: int = 64):
    """step(base_digits, n_valid, salt, salt_len, target) ->
    (count, lanes, _) -- the salted-step contract."""
    flat = gen.flat_charsets
    length = gen.length

    @jax.jit
    def step(base_digits, n_valid, salt, salt_len, target):
        cand = gen.decode_batch(base_digits, flat, batch)
        lengths = jnp.full((batch,), length, jnp.int32)
        kw = pack_raw_varlen(cand, lengths, big_endian=True)
        dk = scrypt_dk(kw, salt, salt_len, n, r, p)
        found = cmp_ops.compare_single(dk, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step


def make_scrypt_wordlist_step(gen, word_batch: int, n: int, r: int,
                              p: int, hit_capacity: int = 64):
    from jax import lax

    from dprf_tpu.ops.rules_pipeline import expand_rules

    B, L = word_batch, gen.max_len
    words_np, lens_np = gen.packed_words(pad_to=B,
                                         min_size=gen.n_words + B - 1)
    words_dev = jnp.asarray(words_np)
    lens_dev = jnp.asarray(lens_np)
    rules = gen.rules

    @jax.jit
    def step(w0, n_valid_words, salt, salt_len, target):
        wslice = lax.dynamic_slice(words_dev, (w0, 0), (B, L))
        lslice = lax.dynamic_slice(lens_dev, (w0,), (B,))
        base_valid = jnp.arange(B, dtype=jnp.int32) < n_valid_words
        cw, cl, cv = expand_rules(rules, wslice, lslice, base_valid, L)
        kw = pack_raw_varlen(cw, cl, big_endian=True)
        dk = scrypt_dk(kw, salt, salt_len, n, r, p)
        found = cmp_ops.compare_single(dk, target) & cv
        return cmp_ops.compact_hits(found, jnp.zeros_like(cl),
                                    hit_capacity)

    return step


def make_sharded_scrypt_mask_step(gen, mesh, batch_per_device: int,
                                  n: int, r: int, p: int,
                                  hit_capacity: int = 64):
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from dprf_tpu.parallel.mesh import SHARD_AXIS

    flat = gen.flat_charsets
    length = gen.length
    B = batch_per_device

    def shard_fn(base_digits, n_valid, salt, salt_len, target):
        dev = lax.axis_index(SHARD_AXIS)
        offset = (dev * B).astype(jnp.int32)
        cand = gen.decode_batch(base_digits, flat, B, lane_offset=offset)
        lengths = jnp.full((B,), length, jnp.int32)
        kw = pack_raw_varlen(cand, lengths, big_endian=True)
        dk = scrypt_dk(kw, salt, salt_len, n, r, p)
        lane_global = offset + jnp.arange(B, dtype=jnp.int32)
        found = cmp_ops.compare_single(dk, target) & \
            (lane_global < n_valid)
        count, lanes, tpos = cmp_ops.compact_hits(
            found, jnp.zeros((B,), jnp.int32), hit_capacity)
        lanes = jnp.where(lanes >= 0, lanes + offset, lanes)
        total = lax.psum(count, SHARD_AXIS)
        return (total[None],
                lax.all_gather(count, SHARD_AXIS),
                lax.all_gather(lanes, SHARD_AXIS),
                lax.all_gather(tpos, SHARD_AXIS))

    sharded = shard_map(
        shard_fn, mesh=mesh, in_specs=(P(),) * 5,
        out_specs=(P(), P(), P(), P()), check_vma=False)

    @jax.jit
    def step(base_digits, n_valid, salt, salt_len, target):
        total, counts, lanes, tpos = sharded(base_digits, n_valid, salt,
                                             salt_len, target)
        return total[0], counts, lanes, tpos

    step.super_batch = mesh.devices.size * B
    return step


class _ScryptStepsMixin:
    """Per-(N, r, p) compiled steps shared by targets with identical
    parameters; _invoke routes each target to its step."""

    SALT_WIDTH = SALT_MAX      # u1_block's 51-byte PBKDF2 salt buffer

    def _build_steps(self, factory):
        cache: dict = {}
        self._steps = []
        for t in self.targets:
            key = (t.params["n"], t.params["r"], t.params["p"])
            if key not in cache:
                cache[key] = factory(*key)
            self._steps.append(cache[key])

    def _invoke(self, ti: int, base, n):
        salt, salt_len, tgt = self._targs[ti]
        return self._steps[ti](base, n, salt, salt_len, tgt)


class ScryptMaskWorker(_ScryptStepsMixin, SaltedMaskWorker):
    def __init__(self, engine, gen, targets, batch: int = 1 << 10,
                 hit_capacity: int = 64, oracle=None):
        batch = _clamp_batch(batch, targets, "batch")
        _SaltedWorkerBase.__init__(self, engine, gen, targets, batch,
                                   hit_capacity, oracle)
        self.stride = batch
        self._build_steps(
            lambda n, r, p: make_scrypt_mask_step(gen, batch, n, r, p,
                                                  hit_capacity))


class ScryptWordlistWorker(_ScryptStepsMixin, SaltedWordlistWorker):
    def __init__(self, engine, gen, targets, batch: int = 1 << 10,
                 hit_capacity: int = 64, oracle=None):
        # a dispatch materializes word_batch * n_rules candidates' V
        # arrays, so the clamp must bound that product, not the nominal
        # batch; a rule file bigger than the whole memory budget cannot
        # be subdivided (word_batch floors at 1) and is an error
        batch = _clamp_batch(batch, targets, "batch")
        if gen.n_rules > batch:
            raise ValueError(
                f"scrypt: {gen.n_rules} rules expand one word to more "
                f"candidates than the ROMix memory budget allows "
                f"({batch}; raise DPRF_SCRYPT_MEM or split the rules)")
        _SaltedWorkerBase.__init__(self, engine, gen, targets, batch,
                                   hit_capacity, oracle)
        self.word_batch = max(1, batch // gen.n_rules)
        self.stride = self.word_batch * gen.n_rules
        self._build_steps(
            lambda n, r, p: make_scrypt_wordlist_step(
                gen, self.word_batch, n, r, p, hit_capacity))


class ShardedScryptMaskWorker(_ScryptStepsMixin, ShardedSaltedMaskWorker):
    def __init__(self, engine, gen, targets, mesh,
                 batch_per_device: int = 1 << 10, hit_capacity: int = 64,
                 oracle=None):
        batch_per_device = _clamp_batch(batch_per_device, targets,
                                        "batch_per_device")
        _SaltedWorkerBase.__init__(self, engine, gen, targets,
                                   mesh.devices.size * batch_per_device,
                                   hit_capacity, oracle)
        self.mesh = mesh
        self.stride = self.batch
        self._build_steps(
            lambda n, r, p: make_sharded_scrypt_mask_step(
                gen, mesh, batch_per_device, n, r, p, hit_capacity))


@register("scrypt", device="jax")
class JaxScryptEngine(ScryptEngine):
    """Device scrypt.  Inherits parsing and the oracle hash_batch from
    the CPU engine; adds the ROMix device pipeline workers."""

    little_endian = False      # dk words are big-endian SHA-256 output
    digest_words = 8

    def make_mask_worker(self, gen, targets, batch: int, hit_capacity: int,
                         oracle=None):
        return ScryptMaskWorker(self, gen, targets, batch=batch,
                                hit_capacity=hit_capacity, oracle=oracle)

    def make_wordlist_worker(self, gen, targets, batch: int,
                             hit_capacity: int, oracle=None):
        return ScryptWordlistWorker(self, gen, targets, batch=batch,
                                    hit_capacity=hit_capacity,
                                    oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        return ShardedScryptMaskWorker(self, gen, targets, mesh,
                                       batch_per_device=batch_per_device,
                                       hit_capacity=hit_capacity,
                                       oracle=oracle)

    make_sharded_wordlist_worker = None
    make_combinator_worker = None
    make_sharded_combinator_worker = None
