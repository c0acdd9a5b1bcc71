"""JAX device engines: the TPU-native execution backends.

Each engine exposes (a) `digest_packed` -- the raw jit-traceable digest
over packed message words, used by the fused crack pipeline; and (b)
`hash_batch` -- the HashEngine-compatible host API (used by tests and
`--device=jax` verification paths), which round-trips bytes through the
device.

Digest word layouts match the CPU oracles bit-for-bit; tests/test_device_engines.py
checks every engine against the oracle over random candidate batches.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax.numpy as jnp

from dprf_tpu.engines import register
from dprf_tpu.engines.base import DeviceHashEngine, HashEngine
from dprf_tpu.ops import pack as pack_ops
from dprf_tpu.ops.md4 import md4_digest_words
from dprf_tpu.ops.md5 import md5_digest_words
from dprf_tpu.ops.sha1 import sha1_digest_words
from dprf_tpu.ops.sha256 import (sha224_digest_words,
                                 sha256_digest_words)
from dprf_tpu.ops.sha512 import sha384_digest_words, sha512_digest_words


class GenericWorkerFactories:
    """Combinator + multi-chip (keyspace DP over a 1-D mesh) worker
    factories over the generic fused steps.  Any engine exposing the
    digest_candidates hook can mix this in (JaxEngineBase and the
    keccak family both do); salted engines (bcrypt, PMKID) override
    with their own sharded pipelines, so every engine exposes the same
    multi-chip surface and `--devices N` never silently degrades to
    one chip."""

    def make_combinator_worker(self, gen, targets, batch: int,
                               hit_capacity: int, oracle=None):
        """Fused combinator/hybrid worker (left x right word tables)."""
        from dprf_tpu.runtime.worker import DeviceCombinatorWorker
        return DeviceCombinatorWorker(self, gen, targets, batch=batch,
                                      hit_capacity=hit_capacity,
                                      oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        """Sharded mask worker; kernel-capable jobs run the FUSED
        PALLAS KERNEL as the per-shard compute (parallel/sharded.
        make_sharded_kernel_mask_step) -- the single-chip
        make_mask_worker routing ladder at mesh scale, with the XLA
        sharded runtime for jobs that are not kernel-eligible.  A
        kernel that fails to build or compile raises.  Bulk lists (probe_eligible) stay on the XLA probe-table
        compute; the in-kernel blocked probe covers 2..MAX_TARGETS
        and needs an oracle to verify its sentinel survivors."""
        from dprf_tpu.ops.pallas_mask import kernel_eligible, pallas_mode
        from dprf_tpu.parallel.worker import ShardedMaskWorker
        from dprf_tpu.targets import probe as probe_mod
        from dprf_tpu.utils.logging import DEFAULT as log
        mode = pallas_mode()
        if mode is not None and probe_mod.probe_eligible(targets, self):
            log.info("bulk target list routes to the sharded "
                     "probe-table XLA pipeline", engine=self.name,
                     targets=len(targets))
        elif mode is not None and not kernel_eligible(self.name, gen,
                                                      len(targets)):
            log.info("pallas kernel not eligible for this sharded "
                     "job; using the XLA pipeline", engine=self.name,
                     targets=len(targets))
        elif mode is not None and len(targets) > 1 and oracle is None:
            log.info("sharded multi-target kernel needs an oracle to "
                     "verify probe survivors; using the XLA pipeline",
                     engine=self.name, targets=len(targets))
        elif mode is not None:
            worker = ShardedMaskWorker(
                self, gen, targets, mesh,
                batch_per_device=batch_per_device,
                hit_capacity=hit_capacity, oracle=oracle,
                kernel=dict(mode))
            worker.warmup()
            return worker
        return ShardedMaskWorker(self, gen, targets, mesh,
                                 batch_per_device=batch_per_device,
                                 hit_capacity=hit_capacity, oracle=oracle)

    def make_sharded_wordlist_worker(self, gen, targets, mesh,
                                     word_batch_per_device: int,
                                     hit_capacity: int, oracle=None):
        from dprf_tpu.parallel.worker import ShardedWordlistWorker
        return ShardedWordlistWorker(
            self, gen, targets, mesh,
            word_batch_per_device=word_batch_per_device,
            hit_capacity=hit_capacity, oracle=oracle)

    def make_sharded_combinator_worker(self, gen, targets, mesh,
                                       batch_per_device: int,
                                       hit_capacity: int, oracle=None):
        from dprf_tpu.parallel.worker import ShardedCombinatorWorker
        return ShardedCombinatorWorker(
            self, gen, targets, mesh,
            batch_per_device=batch_per_device,
            hit_capacity=hit_capacity, oracle=oracle)


class JaxEngineBase(GenericWorkerFactories, DeviceHashEngine, HashEngine):
    """Shared packing + host-convenience layer for single-block engines."""

    #: digest words are little-endian uint32 (MD4/MD5 family) or
    #: big-endian (SHA family); drives target-table layout too.
    little_endian: bool = True
    max_candidate_len = 55
    #: single-block packing limit (55 for 64-byte blocks; 111 for the
    #: SHA-512 family's 128-byte blocks)
    _block_limit = 55
    #: kernel-profile phase mapping (ISSUE 15): substring patterns
    #: matched against device-op names in a jax.profiler capture,
    #: merged OVER telemetry/profiler.py's defaults -- how the
    #: analyzer splits a dispatch's device time into the
    #: generate/hash/compare sub-phases.  Engines whose compiled step
    #: carries distinctive op names (a Pallas custom-call, a
    #: scan-looped compress) refine this per class.
    PROFILE_PHASES: dict = {
        "generate": ("decode_batch", "mixed_radix"),
        "compare": ("compare_digests", "target_table", "bloom"),
        "hash": ("digest_packed", "pack_fixed", "pack_varlen"),
    }

    # -- device path -----------------------------------------------------

    def pack(self, cand: jnp.ndarray, length: int) -> jnp.ndarray:
        """uint8[B, length] candidates -> uint32[B, 16] message words."""
        return pack_ops.pack_fixed(cand, length,
                                   big_endian=not self.little_endian)

    def pack_varlen(self, cand: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        return pack_ops.pack_varlen(cand, lengths,
                                    big_endian=not self.little_endian)

    def digest_candidates(self, cand: jnp.ndarray,
                          lengths) -> jnp.ndarray:
        """uint8[B, L] candidates + int32[B] lengths (or a python int
        for a fixed-length batch) -> digest words.  Default is the
        MD-style pack + compress; engines with non-MD framing (the
        keccak sponge family) override, so the generic sharded /
        combinator / rules factories serve every family through ONE
        hook instead of assuming the block packers."""
        if isinstance(lengths, int):
            words = self.pack(cand, lengths)
        else:
            words = self.pack_varlen(cand, lengths)
        return self.digest_packed(words)

    def make_mask_worker(self, gen, targets, batch: int, hit_capacity: int,
                         oracle=None):
        """Build the fused-pipeline worker for a mask attack on this
        engine.  Engines with special pipelines (PMKID, bcrypt) override
        this -- it is the CLI's single entry into the device path.

        Kernel-capable engines route to the hand-written Pallas kernel
        when eligible (see ops/pallas_mask.pallas_mode): exact
        single-target compare, the Bloom-prefilter multi-target path
        (which needs an oracle to verify maybes -- without one the job
        stays on the generic fused XLA pipeline), or for a bulk list
        (targets/probe.probe_eligible) the kernel's hash with the
        HBM-resident probe table behind it.

        A kernel that fails to build or compile (a Mosaic lowering
        regression, an unexpected shape) raises with the compiler's
        message: the XLA pipeline is 1-2 orders of magnitude slower,
        so a silent switch to it would be a wrong result that still
        "passes".  The warmup here forces the compile at construction.
        """
        from dprf_tpu.ops.pallas_mask import (CORES, kernel_eligible,
                                              pallas_mode)
        from dprf_tpu.targets import probe as probe_mod
        from dprf_tpu.utils.logging import DEFAULT as log
        mode = pallas_mode()
        # a bulk list (probe_eligible) hashes on the kernel too: its
        # body ends at the digest and the probe table, which lives in
        # HBM, is a stage of the same program behind it (PallasMask
        # Worker's bulk mode), so the list's size is no bar
        bulk = (mode is not None and self.name in CORES
                and probe_mod.probe_eligible(targets, self))
        if mode is not None and not kernel_eligible(
                self.name, gen, 1 if bulk else len(targets)):
            # weak-spot visibility: `--impl auto` users otherwise can't
            # tell which path ran without reading the result JSON
            log.info("pallas kernel not eligible for this job; "
                     "using the XLA pipeline", engine=self.name,
                     targets=len(targets))
        elif (mode is not None and len(targets) > 1 and oracle is None
              and not bulk):
            log.info("pallas multi-target kernel needs an oracle to "
                     "verify Bloom maybes; using the XLA pipeline",
                     engine=self.name, targets=len(targets))
        elif mode is not None:
            from dprf_tpu import tune as tune_mod
            from dprf_tpu.runtime.worker import PallasMaskWorker
            # tuned tile size (dprf tune --rungs sub): a cache miss
            # returns None and the kernel default stands
            sub = tune_mod.lookup_tuned_value(
                self.name, "sub", attack="mask",
                extras={"hit_cap": int(hit_capacity)})
            worker = PallasMaskWorker(self, gen, targets, batch=batch,
                                      hit_capacity=hit_capacity,
                                      oracle=oracle, sub=sub, **mode)
            worker.warmup()
            return worker
        from dprf_tpu.runtime.worker import DeviceMaskWorker
        return DeviceMaskWorker(self, gen, targets, batch=batch,
                                hit_capacity=hit_capacity, oracle=oracle)

    def make_wordlist_worker(self, gen, targets, batch: int,
                             hit_capacity: int, oracle=None):
        """Fused wordlist+rules worker (config 3's on-device expansion).
        Single-target jobs whose rule set the in-VMEM interpreter
        kernel supports get the Pallas path (ops/pallas_rules.py);
        a kernel build/compile failure raises."""
        from dprf_tpu.ops.pallas_mask import pallas_mode
        from dprf_tpu.ops.pallas_rules import kernel_rules_eligible
        from dprf_tpu.runtime.worker import DeviceWordlistWorker
        from dprf_tpu.utils.logging import DEFAULT as log
        mode = pallas_mode()
        if (mode is not None
                and kernel_rules_eligible(self.name, gen, len(targets))):
            from dprf_tpu.runtime.worker import PallasWordlistWorker
            worker = PallasWordlistWorker(
                self, gen, targets, batch=batch,
                hit_capacity=hit_capacity, oracle=oracle, **mode)
            worker.warmup()
            return worker
        if mode is not None:
            log.info("rules kernel not eligible for this job; "
                     "using the XLA pipeline", engine=self.name,
                     targets=len(targets))
        return DeviceWordlistWorker(self, gen, targets, batch=batch,
                                    hit_capacity=hit_capacity, oracle=oracle)

    # -- host-facing HashEngine API --------------------------------------

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        maxlen = max((len(c) for c in candidates), default=1) or 1
        # _block_limit is the single-block packing limit; engine-specific
        # max_candidate_len (e.g. NTLM's 27 pre-widening chars) is
        # enforced by callers/overrides on the raw candidate.
        if maxlen > self._block_limit:
            raise ValueError(
                f"{self.name}: candidate longer than the "
                f"{self._block_limit}-byte single-block limit")
        batch = len(candidates)
        buf = np.zeros((batch, maxlen), dtype=np.uint8)
        lengths = np.zeros((batch,), dtype=np.int32)
        for i, c in enumerate(candidates):
            buf[i, :len(c)] = np.frombuffer(c, dtype=np.uint8)
            lengths[i] = len(c)
        words = self.pack_varlen(jnp.asarray(buf), jnp.asarray(lengths))
        digest = np.asarray(self.digest_packed(words))
        dt = "<u4" if self.little_endian else ">u4"
        return [digest[i].astype(dt).tobytes()[:self.digest_size]
                for i in range(batch)]


@register("md5", device="jax")
class JaxMd5Engine(JaxEngineBase):
    name = "md5"
    digest_size = 16
    digest_words = 4
    little_endian = True
    #: the md5 compress body fuses under names carrying the jitted
    #: scope ("md5") on TPU; the Pallas path shows as a custom-call
    PROFILE_PHASES = {
        **JaxEngineBase.PROFILE_PHASES,
        "hash": ("md5",) + JaxEngineBase.PROFILE_PHASES["hash"],
    }

    def digest_packed(self, blocks: jnp.ndarray,
                      lengths=None) -> jnp.ndarray:
        return md5_digest_words(blocks)


@register("sha1", device="jax")
@register("sha-1", device="jax")
class JaxSha1Engine(JaxEngineBase):
    name = "sha1"
    digest_size = 20
    digest_words = 5
    little_endian = False

    def digest_packed(self, blocks: jnp.ndarray,
                      lengths=None) -> jnp.ndarray:
        return sha1_digest_words(blocks)


@register("sha256", device="jax")
@register("sha-256", device="jax")
class JaxSha256Engine(JaxEngineBase):
    name = "sha256"
    digest_size = 32
    digest_words = 8
    little_endian = False

    def digest_packed(self, blocks: jnp.ndarray,
                      lengths=None) -> jnp.ndarray:
        return sha256_digest_words(blocks)


@register("sha224", device="jax")
class JaxSha224Engine(JaxEngineBase):
    """SHA-224: SHA-256 with its own IV, truncated to 28 bytes."""

    name = "sha224"
    digest_size = 28
    digest_words = 7
    little_endian = False

    def digest_packed(self, blocks: jnp.ndarray,
                      lengths=None) -> jnp.ndarray:
        return sha224_digest_words(blocks)


@register("sha512", device="jax")
@register("sha-512", device="jax")
class JaxSha512Engine(JaxEngineBase):
    """SHA-512 over 128-byte blocks; 64-bit words emulated as uint32
    (hi, lo) lane pairs (see ops/sha512.py)."""

    name = "sha512"
    digest_size = 64
    digest_words = 16
    little_endian = False
    max_candidate_len = 111
    _block_limit = 111

    def pack(self, cand: jnp.ndarray, length: int) -> jnp.ndarray:
        return pack_ops.pack_fixed_wide(cand, length)

    def pack_varlen(self, cand: jnp.ndarray,
                    lengths: jnp.ndarray) -> jnp.ndarray:
        return pack_ops.pack_varlen_wide(cand, lengths)

    def digest_packed(self, blocks: jnp.ndarray,
                      lengths=None) -> jnp.ndarray:
        return sha512_digest_words(blocks)


@register("sha384", device="jax")
@register("sha-384", device="jax")
class JaxSha384Engine(JaxSha512Engine):
    name = "sha384"
    digest_size = 48
    digest_words = 12

    def digest_packed(self, blocks: jnp.ndarray,
                      lengths=None) -> jnp.ndarray:
        return sha384_digest_words(blocks)


@register("ntlm", device="jax")
class JaxNtlmEngine(JaxEngineBase):
    """NTLM: MD4 over UTF-16LE.  The fused pipeline widens the latin-1
    candidate bytes to UTF-16LE on device (widen_utf16); the host
    hash_batch path widens here before packing."""

    name = "ntlm"
    digest_size = 16
    digest_words = 4
    little_endian = True
    widen_utf16 = True
    # 27 chars -> 54 UTF-16LE bytes: still one MD4 block.
    max_candidate_len = 27

    def digest_packed(self, blocks: jnp.ndarray,
                      lengths=None) -> jnp.ndarray:
        return md4_digest_words(blocks)

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if any(len(c) > self.max_candidate_len for c in candidates):
            raise ValueError("ntlm: candidate longer than 27 chars")
        widened = [bytes(b for ch in c for b in (ch, 0)) for c in candidates]
        return super().hash_batch(widened, params=params)


@register("ldap-sha", device="jax")
class JaxLdapShaEngine(JaxSha1Engine):
    """LDAP {SHA} (hashcat 101): the unsalted sha1 fast path (incl.
    multi-target compare) with the base64 line format."""

    name = "ldap-sha"

    def parse_target(self, text: str):
        from dprf_tpu.engines.cpu.engines import LdapShaEngine
        return LdapShaEngine().parse_target(text)


@register("ldap-md5", device="jax")
class JaxLdapMd5Engine(JaxMd5Engine):
    """LDAP {MD5}: the unsalted md5 fast path with the base64 line
    format."""

    name = "ldap-md5"

    def parse_target(self, text: str):
        from dprf_tpu.engines.cpu.engines import LdapMd5Engine
        return LdapMd5Engine().parse_target(text)


@register("mysql323", device="jax")
@register("mysql-old", device="jax")
class JaxMysql323Engine(JaxEngineBase):
    """MySQL pre-4.1 OLD_PASSWORD (hashcat 200): an add/xor/shift scan
    over the password bytes.  digest_packed recovers bytes and length
    from the standard big-endian single-block packing (bit count in
    word 15), so every generic pipeline -- mask, wordlist+rules,
    combinator, multi-target table, sharded -- applies unchanged."""

    name = "mysql323"
    digest_size = 8
    digest_words = 2
    little_endian = False

    def digest_packed(self, blocks: jnp.ndarray,
                      lengths=None) -> jnp.ndarray:
        B = blocks.shape[0]
        lens = (blocks[:, 15] // 8).astype(jnp.int32)
        shifts = jnp.asarray([24, 16, 8, 0], jnp.uint32)
        byts = ((blocks[:, :14, None] >> shifts) &
                jnp.uint32(0xFF)).reshape(B, 56)
        nr = jnp.full((B,), jnp.uint32(1345345333))
        nr2 = jnp.full((B,), jnp.uint32(0x12345671))
        add = jnp.full((B,), jnp.uint32(7))
        for i in range(55):
            c = byts[:, i]
            active = ((i < lens) & (c != 0x20) & (c != 0x09))
            nr_n = nr ^ ((((nr & 63) + add) * c) + (nr << 8))
            nr2_n = nr2 + ((nr2 << 8) ^ nr_n)
            add_n = add + c
            nr = jnp.where(active, nr_n, nr)
            nr2 = jnp.where(active, nr2_n, nr2)
            add = jnp.where(active, add_n, add)
        mask31 = jnp.uint32(0x7FFFFFFF)
        return jnp.stack([nr & mask31, nr2 & mask31], axis=1)

    def parse_target(self, text: str):
        from dprf_tpu.engines.cpu.engines import Mysql323Engine
        return Mysql323Engine().parse_target(text)
