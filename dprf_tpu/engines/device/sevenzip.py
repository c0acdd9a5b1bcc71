"""Device 7-Zip engine (hashcat 11600): fully-fused stored-entry check.

The whole verification runs in one jitted step per target:

- **KDF**: SHA-256 over the 2^cycles concatenated counter units.  The
  stream layout (salt || UTF-16LE pw || LE64 counter, repeating) is
  STATIC for a fixed mask length, so the step walks it in
  lcm(64, unit)-byte groups — each group is a whole number of both
  64-byte SHA blocks and counter units, so every byte's source
  (salt const / candidate column / counter shift) is compile-time
  wiring and the group loop is a `lax.fori_loop` of
  `sha256_compress` calls with zero gathers.
- **AES-256-CBC**: ops/aes.aes_decrypt_blocks (ciphertext and IV are
  target constants, so the CBC xor chain is constant wiring too).
- **CRC32**: vectorized table walk over the decrypted bytes; the
  found-mask compares the full 32-bit CRC, so device hits are exact.

Throughput is KDF-bound (~2^19 * unit/64 SHA-256 compressions per
candidate at the standard cycles=19).  Wordlist attacks fall back to
the CPU oracle (the stream layout is length-dependent, and hashlib's
C loop is genuinely competitive for this shape); mask + sharded mask
are the device paths.
"""

from __future__ import annotations

import math
import struct

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from dprf_tpu.engines import register
from dprf_tpu.engines.base import Target
from dprf_tpu.engines.cpu.sevenzip import SevenZipEngine
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.utils import env as envreg
from dprf_tpu.ops.aes import aes_decrypt_blocks
from dprf_tpu.ops.sha256 import INIT as SHA256_INIT, sha256_compress

#: device-path cap on the encrypted payload: the AES block loop and
#: CRC walk are part of one jitted step, so a multi-KB stored file
#: would explode the trace (aes_decrypt_blocks unrolls 14 rounds per
#: block).  Targets above the cap run on the CPU oracle instead --
#: correct either way, and the KDF (not the payload) dominates cost.
DEVICE_DATA_CAP = envreg.get_int("DPRF_7Z_DEVICE_DATA_CAP")

#: CRC-32 (IEEE 802.3, the zlib polynomial) byte-step table.
_CRC_TABLE = np.zeros(256, np.uint32)
for _i in range(256):
    _c = _i
    for _ in range(8):
        _c = (0xEDB88320 ^ (_c >> 1)) if _c & 1 else _c >> 1
    _CRC_TABLE[_i] = _c


def crc32_batch(data: jnp.ndarray, nbytes: int) -> jnp.ndarray:
    """CRC32 over the first nbytes of uint8[B, N] rows, vectorized:
    a fori_loop of one 256-entry table gather per byte (the loop is
    rolled so the trace stays small whatever the payload size)."""
    tbl = jnp.asarray(_CRC_TABLE)
    c0 = jnp.full((data.shape[0],), 0xFFFFFFFF, jnp.uint32)

    def body(q, c):
        byte = lax.dynamic_slice_in_dim(data, q, 1,
                                        axis=1)[:, 0].astype(jnp.uint32)
        idx = ((c ^ byte) & jnp.uint32(0xFF)).astype(jnp.int32)
        return jnp.take(tbl, idx) ^ (c >> jnp.uint32(8))

    return lax.fori_loop(0, nbytes, body, c0) ^ jnp.uint32(0xFFFFFFFF)


def sevenzip_key_words(cand, length: int, salt: bytes, cycles: int):
    """Candidates uint32[B, length] -> SHA-256 key state uint32[B, 8].

    Walks the counter stream in lcm(64, unit)-byte groups; see module
    docstring.  cycles <= 24 keeps the counter in 32 bits."""
    B = cand.shape[0]
    sl = len(salt)
    unit = sl + 2 * length + 8
    g = math.gcd(64, unit)
    bpg, upg = unit // g, 64 // g          # blocks / units per group
    n_units = 1 << cycles
    if n_units % upg:
        raise ValueError(f"cycles {cycles} stream does not align to "
                         f"the {upg}-unit group")
    n_groups = n_units // upg

    def byte_at(q: int, grp):
        """Stream byte at group offset q as uint32[B] (grp traced)."""
        u, off = divmod(q, unit)
        if off < sl:
            return jnp.full((B,), np.uint32(salt[off]))
        off -= sl
        if off < 2 * length:
            if off % 2:
                return jnp.zeros((B,), jnp.uint32)   # UTF-16LE high
            return cand[:, off // 2].astype(jnp.uint32)
        cb = off - 2 * length                        # LE64 counter
        if cb >= 4:
            return jnp.zeros((B,), jnp.uint32)       # cycles <= 24
        counter = (grp * upg + u).astype(jnp.uint32)
        return jnp.broadcast_to(
            (counter >> jnp.uint32(8 * cb)) & jnp.uint32(0xFF), (B,))

    def group(grp, state):
        grp32 = grp.astype(jnp.int32)
        for b in range(bpg):
            words = []
            for w in range(16):
                q = 64 * b + 4 * w
                words.append(
                    (byte_at(q, grp32) << jnp.uint32(24))
                    | (byte_at(q + 1, grp32) << jnp.uint32(16))
                    | (byte_at(q + 2, grp32) << jnp.uint32(8))
                    | byte_at(q + 3, grp32))
            state = sha256_compress(state, jnp.stack(words, axis=1))
        return state

    state = jnp.broadcast_to(
        jnp.asarray(SHA256_INIT, jnp.uint32), (B, 8))
    state = lax.fori_loop(0, n_groups, group, state)

    # final padding block: the stream ends exactly on a group
    # boundary, so it is 0x80 + zeros + the 64-bit big-endian bitlen
    bitlen = n_units * unit * 8
    pad = np.zeros(16, np.uint32)
    pad[0] = 0x80000000
    pad[14] = (bitlen >> 32) & 0xFFFFFFFF
    pad[15] = bitlen & 0xFFFFFFFF
    return sha256_compress(state, jnp.broadcast_to(
        jnp.asarray(pad), (B, 16)))


def make_state_check(params: dict):
    """uint32[B, 8] SHA-256 key states -> uint32[B, 1] recomputed
    CRC32 (exact); shared by the XLA KDF path and the Pallas KDF
    kernel (ops/pallas_7z.py)."""
    data, iv = params["data"], params["iv"]
    unpacked = params["unpacked_len"]
    blocks = np.frombuffer(data, np.uint8).reshape(-1, 16)
    prev = np.concatenate(
        [np.frombuffer((iv + bytes(16))[:16], np.uint8)[None],
         blocks[:-1]], axis=0)           # CBC xor chain, all constant

    def check(state):
        # key bytes: big-endian serialization of the 8 state words
        B = state.shape[0]
        shifts = jnp.asarray([24, 16, 8, 0], jnp.uint32)
        keys = ((state[:, :, None] >> shifts[None, None, :])
                & jnp.uint32(0xFF)).reshape(B, 32).astype(jnp.uint8)
        plain = aes_decrypt_blocks(keys, blocks) ^ \
            jnp.asarray(prev)[None]
        flat = plain.reshape(B, -1)
        return crc32_batch(flat, unpacked)[:, None]

    return check


def make_7z_filter(length: int, params: dict):
    """fb(cand, lens) -> uint32[B, 1] recomputed CRC32 (exact)."""
    salt, cycles = params["salt"], params["cycles"]
    check = make_state_check(params)

    def fb(cand, lens):
        return check(sevenzip_key_words(cand, length, salt, cycles))

    return fb


def _make_step(gen, batch: int, params: dict, hit_capacity: int):
    flat = gen.flat_charsets
    length = gen.length
    fb = make_7z_filter(length, params)

    @jax.jit
    def step(base_digits, n_valid, target):
        cand = gen.decode_batch(base_digits, flat, batch)
        lens = jnp.full((batch,), length, jnp.int32)
        word = fb(cand, lens)
        found = cmp_ops.compare_single(word, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step


def _make_kernel_step(gen, batch: int, params: dict,
                      hit_capacity: int, interpret: bool):
    """KDF on the Pallas kernel (ops/pallas_7z.py), AES+CRC verdict
    in XLA -- the KDF is ~99.9% of the work at production cycles."""
    from dprf_tpu.ops.pallas_7z import make_7z_kdf_pallas_fn

    check = make_state_check(params)
    kdf = make_7z_kdf_pallas_fn(gen, batch, params["salt"],
                                params["cycles"], interpret=interpret)

    @jax.jit
    def step(base_digits, n_valid, target):
        word = check(kdf(base_digits))
        found = cmp_ops.compare_single(word, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step


from dprf_tpu.engines.device.phpass import (PhpassMaskWorker,  # noqa: E402
                                            ShardedPhpassMaskWorker)


def _crc_word(t: Target) -> jnp.ndarray:
    return jnp.asarray(
        np.array([struct.unpack("<I", t.digest)[0]], np.uint32))


class SevenZipMaskWorker(PhpassMaskWorker):
    """Per-target sweep; every target's stream layout/data are static,
    so each target owns a compiled step."""

    def __init__(self, engine, gen, targets, batch: int = 1 << 12,
                 hit_capacity: int = 64, oracle=None):
        from dprf_tpu.ops.pallas_7z import sevenzip_kernel_eligible
        from dprf_tpu.ops.pallas_mask import TILE, pallas_mode

        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        mode = pallas_mode()
        if mode is not None:
            batch = max(TILE, (batch // TILE) * TILE)
        self.batch = self.stride = batch
        self._steps = []
        for t in self.targets:
            step = None
            if mode is not None and sevenzip_kernel_eligible(
                    gen, t.params["cycles"], len(t.params["salt"])):
                from dprf_tpu.engines.device._kernel_util import \
                    kind_kernel_step
                from dprf_tpu.utils.sync import hard_sync
                tw = _crc_word(t)
                step = kind_kernel_step(
                    lambda t=t: _make_kernel_step(
                        gen, batch, t.params, hit_capacity,
                        interpret=mode.get("interpret", False)),
                    lambda s, tw=tw: hard_sync(s(
                        jnp.zeros((gen.length,), jnp.int32),
                        jnp.int32(0), tw)))
            if step is None:
                step = _make_step(gen, batch, t.params, hit_capacity)
            self._steps.append(step)
        self._targs = [(ti, _crc_word(t))
                       for ti, t in enumerate(self.targets)]

    def step(self, base, n_valid, ti: int, target):
        return self._steps[ti](base, n_valid, target)


class ShardedSevenZipMaskWorker(ShardedPhpassMaskWorker):
    def __init__(self, engine, gen, targets, mesh,
                 batch_per_device: int = 1 << 10, hit_capacity: int = 64,
                 oracle=None):
        from dprf_tpu.parallel.sharded import \
            make_sharded_pertarget_step
        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        self.mesh = mesh
        self.batch = self.stride = mesh.devices.size * batch_per_device
        self._steps = [make_sharded_pertarget_step(
            gen, mesh, batch_per_device,
            make_7z_filter(gen.length, t.params), 0, hit_capacity)
            for t in self.targets]
        self._targs = [(ti, _crc_word(t))
                       for ti, t in enumerate(self.targets)]

    def step(self, base, n_valid, ti: int, target):
        return self._steps[ti](base, n_valid, target)


def _over_cap(targets) -> bool:
    big = max(len(t.params["data"]) for t in targets)
    if big <= DEVICE_DATA_CAP:
        return False
    from dprf_tpu.utils.logging import DEFAULT as log
    log.warn("7z stored entry exceeds the device payload cap; "
             "running on the CPU oracle",
             data_bytes=big, cap=DEVICE_DATA_CAP)
    return True


@register("7z", device="jax")
@register("sevenzip", device="jax")
class JaxSevenZipEngine(SevenZipEngine):
    def make_mask_worker(self, gen, targets, batch: int,
                         hit_capacity: int, oracle=None):
        if _over_cap(targets):
            from dprf_tpu.runtime.worker import CpuWorker
            return CpuWorker(oracle or self, gen, targets)
        return SevenZipMaskWorker(self, gen, targets, batch=batch,
                                  hit_capacity=hit_capacity,
                                  oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        if _over_cap(targets):
            from dprf_tpu.runtime.worker import CpuWorker
            return CpuWorker(oracle or self, gen, targets)
        return ShardedSevenZipMaskWorker(
            self, gen, targets, mesh, batch_per_device=batch_per_device,
            hit_capacity=hit_capacity, oracle=oracle)
