"""Fused 7-Zip KDF Pallas kernel: the 2^cycles SHA-256 counter stream.

The 7z check is KDF-bound (~2^cycles * unit/64 SHA-256 compressions
per candidate; the AES+CRC tail is noise), and the XLA fori_loop form
leaves most of the VPU idle between small per-group fusions — the
same gap the PBKDF2/PMKID kernel closed for config 5.  This kernel
keeps the whole stream walk in registers per candidate lane:

  mask decode -> lcm(64, unit)-byte group loop (every byte's source
  is compile-time wiring: salt const / candidate byte / counter
  shift, exactly the scheme of engines/device/sevenzip.py's XLA
  walker) -> final padding block -> 8 key words to HBM.

The AES-256-CBC decrypt + CRC32 verdict stays in XLA downstream
(per-candidate S-box gathers don't belong in the candidate-per-lane
layout); the kernel output is uint32[B, 8] key states consumed by
the engine's `_check_from_state`.

The group loop is `lax.fori_loop` with an 8-register carry — the
small-carry shape the PBKDF2 kernel uses too; the bpg compress calls inside the body are
statically unrolled.
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dprf_tpu.ops import sha256 as sha256_ops
from dprf_tpu.ops.pallas_mask import (SUB, decode_candidate_bytes,
                                      mask_supported, segment_tables)


def sevenzip_kernel_eligible(gen, cycles: int, salt_len: int) -> bool:
    """Any mask charset order (segment mux, unbounded since r5); the
    counter stream must tile into whole groups (always true for
    cycles >= 6, the realistic range)."""
    if not hasattr(gen, "charsets") or not mask_supported(gen.charsets):
        return False
    unit = salt_len + 2 * gen.length + 8
    upg = 64 // math.gcd(64, unit)
    return (1 << cycles) % upg == 0 and 0 < cycles <= 24


def _compress(state, m):
    out = sha256_ops.sha256_rounds(*state, m)
    return tuple(o + s for o, s in zip(out, state))


def _kdf_lanes(byts, length: int, salt: bytes, cycles: int, shape):
    """Candidate byte arrays -> 8 SHA-256 key words; pure function
    shared by the pallas kernel and eager validation tests."""
    sl = len(salt)
    unit = sl + 2 * length + 8
    g = math.gcd(64, unit)
    bpg, upg = unit // g, 64 // g
    n_units = 1 << cycles
    n_groups = n_units // upg

    def byte_at(q: int, grp):
        u, off = divmod(q, unit)
        if off < sl:
            return jnp.full(shape, jnp.uint32(salt[off]))
        off -= sl
        if off < 2 * length:
            if off % 2:
                return jnp.zeros(shape, jnp.uint32)
            return byts[off // 2]
        cb = off - 2 * length
        if cb >= 4:
            return jnp.zeros(shape, jnp.uint32)
        counter = (grp * upg + u).astype(jnp.uint32)
        return jnp.full(shape,
                        (counter >> jnp.uint32(8 * cb))
                        & jnp.uint32(0xFF))

    def group(grp, state):
        for b in range(bpg):
            m = []
            for w in range(16):
                q = 64 * b + 4 * w
                m.append((byte_at(q, grp) << jnp.uint32(24))
                         | (byte_at(q + 1, grp) << jnp.uint32(16))
                         | (byte_at(q + 2, grp) << jnp.uint32(8))
                         | byte_at(q + 3, grp))
            state = _compress(state, m)
        return state

    state = tuple(jnp.full(shape, jnp.uint32(int(w)))
                  for w in sha256_ops.INIT)
    state = lax.fori_loop(0, n_groups, group, state)

    bitlen = n_units * unit * 8
    pad = [jnp.zeros(shape, jnp.uint32) for _ in range(16)]
    pad[0] = jnp.full(shape, jnp.uint32(0x80000000))
    pad[14] = jnp.full(shape, jnp.uint32((bitlen >> 32) & 0xFFFFFFFF))
    pad[15] = jnp.full(shape, jnp.uint32(bitlen & 0xFFFFFFFF))
    return _compress(state, pad)


def make_7z_kdf_pallas_fn(gen, batch: int, salt: bytes, cycles: int,
                          sub: int = SUB, interpret: bool = False):
    """fn(base_digits) -> uint32[batch, 8] key states (invalid lanes
    produce garbage keys; the downstream step masks by n_valid)."""
    tile = sub * 128
    if batch % tile or batch <= 0:
        raise ValueError(f"batch {batch} must be a multiple of "
                         f"tile {tile}")
    if not sevenzip_kernel_eligible(gen, cycles, len(salt)):
        raise ValueError("7z KDF kernel: job not eligible")
    grid = batch // tile
    seg_tables = segment_tables(gen.charsets)
    radices, length = gen.radices, gen.length

    def kernel(base_ref, out_ref):
        shape = (sub, 128)
        pid = pl.program_id(0)
        lane = (lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + lax.broadcasted_iota(jnp.int32, shape, 1))
        byts = decode_candidate_bytes(radices, seg_tables, length,
                                      base_ref, pid * tile, lane, tile)
        state = _kdf_lanes(byts, length, salt, cycles, shape)
        out_ref[...] = jnp.concatenate(list(state), axis=0)

    L = gen.length
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((L,), lambda i: (0,),
                               memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((8 * sub, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * 8 * sub, 128),
                                        jnp.uint32)],
        interpret=interpret,
    )

    @jax.jit
    def fn(base_digits):
        # jit even in interpret mode: an eager interpreter walk of
        # the unrolled sha256 rounds is ~100k op dispatches
        (packed,) = raw(base_digits.astype(jnp.int32))
        # rows (grid, word, sub) x lanes -> candidate-major (batch, 8)
        words = packed.reshape(grid, 8, sub, 128)
        return words.transpose(0, 2, 3, 1).reshape(batch, 8)

    return fn
