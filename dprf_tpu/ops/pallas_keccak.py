"""Fused mask->Keccak->compare Pallas kernel for the SHA3/Keccak
family (sha3-224/256/384/512, keccak-224/256/384/512).

Same skeleton as ops/pallas_mask.py -- decode, hash, compare, and the
packed (count << 16) | (hit_lane + 1) per-tile output all stay in
VMEM -- but the sponge replaces the Merkle-Damgard framing: the
candidate absorbs into the rate lanes with the variant's pad byte at
the (static) message length and 0x80 at rate-1, then 24 unrolled
Keccak-f rounds run over (hi, lo) uint32 pairs
(ops/keccak.keccak_f_unrolled; a fori_loop with a 50-array dict carry
does not lower to Mosaic).

Register pressure is the sizing constraint: ~120 (hi, lo) pair tiles
are live through theta/rho-pi/chi, so the default sublane count SUBK
is smaller than the MD kernels' 128.  Single target only (multi-target
lists stay on the XLA sorted-table pipeline); TPU-only like the
SHA-256/512 kernels -- XLA:CPU takes minutes on the flat unrolled
graph, so correctness off-TPU is validated eagerly via
emulate_keccak_kernel.
"""

from __future__ import annotations


import numpy as np

from dprf_tpu.utils import env as envreg  # noqa: E402 -- stdlib-only
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dprf_tpu.ops.keccak import keccak_f_unrolled, squeeze_words
from dprf_tpu.ops.pallas_mask import (check_batch,
                                      decode_candidate_bytes,
                                      mask_supported, reduce_tile_hits,
                                      segment_tables)

#: sublane count per grid cell (tile = SUBK * 128 lanes).  Keccak-f
#: holds ~120 pair registers live, ~4x the MD cores, so the default
#: tile is smaller; DPRF_PALLAS_SUBK overrides for hardware sweeps.
SUBK = envreg.get_int("DPRF_PALLAS_SUBK")


def keccak_kernel_eligible(gen, n_targets: int, rate: int) -> bool:
    """Kernel path eligibility: single target, mask generator whose
    charsets are segment-decodable, candidate fits the rate block,
    real TPU backend only (the flat unrolled graph takes XLA:CPU
    minutes even under pallas interpret, so off-TPU the family rides
    the XLA sponge and the body is validated via
    emulate_keccak_kernel, exactly like the SHA-256/512 kernels)."""
    if n_targets != 1:
        return False
    if not hasattr(gen, "charsets"):
        return False
    if jax.default_backend() != "tpu":
        return False
    return gen.length <= rate - 1 and mask_supported(gen.charsets)


def _build_keccak_body(radices, seg_tables, length: int, tw,
                       pad_byte: int, rate: int, out_bytes: int,
                       sub: int):
    """Kernel math as a pure function of (pid, base, n_valid) ->
    (count, hit_lane), mirroring pallas_mask._build_kernel_body."""
    tile = sub * 128
    tw_ints = [int(w) for w in np.asarray(tw).reshape(-1)]
    n_words = -(-out_bytes // 4)
    if len(tw_ints) != n_words:
        raise ValueError(f"expected {n_words} target words")

    def body(pid, base, n_valid):
        shape = (sub, 128)
        lane = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        byts = decode_candidate_bytes(radices, seg_tables, length,
                                      base, pid * tile, lane, tile)

        def const_byte(q: int) -> int:
            # the padding is STATIC: mask candidates all have length
            # `length`, so pad_byte lands at byte `length` and 0x80 at
            # rate-1 (merged when length == rate - 1, per pad10*1)
            v = 0
            if q == length:
                v |= pad_byte
            if q == rate - 1:
                v |= 0x80
            return v

        def half_lane(q0: int):
            """uint32 from bytes q0..q0+3 (little-endian)."""
            acc = None
            const = 0
            for j in range(4):
                q = q0 + j
                if q < length:
                    term = byts[q] << jnp.uint32(8 * j)
                    acc = term if acc is None else acc + term
                else:
                    const |= const_byte(q) << (8 * j)
            if const:
                c = jnp.uint32(const)
                acc = jnp.full(shape, c) if acc is None else acc + c
            return jnp.zeros(shape, jnp.uint32) if acc is None else acc

        zero = jnp.zeros(shape, jnp.uint32)
        state = {(x, y): (zero, zero)
                 for x in range(5) for y in range(5)}
        for i in range(rate // 8):
            state[(i % 5, i // 5)] = (half_lane(8 * i + 4),
                                      half_lane(8 * i))
        state = keccak_f_unrolled(state)
        digest = squeeze_words(state, out_bytes)

        valid = (lane + pid * tile) < n_valid
        found = valid
        for got, want in zip(digest, tw_ints):
            found = found & (got == jnp.uint32(want))
        count = jnp.sum(found.astype(jnp.int32))
        hit_lane = jnp.max(jnp.where(found, lane, -1))
        return count, hit_lane

    return body


def emulate_keccak_kernel(gen, tw, batch: int, base_digits, n_valid,
                          pad_byte: int, rate: int, out_bytes: int,
                          sub: int = SUBK):
    """Eager per-tile drive of the kernel body (the CPU validation
    vehicle; XLA:CPU cannot compile the unrolled graph)."""
    tile = sub * 128
    check_batch(batch, sub)
    seg_tables = segment_tables(gen.charsets)
    body = _build_keccak_body(gen.radices, seg_tables, gen.length, tw,
                              pad_byte, rate, out_bytes, sub)
    base = jnp.asarray(base_digits, jnp.int32)
    counts, lanes = [], []
    for pid in range(batch // tile):
        c, l = body(jnp.int32(pid), base, jnp.int32(n_valid))
        counts.append(int(c))
        lanes.append(int(l))
    return (np.asarray(counts, np.int32)[:, None],
            np.asarray(lanes, np.int32)[:, None])


def make_keccak_pallas_fn(gen, tw, batch: int, pad_byte: int,
                          rate: int, out_bytes: int, sub: int = SUBK,
                          interpret: bool = False):
    """fn(base_digits int32[L], n_valid int32[1]) ->
    (counts int32[G, 1], hit_lanes int32[G, 1])."""
    tile = sub * 128
    grid = check_batch(batch, sub)
    if not keccak_kernel_eligible(gen, 1, rate):
        raise ValueError("mask job not keccak-kernel eligible")
    seg_tables = segment_tables(gen.charsets)
    body = _build_keccak_body(gen.radices, seg_tables, gen.length, tw,
                              pad_byte, rate, out_bytes, sub)
    L = gen.length

    def kernel(base_ref, nvalid_ref, out_ref):
        count, hit_lane = body(pl.program_id(0), base_ref,
                               nvalid_ref[0])
        packed = (count << 16) | (hit_lane + 1)
        out_ref[...] = jnp.full((8, 128), packed, jnp.int32)

    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((L,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * 8, 128), jnp.int32)],
        interpret=interpret,
    )

    def fn(base_digits, n_valid):
        (packed,) = raw(base_digits, n_valid)
        p = packed[::8, 0:1]
        return p >> 16, (p & 0xFFFF) - 1

    return fn


def make_pallas_keccak_crack_step(gen, tw, batch: int, pad_byte: int,
                                  rate: int, out_bytes: int,
                                  hit_capacity: int = 64,
                                  interpret: bool = False):
    """Drop-in replacement for sha3.make_keccak_mask_step on the
    single-target kernel path: step(base_digits, n_valid) ->
    (count, lanes, tpos)."""
    tile = SUBK * 128
    fn = make_keccak_pallas_fn(gen, tw, batch, pad_byte, rate,
                               out_bytes, interpret=interpret)

    @jax.jit
    def step(base_digits, n_valid):
        counts, hit_lanes = fn(base_digits.astype(jnp.int32),
                               jnp.reshape(n_valid, (1,))
                               .astype(jnp.int32))
        return reduce_tile_hits(counts, hit_lanes, hit_capacity, tile)

    return step
