"""Pallas Kerberos etype-23 prefilter kernel: vector-rate RC4.

The XLA krb5 filter step (engines/device/krb5.py) is gather-bound: its
RC4 KSA is a fori_loop whose per-candidate S-box swap lowers to
per-lane SERIAL gathers + scatters, the same failure mode the bcrypt
XLA form hit.
This kernel applies the pallas_bcrypt layout cure to RC4:

- candidates ride the SUBLANE axis, SUBC per chunk; every working
  value (digest words, j, keystream) is an (SUBC, 128) lane-replicated
  tile;
- each candidate's 256-entry S state is two (SUBC, 128) uint32 halves
  with the ENTRY INDEX along lanes, so `S[j]` is the hardware's native
  per-sublane `take_along_axis` gather (two halves + a bit-7 select)
  and the swap WRITES are lane-iota compare + select — no scatter;
- the KSA runs as an in-kernel `lax.fori_loop` with a 3-array carry
  (S_lo, S_hi, j) — the small-carry shape the PBKDF2 kernel also
  uses (large SoA-tuple carries are what the compiler has refused);
- upstream of RC4, the whole chain — mask decode, UTF-16LE widening,
  MD4 (NTLM), HMAC-MD5(K, msg_type), HMAC-MD5(K1, checksum) — runs
  lane-replicated in the same kernel, so nothing touches HBM between
  decode and verdict;
- one grid cell sweeps CHUNKS × SUBC candidates through a fori_loop
  (accumulating count / hit-index scalars) so the mandatory (8, 128)
  output block amortizes to ~2 B/candidate of HBM traffic.

Like the decrypted-header filter it accelerates, the kernel checks
keystream bytes [8, 12) (past the RFC 4757 confounder) against the
DER expectation; the checksum, ciphertext word, expectation, and mask
are RUNTIME SMEM scalars, so ONE compiled kernel per mask serves every
target of both krb5tgs and krb5asrep (the msg_type is a scalar too).
"""

from __future__ import annotations


import numpy as np

from dprf_tpu.utils import env as envreg  # noqa: E402 -- stdlib-only
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dprf_tpu.ops import md4 as md4_ops
from dprf_tpu.ops import md5 as md5_ops
from dprf_tpu.ops.pallas_mask import (decode_candidate_bytes,
                                      mask_supported, segment_tables,
                                      _pack_message)

#: candidates per sublane chunk / chunks per grid cell.  VMEM per
#: chunk is ~SUBC * 1 KB of S state plus the lane-replicated words.
SUBC = envreg.get_int("DPRF_KRB5_SUBC")
CHUNKS = envreg.get_int("DPRF_KRB5_CHUNKS")
#: statically unroll the 256-step KSA: the loop counter's S read
#: becomes a static lane slice and the key byte a trace-time shift
#: (no gather), leaving ONE dynamic gather per step instead of three.
#: DEFAULT OFF: on an older toolchain the unrolled graph aborted the
#: Mosaic compile at every SUBC tried, while the fori_loop form
#: compiled in ~10 s.  Not re-tried on the installed toolchain.
UNROLL = envreg.get_bool("DPRF_KRB5_UNROLL")

_IPAD = 0x36363636
_OPAD = 0x5C5C5C5C


def krb5_kernel_eligible(gen, max_len: int = 27) -> bool:
    """Mask-attack jobs the kernel covers: any charset order
    (unbounded segment mux since r5),
    NTLM's single-block UTF-16LE candidate limit."""
    return (hasattr(gen, "charsets") and gen.length <= max_len
            and mask_supported(gen.charsets))


# lane-replicated MD5 compress now shared via pallas_mask (also used
# by the PDF kernel); historical local name kept for the bodies below.
from dprf_tpu.ops.pallas_mask import md5_compress_lanes as _compress  # noqa: E402


def _hmac_md5(key4, msg_words, msg_len: int, shape):
    """HMAC-MD5 with a per-candidate 16-byte key and a short
    word-aligned message (msg_len in {4, 16} bytes) -> 4 words."""
    init = tuple(jnp.full(shape, jnp.uint32(int(w)))
                 for w in md5_ops.INIT)
    zero = jnp.zeros(shape, jnp.uint32)
    ipad = [key4[t] ^ jnp.uint32(_IPAD) for t in range(4)] + \
        [jnp.full(shape, jnp.uint32(_IPAD)) for _ in range(12)]
    opad = [key4[t] ^ jnp.uint32(_OPAD) for t in range(4)] + \
        [jnp.full(shape, jnp.uint32(_OPAD)) for _ in range(12)]
    istate = _compress(init, ipad)
    ostate = _compress(init, opad)
    nw = msg_len // 4
    inner_m = list(msg_words[:nw]) + [zero] * (16 - nw)
    inner_m[nw] = jnp.full(shape, jnp.uint32(0x80))
    inner_m[14] = jnp.full(shape, jnp.uint32((64 + msg_len) * 8))
    inner = _compress(istate, inner_m)
    outer_m = list(inner) + [zero] * 12
    outer_m[4] = jnp.full(shape, jnp.uint32(0x80))
    outer_m[14] = jnp.full(shape, jnp.uint32((64 + 16) * 8))
    return _compress(ostate, outer_m)


# the 256-entry lane-axis lookup/swap pair now lives in pallas_mask
# (shared with the PDF RC4 kernel and the LUT charset decode); kept
# under the historical names for this module's KSA/PRGA bodies.
from dprf_tpu.ops.pallas_mask import gather256 as _gather256  # noqa: E402
from dprf_tpu.ops.pallas_mask import swap256 as _swap256  # noqa: E402


def _rc4_word2(key4, shape, unroll: bool):
    """RC4 keystream bytes [8, 12) for 16-byte keys, packed LE."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    S_lo0 = lane.astype(jnp.uint32)
    S_hi0 = S_lo0 + jnp.uint32(128)

    if unroll:
        S_lo, S_hi = S_lo0, S_hi0
        j = jnp.zeros(shape, jnp.uint32)
        for i in range(256):        # static i: S[i] is a lane slice,
            half = S_lo if i < 128 else S_hi          # key a shift
            si = jnp.broadcast_to(half[:, i % 128:i % 128 + 1], shape)
            t = i % 16
            ki = (key4[t // 4] >> jnp.uint32(8 * (t % 4))) \
                & jnp.uint32(0xFF)
            j = (j + si + ki) & jnp.uint32(255)
            sj = _gather256(S_lo, S_hi, j)
            at_i = lane == i % 128
            if i < 128:
                S_lo = jnp.where(at_i, sj, S_lo)
            else:
                S_hi = jnp.where(at_i, sj, S_hi)
            S_lo, S_hi = _swap256(S_lo, S_hi, j, si, lane)
    else:
        # key bytes along the first 16 lanes (gathered by i % 16)
        kb = jnp.zeros(shape, jnp.uint32)
        for t in range(16):
            kb = jnp.where(lane == t,
                           (key4[t // 4] >> jnp.uint32(8 * (t % 4)))
                           & jnp.uint32(0xFF), kb)

        def ksa(i, carry):
            S_lo, S_hi, j = carry
            i_rep = jnp.full(shape, i.astype(jnp.uint32))
            si = _gather256(S_lo, S_hi, i_rep)
            ki = jnp.take_along_axis(
                kb, jnp.full(shape, i % 16, jnp.int32), axis=1)
            j = (j + si + ki) & jnp.uint32(255)
            sj = _gather256(S_lo, S_hi, j)
            S_lo, S_hi = _swap256(S_lo, S_hi, i_rep, sj, lane)
            S_lo, S_hi = _swap256(S_lo, S_hi, j, si, lane)
            return S_lo, S_hi, j

        S_lo, S_hi, _ = lax.fori_loop(
            0, 256, ksa, (S_lo0, S_hi0, jnp.zeros(shape, jnp.uint32)))

    j = jnp.zeros(shape, jnp.uint32)
    word = jnp.zeros(shape, jnp.uint32)
    for t in range(12):             # PRGA, static i = t + 1 < 128
        i = t + 1
        si = jnp.broadcast_to(S_lo[:, i:i + 1], shape)
        j = (j + si) & jnp.uint32(255)
        sj = _gather256(S_lo, S_hi, j)
        i_rep = jnp.full(shape, jnp.uint32(i))
        S_lo, S_hi = _swap256(S_lo, S_hi, i_rep, sj, lane)
        S_lo, S_hi = _swap256(S_lo, S_hi, j, si, lane)
        k = _gather256(S_lo, S_hi, (si + sj) & jnp.uint32(255))
        if t >= 8:
            word = word | (k << jnp.uint32(8 * (t - 8)))
    return word


def _build_body(radices, seg_tables, length: int, sub: int,
                chunks: int, unroll: bool):
    """(pid, base, n_valid, type_w, chk_ref, cipher_w, mask_w, exp_w)
    -> (count, hit_index) scalars; hit_index is tile-local
    (chunk * sub + row), tile = sub * chunks."""
    tile = sub * chunks

    def body(pid, base, n_valid, type_w, chk_ref, cipher_w, mask_w,
             exp_w):
        shape = (sub, 128)
        row = lax.broadcasted_iota(jnp.int32, shape, 0)

        def chunk(c, acc):
            count, hit = acc
            start = pid * tile + c * sub
            gidx = start + row
            byts = decode_candidate_bytes(radices, seg_tables, length,
                                          base, start, row, sub)
            m = _pack_message(byts, length, shape, False, True)
            init = tuple(jnp.full(shape, jnp.uint32(int(w)))
                         for w in md4_ops.INIT)
            out = md4_ops.md4_rounds(*init, m)
            nt = tuple(x + s for x, s in zip(out, init))
            k1 = _hmac_md5(nt, [jnp.full(shape, type_w)], 4, shape)
            chk = [jnp.full(shape, chk_ref[i].astype(jnp.uint32))
                   for i in range(4)]
            k3 = _hmac_md5(k1, chk, 16, shape)
            ks = _rc4_word2(k3, shape, unroll)
            plain = ks ^ cipher_w
            found = ((plain & mask_w) == exp_w) & (gidx < n_valid)
            # lanes are replicated: count each candidate (row) once
            lane0 = lax.broadcasted_iota(jnp.int32, shape, 1) == 0
            found = found & lane0
            count = count + jnp.sum(found.astype(jnp.int32))
            hit = jnp.maximum(
                hit, jnp.max(jnp.where(found, c * sub + row, -1)))
            return count, hit

        return lax.fori_loop(0, chunks, chunk,
                             (jnp.int32(0), jnp.int32(-1)))

    return body


def make_krb5_pallas_fn(gen, batch: int, sub: int = 0,
                        chunks: int = 0, unroll: bool = None,
                        interpret: bool = False):
    """fn(base_digits, n_valid int32[1], type_w int32[1],
    chk int32[4], cipher int32[1], mask int32[1], expected int32[1])
    -> (counts int32[grid, 1], hit_idx int32[grid, 1]), tile-local
    hit indices; tile = sub * chunks."""
    sub = sub or SUBC
    chunks = chunks or CHUNKS
    unroll = UNROLL if unroll is None else unroll
    tile = sub * chunks
    if batch % tile or batch <= 0:
        raise ValueError(f"batch {batch} must be a multiple of "
                         f"tile {tile}")
    if tile > 0x7FFF:
        # hit+1 and count share one int32 as (count << 16) | (hit+1);
        # a larger tile would bleed into the count bits and report the
        # WRONG candidate index (a silent false negative after oracle
        # rejection)
        raise ValueError(f"tile {tile} exceeds the 15-bit packed "
                         "output limit (lower DPRF_KRB5_SUBC/CHUNKS)")
    if not krb5_kernel_eligible(gen):
        raise ValueError("krb5 kernel: mask not eligible")
    grid = batch // tile
    seg_tables = segment_tables(gen.charsets)
    body = _build_body(gen.radices, seg_tables, gen.length, sub,
                       chunks, unroll)

    def kernel(base_ref, nvalid_ref, type_ref, chk_ref, cipher_ref,
               mask_ref, exp_ref, out_ref):
        count, hit = body(
            pl.program_id(0), base_ref, nvalid_ref[0],
            type_ref[0].astype(jnp.uint32), chk_ref,
            cipher_ref[0].astype(jnp.uint32),
            mask_ref[0].astype(jnp.uint32),
            exp_ref[0].astype(jnp.uint32))
        out_ref[...] = jnp.full((8, 128), (count << 16) | (hit + 1),
                                jnp.int32)

    L = gen.length
    smem = lambda n: pl.BlockSpec((n,), lambda i: (0,),
                                  memory_space=pltpu.SMEM)
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[smem(L), smem(1), smem(1), smem(4), smem(1),
                  smem(1), smem(1)],
        out_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * 8, 128), jnp.int32)],
        interpret=interpret,
    )

    def fn(base_digits, n_valid, type_w, chk, cipher, mask, expected):
        (packed,) = raw(base_digits, n_valid, type_w, chk, cipher,
                        mask, expected)
        p = packed[::8, 0:1]
        return p >> 16, (p & 0xFFFF) - 1

    return fn


def make_krb5_crack_step(gen, batch: int, hit_capacity: int = 64,
                         sub: int = 0, chunks: int = 0,
                         unroll: bool = None,
                         interpret: bool = False):
    """Kernel crack step with the worker (count, lanes, tpos)
    contract and runtime per-target scalars:
    step(base_digits, n_valid, type_w, chk, cipher, mask, expected).
    """
    from dprf_tpu.ops.pallas_mask import reduce_tile_hits

    sub = sub or SUBC
    chunks = chunks or CHUNKS
    tile = sub * chunks
    fn = make_krb5_pallas_fn(gen, batch, sub=sub, chunks=chunks,
                             unroll=unroll, interpret=interpret)

    @jax.jit
    def step(base_digits, n_valid, type_w, chk, cipher, mask,
             expected):
        counts, lanes = fn(base_digits.astype(jnp.int32),
                           jnp.reshape(n_valid, (1,)).astype(jnp.int32),
                           type_w, chk, cipher, mask, expected)
        return reduce_tile_hits(counts, lanes, hit_capacity, tile)

    return step


def target_scalars(target) -> tuple:
    """Target.params -> the kernel's five runtime scalar arrays."""
    from dprf_tpu.engines.device.krb5 import CONF, der_filter_words

    p = target.params
    expected, mask = der_filter_words(len(p["edata"]), p["msg_type"])

    def i32(v: int) -> jnp.ndarray:
        # uint32 bit pattern -> int32 SMEM scalar (no x64 needed)
        return jnp.asarray(np.array([v], np.uint32).view(np.int32))

    chk = np.frombuffer(p["checksum"], "<u4").view(np.int32).copy()
    return (i32(p["msg_type"]), jnp.asarray(chk),
            i32(int.from_bytes(p["edata"][CONF:CONF + 4], "little")),
            i32(mask), i32(expected))
