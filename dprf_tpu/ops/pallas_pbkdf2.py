"""Fused PMKID Pallas kernel: decode -> PBKDF2-HMAC-SHA1 -> PMKID.

Config 5 (WPA2-PMKID) measured 17.4 kH/s through the XLA pipeline on
the real chip -- ~285 M SHA-1 compressions/s, ~12% of the sha1 mask
kernel's rate; the XLA fori_loop form leaves most of the VPU idle
between the small per-iteration fusions.  This kernel keeps the whole
chain in VMEM/registers per candidate lane:

  mask decode -> one-block HMAC key states (K^ipad / K^opad) ->
  two PBKDF2 blocks of `iterations` HMAC-SHA1 rounds (the fori_loop
  carries 10 digest-word registers -- small carries DO lower, unlike
  big SoA tuples) -> PMK -> PMKID = HMAC(PMK, "PMK Name"|AP|STA)
  -> compare.

Per-target runtime inputs (SMEM scalars): ESSID bytes (length static
per compiled kernel, like the salted kernels' salt length), the
20-byte PMKID message words, the 4-word target, and the iteration
count -- so one compile per (mask, essid length) serves every target
and any iteration count (tests run 16, production 4096).

Semantics mirror ops/hmac_sha1.py exactly (same ipad/opad single-xor
key pad, same salt||INT(i) first message, same T1||T2[:3] PMK);
the hermetic tests drive the shared pure body (pmkid_lanes)\neagerly against hashlib, and the kernel itself is proven on real\nhardware (planted crack at 4096 iterations).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dprf_tpu.ops import sha1 as sha1_ops
from dprf_tpu.ops.pallas_mask import (SUB, decode_candidate_bytes,
                                      mask_supported, reduce_tile_hits,
                                      segment_tables)

_IPAD = 0x36363636
_OPAD = 0x5C5C5C5C


def pmkid_kernel_eligible(gen, essid_lens) -> bool:
    """Any mask charset order (unbounded segment mux since r5);
    passphrase and ESSID must fit
    their single blocks (ESSID <= 32 by 802.11; belt and braces)."""
    if not hasattr(gen, "charsets") or not mask_supported(gen.charsets):
        return False
    if gen.length > 63:
        return False
    return all(0 < n <= 32 for n in essid_lens)


def _compress(state, m, shape):
    """SHA-1 compression with an arbitrary chaining state on
    (sub, 128) word arrays: rounds + Davies-Meyer feed-forward."""
    out = sha1_ops.sha1_rounds(*state, m)
    return tuple(o + s for o, s in zip(out, state))


def _init_state(shape):
    return tuple(jnp.full(shape, jnp.uint32(int(w)))
                 for w in sha1_ops.INIT)


def _block20(words5, shape):
    """20-byte message following a 64-byte key block: 0x80 marker and
    672-bit length (ops/hmac_sha1._block20 on kernel layouts)."""
    m = [jnp.zeros(shape, jnp.uint32) for _ in range(16)]
    for i in range(5):
        m[i] = words5[i]
    m[5] = jnp.full(shape, jnp.uint32(0x80000000))
    m[15] = jnp.full(shape, jnp.uint32((64 + 20) * 8))
    return m


def _hmac20(istate, ostate, msg5, shape):
    inner = _compress(istate, _block20(msg5, shape), shape)
    return _compress(ostate, _block20(inner, shape), shape)


def pbkdf2_lanes(byts, salt_vals, salt_len: int, iters, n_words: int,
                 shape):
    """Generic PBKDF2-HMAC-SHA1 on kernel layouts: candidate byte
    arrays -> the first n_words uint32 words of T1 || T2 (n_words <= 10
    covers every deployed key width: 4 for AES-128 string-to-key, 8
    for AES-256/PMK).  Same chaining as pmkid_lanes (shared _compress/
    _block20/_hmac20); the salt plays the ESSID's role."""
    K = [jnp.zeros(shape, jnp.uint32) for _ in range(16)]
    for p, b in enumerate(byts):
        K[p // 4] = K[p // 4] | (b << jnp.uint32(8 * (3 - p % 4)))
    init = _init_state(shape)
    istate = _compress(init, [k ^ jnp.uint32(_IPAD) for k in K], shape)
    ostate = _compress(init, [k ^ jnp.uint32(_OPAD) for k in K], shape)

    def as_u32(x):
        return x.astype(jnp.uint32) if hasattr(x, "astype") \
            else jnp.uint32(x)

    def block(block_index: int):
        msg_len = salt_len + 4
        first = [jnp.zeros(shape, jnp.uint32) for _ in range(16)]
        for p in range(salt_len):
            first[p // 4] = first[p // 4] | (
                as_u32(salt_vals[p]) << jnp.uint32(8 * (3 - p % 4)))
        for p, b in zip(range(salt_len, salt_len + 4),
                        int(block_index).to_bytes(4, "big")):
            first[p // 4] = first[p // 4] | (
                jnp.uint32(b) << jnp.uint32(8 * (3 - p % 4)))
        first[msg_len // 4] = first[msg_len // 4] | (
            jnp.uint32(0x80) << jnp.uint32(8 * (3 - msg_len % 4)))
        first[15] = first[15] | jnp.uint32((64 + msg_len) * 8)
        inner = _compress(istate, first, shape)
        u = _compress(ostate, _block20(inner, shape), shape)

        def body(_, uc):
            u, t = uc
            u = _hmac20(istate, ostate, u, shape)
            return u, tuple(a ^ b for a, b in zip(t, u))

        _, t = lax.fori_loop(1, iters, body, (u, u))
        return t

    out = list(block(1))
    if n_words > 5:
        out.extend(block(2))
    return tuple(out[:n_words])


def make_pbkdf2_kdf_pallas_fn(gen, batch: int, salt_len: int,
                              n_words: int, sub: int = SUB,
                              interpret: bool = False):
    """Generic fused mask-decode -> PBKDF2-HMAC-SHA1 kernel producing
    raw derived-key words (the 7z-kernel pattern: KDF on the kernel,
    cheap verdict in XLA downstream).  fn(base_digits int32[L],
    iters int32[1], salt int32[salt_len]) -> uint32[batch, n_words].
    One compile per (mask, salt_len) serves every target and
    iteration count."""
    if sub > 128:
        raise ValueError("sub > 128 overflows the tile layout")
    tile = sub * 128
    if batch % tile or batch <= 0:
        raise ValueError(f"batch {batch} must be a multiple of "
                         f"tile {tile}")
    if not (hasattr(gen, "charsets") and mask_supported(gen.charsets)
            and gen.length <= 63 and 0 < salt_len <= 51):
        raise ValueError("pbkdf2 kdf kernel: job not eligible")
    if not 1 <= n_words <= 10:
        raise ValueError("n_words must be in 1..10 (T1 || T2)")
    seg_tables = segment_tables(gen.charsets)
    radices, length = gen.radices, gen.length
    grid = batch // tile

    def kernel(iters_ref, salt_ref, base_ref, out_ref):
        shape = (sub, 128)
        pid = pl.program_id(0)
        lane = (lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + lax.broadcasted_iota(jnp.int32, shape, 1))
        byts = decode_candidate_bytes(radices, seg_tables, length,
                                      base_ref, pid * tile, lane, tile)
        t = pbkdf2_lanes(byts, [salt_ref[p] for p in range(salt_len)],
                         salt_len, iters_ref[0], n_words, shape)
        out_ref[...] = jnp.concatenate(list(t), axis=0)

    L = gen.length
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((salt_len,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((L,), lambda i: (0,), memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((n_words * sub, 128),
                                lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * n_words * sub, 128),
                                        jnp.uint32)],
        interpret=interpret,
    )

    @jax.jit
    def fn(base_digits, iters, salt):
        (packed,) = raw(jnp.reshape(iters, (1,)).astype(jnp.int32),
                        salt, base_digits.astype(jnp.int32))
        words = packed.reshape(grid, n_words, sub, 128)
        return words.transpose(0, 2, 3, 1).reshape(batch, n_words)

    return fn


def pmkid_lanes(byts, essid_vals, essid_len: int, msg_vals, iters,
                shape):
    """The kernel math as a PURE function: candidate byte arrays ->
    4 PMKID words, shared verbatim by the pallas kernel (SMEM scalar
    reads) and the eager oracle tests (python ints / tiny arrays) --
    one source of truth for the key padding, PBKDF2 chaining, PMK
    assembly, and PMKID truncation."""
    # one-block big-endian key words, RAW zero padding (the HMAC key
    # block is a full block -- no 0x80 marker)
    # PMK = first 8 words of T1 || T2 (the shared generic PBKDF2 body)
    pmk = pbkdf2_lanes(byts, essid_vals, essid_len, iters, 8, shape)
    init = _init_state(shape)
    K2 = [jnp.zeros(shape, jnp.uint32) for _ in range(16)]
    for i in range(8):
        K2[i] = pmk[i]
    istate2 = _compress(init, [k ^ jnp.uint32(_IPAD) for k in K2], shape)
    ostate2 = _compress(init, [k ^ jnp.uint32(_OPAD) for k in K2], shape)
    as_u32 = (lambda x: x.astype(jnp.uint32)
              if hasattr(x, "astype") else jnp.uint32(x))
    msg5 = tuple(jnp.full(shape, jnp.uint32(0)) | as_u32(msg_vals[i])
                 for i in range(5))
    return _hmac20(istate2, ostate2, msg5, shape)[:4]


def make_pmkid_pallas_fn(gen, batch: int, essid_len: int,
                         sub: int = SUB, interpret: bool = False):
    """fn(base_digits int32[L], n_valid int32[1], iters int32[1],
    essid int32[essid_len], msg5 int32[5], target int32[4]) ->
    (counts int32[G,1], hit_lanes int32[G,1])."""
    if sub > 128:
        # same guard as pallas_mask: count and hit_lane+1 must fit the
        # packed 16-bit output fields
        raise ValueError("sub > 128 overflows the packed 16-bit "
                         "count/lane output fields")
    tile = sub * 128
    if batch % tile:
        raise ValueError(f"batch {batch} not a multiple of tile {tile}")
    if not pmkid_kernel_eligible(gen, [essid_len]):
        raise ValueError("pmkid mask job not kernel-eligible")
    seg_tables = segment_tables(gen.charsets)
    radices = gen.radices
    length = gen.length
    grid = batch // tile

    def kernel(nvalid_ref, iters_ref, essid_ref, msg_ref, tgt_ref,
               base_ref, out_ref):
        shape = (sub, 128)
        pid = pl.program_id(0)
        lane = (lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + lax.broadcasted_iota(jnp.int32, shape, 1))
        byts = decode_candidate_bytes(radices, seg_tables, length,
                                      base_ref, pid * tile, lane, tile)
        pmkid = pmkid_lanes(byts, [essid_ref[p] for p in range(essid_len)],
                            essid_len, [msg_ref[i] for i in range(5)],
                            iters_ref[0], shape)
        valid = (lane + pid * tile) < nvalid_ref[0]
        found = valid
        for i in range(4):
            found = found & (pmkid[i] == tgt_ref[i].astype(jnp.uint32))
        count = jnp.sum(found.astype(jnp.int32))
        hit_lane = jnp.max(jnp.where(found, lane, -1))
        out_ref[...] = jnp.full((8, 128), (count << 16) | (hit_lane + 1),
                                jnp.int32)

    L = gen.length
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((essid_len,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((5,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((4,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((L,), lambda i: (0,), memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * 8, 128), jnp.int32)],
        interpret=interpret,
    )

    def fn(base_digits, n_valid, iters, essid, msg5, target):
        (packed,) = raw(n_valid, iters, essid, msg5, target,
                        base_digits)
        p = packed[::8, 0:1]
        return p >> 16, (p & 0xFFFF) - 1

    return fn


def make_pmkid_kernel_step(gen, batch: int, essid_len: int,
                           hit_capacity: int = 64,
                           interpret: bool = False, sub: int = None):
    """Per-target crack step: step(base_digits, n_valid, iters,
    essid int32[essid_len], msg5 int32[5], target int32[4]) ->
    (count, lanes, tpos)."""
    sub = SUB if sub is None else sub
    tile = sub * 128
    batch = max(tile, (batch // tile) * tile)
    fn = make_pmkid_pallas_fn(gen, batch, essid_len, sub=sub,
                              interpret=interpret)

    @jax.jit
    def step(base_digits, n_valid, iters, essid, msg5, target):
        counts, hit_lanes = fn(
            base_digits.astype(jnp.int32),
            jnp.reshape(n_valid, (1,)).astype(jnp.int32),
            jnp.reshape(iters, (1,)).astype(jnp.int32),
            essid, msg5, target)
        return reduce_tile_hits(counts, hit_lanes, hit_capacity, tile)

    step.batch = batch
    return step


def target_kernel_args(target):
    """Target -> (essid_len, essid int32, msg5 int32, tgt int32)."""
    essid = target.params["essid"]
    msg = b"PMK Name" + target.params["mac_ap"] + target.params["mac_sta"]
    return (len(essid),
            jnp.asarray(np.frombuffer(essid, np.uint8).astype(np.int32)),
            jnp.asarray(np.frombuffer(msg, ">u4").astype(np.uint32)
                        .view(np.int32)),
            jnp.asarray(np.frombuffer(target.digest, ">u4")
                        .astype(np.uint32).view(np.int32)))
