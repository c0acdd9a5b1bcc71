"""Pallas PDF user-password kernel: vector-rate RC4 cascade.

The XLA PDF R3 check is gather-bound: its 20 RC4 passes per candidate
each lower the KSA's data-dependent swaps to per-lane SERIAL gathers
— the bcrypt/krb5 failure mode, 20x over.  This kernel applies the
krb5 RC4 layout (ops/pallas_krb5.py) to the whole Algorithm-4/5
check:

- candidates on the SUBLANE axis, every working value an (SUBC, 128)
  lane-replicated tile;
- each candidate's 256-entry RC4 S state is two (SUBC, 128) uint32
  halves with the ENTRY INDEX along lanes, so S[j] is the hardware's
  per-sublane `take_along_axis` gather and swap writes are lane-iota
  selects — no scatter (ops/pallas_mask.gather256/swap256, shared);
- the whole chain runs in one kernel with zero HBM round-trips:
  mask decode -> Algorithm-2 MD5 (block 1 = padded password + O,
  block 2 target-constant) -> the 50-fold MD5 stretch (R3+) -> the
  RC4 cascade (R2: one KSA + 4 keystream bytes; R3+: 20 passes of
  KSA + 16-byte PRGA over U', key XOR pass-index per RFC/hashcat
  10500) -> exact compare;
- the spec PAD fill of block 1 is COMPILE-TIME wiring (mask attacks
  have one static length), and O / block-2 / MD5(PAD||ID) / stored-U
  words are runtime SMEM scalars, so ONE compiled kernel per
  (mask, rev, key_len) serves every target in a hashlist.

Per-candidate cost at R3/128-bit: 52 MD5 compressions + 20 x (256-step
KSA + 16 PRGA steps) — ~21x the krb5 kernel's RC4 work, so the
expected rate is a few tens of kH/s against the XLA path's 3.2 kH/s.

Spec reference: engines/cpu/pdf.py (Algorithm 2/4/5); device XLA form
engines/device/pdf.py.
"""

from __future__ import annotations


import numpy as np

from dprf_tpu.utils import env as envreg  # noqa: E402 -- stdlib-only
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dprf_tpu.engines.cpu.pdf import PAD
from dprf_tpu.ops import pallas_krb5 as _krb5
from dprf_tpu.ops.pallas_mask import (decode_candidate_bytes,
                                      gather256, mask_supported,
                                      segment_tables, swap256)

#: chunks per grid cell (tile = SUBC * CHUNKS candidates).  The PDF
#: body is ~21x heavier than krb5's, so the default tile is smaller
#: to keep a single dispatch short.
CHUNKS = envreg.get_int("DPRF_PDF_CHUNKS")

_PAD_BYTES = np.frombuffer(PAD, np.uint8)


def pdf_kernel_eligible(gen, rev: int, key_len: int,
                        on_hardware: bool = False) -> bool:
    """Mask-attack jobs the kernel covers: any mask charset order
    (builtin segments or the Markov/scrambled unbounded mux), password
    no longer than the 32-byte Algorithm-2 pad buffer, the two
    deployed key widths (40-bit R2/R3, 128-bit R3+).

    key_len=5 is GATED OFF on real hardware until it has run there:
    its only recorded Mosaic compile attempt, on an older toolchain,
    hung (the lax.rem suspect is fixed but the kernel has never run
    on a chip).  DPRF_PDF_K5_KERNEL=1 re-enables it for a measuring
    session; interpret mode (tests) is always allowed."""
    if key_len == 5 and on_hardware and \
            not envreg.get_bool("DPRF_PDF_K5_KERNEL"):
        return False
    return (hasattr(gen, "charsets") and gen.length <= 32
            and mask_supported(gen.charsets)
            and rev >= 2 and key_len in (5, 16))


from dprf_tpu.ops.pallas_mask import (  # noqa: E402 -- shared
    md5_compress_lanes as _compress, md5_init_lanes as _md5_init)


def _block1_words(byts, length: int, o_ref, shape):
    """Algorithm-2 block 1: pad32(password) || O.  Bytes past the
    candidate come from the spec PAD string at static offsets (the
    mask length is compile-time), O words are runtime scalars."""
    words = []
    for w in range(8):
        acc = jnp.zeros(shape, jnp.uint32)
        for q in range(4):
            pos = 4 * w + q
            if pos < length:
                byte = byts[pos]
            else:
                byte = jnp.full(shape,
                                jnp.uint32(int(_PAD_BYTES[pos - length])))
            acc = acc | (byte << jnp.uint32(8 * q))
        words.append(acc)
    for w in range(8):
        words.append(jnp.full(shape, o_ref[w].astype(jnp.uint32)))
    return words


def _stretch50(digest, key_len: int, shape):
    """R3+ Algorithm-2 tail: 50 x MD5 over digest[:key_len]."""
    nw, rem = divmod(key_len, 4)
    keep = jnp.uint32((1 << (8 * rem)) - 1)
    zero = jnp.zeros(shape, jnp.uint32)

    def body(_, d):
        m = [zero] * 16
        for w in range(nw):
            m[w] = d[w]
        if rem:
            m[nw] = (d[nw] & keep) | jnp.uint32(0x80 << (8 * rem))
        else:
            m[nw] = jnp.full(shape, jnp.uint32(0x80))
        m[14] = jnp.full(shape, jnp.uint32(key_len * 8))
        return _compress(_md5_init(shape), m)

    return lax.fori_loop(0, 50, body, digest)


def _key_lanes(digest, key_len: int, shape):
    """Key bytes digest[:key_len] spread along the first key_len
    lanes (the krb5 KSA key layout, gathered by i % key_len)."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    kb = jnp.zeros(shape, jnp.uint32)
    for t in range(key_len):
        kb = jnp.where(lane == t,
                       (digest[t // 4] >> jnp.uint32(8 * (t % 4)))
                       & jnp.uint32(0xFF), kb)
    return kb


def _rc4_words(kb, key_len: int, pass_val, nwords: int, shape):
    """One full RC4 run: KSA with key bytes (kb lanes) XOR pass_val,
    then the first 4*nwords keystream bytes packed LE.  The KSA is the
    krb5 kernel's fori_loop form (3-array carry — the shape proven to
    lower; the unrolled form SIGABRTs Mosaic, see pallas_krb5.UNROLL).
    """
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    S_lo0 = lane.astype(jnp.uint32)
    S_hi0 = S_lo0 + jnp.uint32(128)

    def ksa(i, carry):
        # the key index i % key_len rides the carry as a wrapping
        # counter: key_len = 5 would need a real scalar modulo
        # (lax.rem), the suspect of the one recorded compile hang of
        # this kernel (older toolchain)
        S_lo, S_hi, j, t = carry
        i_rep = jnp.full(shape, i.astype(jnp.uint32))
        si = gather256(S_lo, S_hi, i_rep)
        ki = jnp.take_along_axis(
            kb, jnp.full(shape, t, jnp.int32), axis=1) ^ pass_val
        j = (j + si + ki) & jnp.uint32(255)
        sj = gather256(S_lo, S_hi, j)
        S_lo, S_hi = swap256(S_lo, S_hi, i_rep, sj, lane)
        S_lo, S_hi = swap256(S_lo, S_hi, j, si, lane)
        t = jnp.where(t + 1 == key_len, 0, t + 1)
        return S_lo, S_hi, j, t

    S_lo, S_hi, _, _ = lax.fori_loop(
        0, 256, ksa, (S_lo0, S_hi0, jnp.zeros(shape, jnp.uint32),
                      jnp.int32(0)))

    j = jnp.zeros(shape, jnp.uint32)
    words = []
    word = jnp.zeros(shape, jnp.uint32)
    for t in range(4 * nwords):         # PRGA, static i = t + 1 < 128
        i = t + 1
        si = jnp.broadcast_to(S_lo[:, i:i + 1], shape)
        j = (j + si) & jnp.uint32(255)
        sj = gather256(S_lo, S_hi, j)
        i_rep = jnp.full(shape, jnp.uint32(i))
        S_lo, S_hi = swap256(S_lo, S_hi, i_rep, sj, lane)
        S_lo, S_hi = swap256(S_lo, S_hi, j, si, lane)
        k = gather256(S_lo, S_hi, (si + sj) & jnp.uint32(255))
        word = word | (k << jnp.uint32(8 * (t % 4)))
        if t % 4 == 3:
            words.append(word)
            word = jnp.zeros(shape, jnp.uint32)
    return words


def _build_body(radices, seg_tables, length: int, rev: int,
                key_len: int, sub: int, chunks: int):
    """(pid, base, n_valid, o[8], b2[16], x0[4], u[4]) ->
    (count, hit_index) scalars, hit_index tile-local."""
    tile = sub * chunks

    def body(pid, base, n_valid, o_ref, b2_ref, x0_ref, u_ref):
        shape = (sub, 128)
        row = lax.broadcasted_iota(jnp.int32, shape, 0)

        def chunk(c, acc):
            count, hit = acc
            start = pid * tile + c * sub
            gidx = start + row
            byts = decode_candidate_bytes(radices, seg_tables, length,
                                          base, start, row, sub)
            b1 = _block1_words(byts, length, o_ref, shape)
            state = _compress(_md5_init(shape), b1)
            b2 = [jnp.full(shape, b2_ref[w].astype(jnp.uint32))
                  for w in range(16)]
            digest = _compress(state, b2)
            if rev >= 3:
                digest = _stretch50(digest, key_len, shape)
            kb = _key_lanes(digest, key_len, shape)
            if rev == 2:
                ks = _rc4_words(kb, key_len, jnp.uint32(0), 1, shape)
                found = ks[0] == jnp.full(shape,
                                          u_ref[0].astype(jnp.uint32))
            else:
                u0 = [jnp.full(shape, x0_ref[w].astype(jnp.uint32))
                      for w in range(4)]

                def cascade(p, u):
                    ks = _rc4_words(kb, key_len,
                                    p.astype(jnp.uint32), 4, shape)
                    return tuple(uw ^ kw for uw, kw in zip(u, ks))

                u = lax.fori_loop(0, 20, cascade, tuple(u0))
                found = jnp.full(shape, True)
                for w in range(4):
                    found = found & (u[w] == jnp.full(
                        shape, u_ref[w].astype(jnp.uint32)))
            found = found & (gidx < n_valid)
            lane0 = lax.broadcasted_iota(jnp.int32, shape, 1) == 0
            found = found & lane0
            count = count + jnp.sum(found.astype(jnp.int32))
            hit = jnp.maximum(
                hit, jnp.max(jnp.where(found, c * sub + row, -1)))
            return count, hit

        return lax.fori_loop(0, chunks, chunk,
                             (jnp.int32(0), jnp.int32(-1)))

    return body


def make_pdf_pallas_fn(gen, batch: int, rev: int, key_len: int,
                       sub: int = 0, chunks: int = 0,
                       interpret: bool = False):
    """fn(base_digits, n_valid[1], o[8], b2[16], x0[4], u[4]) ->
    (counts int32[grid, 1], hit_idx int32[grid, 1]); R2 ignores x0
    and reads only u[0] (pass zeros for the rest).  The sublane count
    defaults to the krb5 kernel's tuned SUBC (module attr, so tests
    patch one place)."""
    sub = sub or _krb5.SUBC
    chunks = chunks or CHUNKS
    tile = sub * chunks
    if batch % tile or batch <= 0:
        raise ValueError(f"batch {batch} must be a multiple of "
                         f"tile {tile}")
    if tile > 0x7FFF:
        raise ValueError(f"tile {tile} exceeds the 15-bit packed "
                         "output limit (lower DPRF_KRB5_SUBC/"
                         "DPRF_PDF_CHUNKS)")
    if not pdf_kernel_eligible(gen, rev, key_len,
                               on_hardware=not interpret):
        raise ValueError("pdf kernel: job not eligible")
    grid = batch // tile
    seg_tables = segment_tables(gen.charsets)
    body = _build_body(gen.radices, seg_tables, gen.length, rev,
                       key_len, sub, chunks)

    def kernel(base_ref, nvalid_ref, o_ref, b2_ref, x0_ref, u_ref,
               out_ref):
        count, hit = body(pl.program_id(0), base_ref, nvalid_ref[0],
                          o_ref, b2_ref, x0_ref, u_ref)
        out_ref[...] = jnp.full((8, 128), (count << 16) | (hit + 1),
                                jnp.int32)

    L = gen.length
    smem = lambda n: pl.BlockSpec((n,), lambda i: (0,),
                                  memory_space=pltpu.SMEM)
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[smem(L), smem(1), smem(8), smem(16), smem(4),
                  smem(4)],
        out_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * 8, 128), jnp.int32)],
        interpret=interpret,
    )

    def fn(base_digits, n_valid, o, b2, x0, u):
        (packed,) = raw(base_digits, n_valid, o, b2, x0, u)
        p = packed[::8, 0:1]
        return p >> 16, (p & 0xFFFF) - 1

    return fn


def make_pdf_crack_step(gen, batch: int, rev: int, key_len: int,
                        hit_capacity: int = 64, sub: int = 0,
                        chunks: int = 0, interpret: bool = False):
    """Kernel crack step with the worker (count, lanes, tpos)
    contract: step(base_digits, n_valid, o, b2, x0, u)."""
    from dprf_tpu.ops.pallas_mask import reduce_tile_hits

    sub = sub or _krb5.SUBC
    chunks = chunks or CHUNKS
    tile = sub * chunks
    fn = make_pdf_pallas_fn(gen, batch, rev, key_len, sub=sub,
                            chunks=chunks, interpret=interpret)

    @jax.jit
    def step(base_digits, n_valid, o, b2, x0, u):
        counts, lanes = fn(base_digits.astype(jnp.int32),
                           jnp.reshape(n_valid, (1,)).astype(jnp.int32),
                           o, b2, x0, u)
        return reduce_tile_hits(counts, lanes, hit_capacity, tile)

    return step


def target_scalars(target) -> tuple:
    """Target.params -> the kernel's four runtime SMEM arrays
    (o[8], b2[16], x0[4], u[4]); R2's u[0] carries the keystream
    expectation U[0:4] ^ PAD[0:4] (stored U = RC4(key, PAD)).

    PAIRED with engines/device/pdf._target_args: both marshal the same
    $pdf$ params (there into the XLA step's argument layout, here into
    flat SMEM scalars) via the shared _block2_words/_PAD_W0 — a format
    change must touch both or the kernel and XLA paths diverge."""
    import hashlib

    from dprf_tpu.engines.device.pdf import _PAD_W0, _block2_words

    p = target.params

    def i32(data: bytes) -> jnp.ndarray:
        return jnp.asarray(np.frombuffer(data, "<u4").view(np.int32))

    o = i32(p["o"])
    b2 = jnp.asarray(_block2_words(p).view(np.int32))
    if p["rev"] == 2:
        x0 = jnp.zeros((4,), jnp.int32)
        w0 = int.from_bytes(p["u"][:4], "little") ^ _PAD_W0
        u = jnp.asarray(np.array([w0, 0, 0, 0], np.uint32)
                        .view(np.int32))
    else:
        x0 = i32(hashlib.md5(PAD + p["id"]).digest())
        u = i32(p["u"][:16])
    return o, b2, x0, u
