"""Fused mask->hash->compare Pallas TPU kernels for the single-block
unsalted engines (MD5, SHA-1, NTLM).

Why a kernel at all: the XLA path (ops/pipeline.py) materializes the
candidate block uint8[B, L] and the digest uint32[B, W] in HBM between
fusions.  At the throughputs these engines target, those intermediate
writes are the bandwidth floor.  This kernel keeps the whole chain --
index -> candidate decode, charset lookup, message packing (with
UTF-16LE widening for NTLM), the full compression rounds, compare, hit
reduction -- in VMEM/registers, and writes one packed int32 per grid
cell -- (count << 16) | (hit_lane + 1), splatted over the minimum
(8, 128) Mosaic output block -- back to HBM: ~4096/TILE bytes per
candidate (1 byte at sub=32) instead of ~(L+4W).

The compression rounds themselves are imported from the same modules
the XLA path uses (md5_rounds/sha1_rounds/md4_rounds/sha256_rounds/
sha512_rounds), so there is one source of truth per algorithm.  The
SHA-256 and SHA-512-family kernels use the statically-unrolled
rolling-schedule round forms (fori_loop+concatenate carries do not
lower to Mosaic) and are TPU-only: XLA:CPU takes minutes to compile
the flat unrolled graphs, so off-TPU those engines ride the XLA
pipeline and the kernel bodies are validated eagerly via
emulate_mask_kernel.

Design choices forced by the VPU:
- The decode is an odometer, not a division (decode_candidate_bytes):
  the vector unit has no integer divide, and a mixed-radix decode of
  each lane's whole index was 27 emulated divisions a candidate at
  ?l x9.  A tile's first index is a scalar, added to the base digits
  once a tile on the scalar unit; a lane's index inside its tile has
  digits only in the last few positions, where tile digit + lane digit
  + carry is one compare and one conditional subtract; every position
  above them takes one of two scalar bytes, chosen by one carry bit.
- Charset lookup is arithmetic where possible: a charset in digit
  order is piecewise byte = digit + delta, so the lookup is a few
  vectorized `where` adds (7 segments for ?a, 1 for ?l/?u/?d).
  Positions needing more than MAX_SEGMENTS segments (Markov-permuted
  orders, scrambled custom charsets) use a 256-entry LUT with the
  digit index along the LANE axis instead — one per-sublane
  `take_along_axis` gather, the krb5/bcrypt S-box layout — so every
  mask now rides the kernel path (r5; previously the XLA fallback).
- Hit extraction per tile is count + single-lane arithmetic max.  Two
  hits in one TILE-candidate tile (vanishingly rare for random
  targets; always visible in the count) force the caller's exact host
  rescan, so correctness never depends on the rarity.
- All lane arithmetic is int32, so a step's batch is capped below 2^31
  candidates (the factory enforces it); larger sweeps are driven as
  multiple steps by the worker, exactly like the XLA path.
"""

from __future__ import annotations

from typing import Optional, Sequence

from dprf_tpu.utils import env as envreg

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dprf_tpu.ops import md4 as md4_ops
from dprf_tpu.ops import md5 as md5_ops
from dprf_tpu.ops import sha1 as sha1_ops
from dprf_tpu.ops import sha256 as sha256_ops
from dprf_tpu.ops import sha512 as sha512_ops

#: sublane count per grid cell; TILE = SUB * 128 candidate lanes.
#: DPRF_PALLAS_SUB overrides for tuning (`dprf tune --rungs sub`
#: sweeps it).  Bigger tiles amortize the per-grid-cell scalar work --
#: the md5 kernel's rate rose monotonically over SUB 8..128 in a sweep
#: on an earlier installation -- so the packed-output format's
#: maximum (128) is the default.
SUB = envreg.get_int("DPRF_PALLAS_SUB")
TILE = SUB * 128
#: charsets needing more piecewise segments than MAX_SEGMENTS use the
#: lane-axis LUT decode in kernels (charset_lut below) and the gather
#: decode in the XLA pipeline; the bound and the segment model are
#: shared with the generator's mux decode.
from dprf_tpu.generators.mask import (MAX_SEGMENTS,  # noqa: E402,F401
                                      charset_segments, segment_mux)

# -- multi-target prefilter parameters ---------------------------------------
#: hard cap on kernel-path targets: past it the capped probe bitmap
#: (KERNEL_PROBE_GROUPS) no longer reaches its false-positive budget.
MAX_TARGETS = 8192

#: 128-block groups the in-kernel blocked-probe bitmap may span.  The
#: kernel gathers a lane's 512-bit block with one take_along_axis per
#: (group, word) pair, so groups bound both the gather count (16 per
#: group) and the bitmap footprint (64 KiB at 8 groups) -- VMEM-small
#: and constant in N.  At MAX_TARGETS the capped bitmap still reaches
#: the DPRF_PALLAS_PROBE_FP budget (~4e-8 analytic at 8192 keys).
KERNEL_PROBE_GROUPS = 8


def check_batch(batch: int, sub: int) -> int:
    """Shared guard for every packed-output mask kernel factory
    (this module's, pallas_ext's, pallas_keccak's): sub bound for the
    16-bit packed count/lane fields, tile alignment, and the int32
    index-arithmetic headroom (the decode's first mixed-radix addition
    computes base_digit + tile start with base_digit <= 255, so the
    index needs 256 of headroom below 2^31 or the last tiles wrap and
    decode wrong candidates).  Returns the grid size."""
    if sub > 128:
        raise ValueError("sub > 128 overflows the packed 16-bit "
                         "count/lane output fields")
    tile = sub * 128
    if batch % tile:
        raise ValueError(f"batch {batch} not a multiple of tile {tile}")
    if batch > (1 << 31) - 256:
        raise ValueError("batch must fit in int32 lane arithmetic "
                         "(max 2**31 - 256)")
    return batch // tile


def _make_core(rounds_fn, init_words):
    """Wrap a shared rounds function into a kernel digest core:
    broadcast the initial state, run the rounds, add the Davies-Meyer
    feed-forward."""
    def core(m, shape):
        init = [jnp.uint32(int(w)) for w in init_words]
        out = rounds_fn(*(jnp.full(shape, w) for w in init), m)
        return tuple(x + i for x, i in zip(out, init))
    return core


_md5_core = _make_core(md5_ops.md5_rounds, md5_ops.INIT)
_md4_core = _make_core(md4_ops.md4_rounds, md4_ops.INIT)
_sha1_core = _make_core(sha1_ops.sha1_rounds, sha1_ops.INIT)
_sha256_core = _make_core(sha256_ops.sha256_rounds, sha256_ops.INIT)


def _make_sha512_core(init_words, out_words: int):
    """SHA-512-family digest core over (hi, lo) uint32 pairs: m is the
    32 words of one 128-byte block; returns the first out_words uint32
    digest words (16 for sha512, 12 for the sha384 truncation)."""
    def core(m, shape):
        pairs = [(m[2 * i], m[2 * i + 1]) for i in range(16)]
        init = [(jnp.uint32(v >> 32), jnp.uint32(v & 0xFFFFFFFF))
                for v in init_words]
        vars8 = tuple((jnp.full(shape, h), jnp.full(shape, l))
                      for h, l in init)
        out = sha512_ops.sha512_rounds(vars8, pairs)
        res = []
        for v, iv in zip(out, init):
            h, l = sha512_ops._add64(v, iv)
            res.extend([h, l])
        return tuple(res[:out_words])
    return core


_sha512_core = _make_sha512_core(sha512_ops.INIT512, 16)
_sha384_core = _make_sha512_core(sha512_ops.INIT384, 12)

#: engine name -> (rounds core, digest words, big-endian packing,
#: UTF-16LE widening)
CORES = {
    "md5": (_md5_core, 4, False, False),
    "sha1": (_sha1_core, 5, True, False),
    "sha-1": (_sha1_core, 5, True, False),
    "sha256": (_sha256_core, 8, True, False),
    "sha-256": (_sha256_core, 8, True, False),
    "ntlm": (_md4_core, 4, False, True),
    "sha512": (_sha512_core, 16, True, False),
    "sha-512": (_sha512_core, 16, True, False),
    "sha384": (_sha384_core, 12, True, False),
    "sha-384": (_sha384_core, 12, True, False),
}

#: engines whose compression consumes a 128-byte block (32 message
#: words, 128-bit length field) instead of the 64-byte default.
WIDE_BLOCK = frozenset(("sha512", "sha-512", "sha384", "sha-384"))


def pallas_mode() -> Optional[dict]:
    """Whether the Pallas kernel path should be used, and how.

    DPRF_PALLAS=0 disables it; =1 forces it (interpret mode off-TPU,
    for tests); default "auto" uses it on real TPU only.  Returns
    kwargs for the step factory, or None for the XLA path.
    """
    mode = envreg.get_str("DPRF_PALLAS")
    if mode == "0":
        return None
    import jax
    if jax.default_backend() == "tpu":
        return {"interpret": False}
    if mode == "1":
        return {"interpret": True}
    return None


# charset_segments / MAX_SEGMENTS: canonical segment model lives with
# the generator (generators/mask.py -- the XLA mux uses the same
# tables); imported above and re-exported for the kernel builders.


def mask_supported(charsets: Sequence[bytes]) -> bool:
    """True if every position decodes on the kernel path.  Since r5
    that is EVERY well-formed mask: positions within MAX_SEGMENTS
    arithmetic pieces use the segment mux; arbitrary orders (Markov
    permutations, scrambled custom charsets) use a 256-entry LUT on
    the lane axis (charset_lut below) -- the per-sublane gather layout
    proven by the bcrypt/krb5 kernels.  The predicate keeps only the
    structural requirement: nonempty byte charsets."""
    return all(1 <= len(cs) <= 256 for cs in charsets)


def charset_lut(cs: bytes) -> np.ndarray:
    """Arbitrary charset -> (2, 128) uint32 LUT with the DIGIT INDEX
    along lanes (row 0 digits 0..127, row 1 digits 128..255) -- the
    krb5 S-box layout, so the lookup is one per-sublane
    `take_along_axis` gather + a row select, independent of how many
    contiguous runs the byte values form."""
    tbl = np.zeros((2, 128), np.uint32)
    arr = np.frombuffer(cs, np.uint8)
    tbl.reshape(-1)[:len(arr)] = arr
    return tbl


def position_tables(charsets: Sequence[bytes]):
    """Per-position decode tables for THIS module's fast mask kernels:
    (proc_tables, luts) where proc entries are segment lists
    (arithmetic mux) or ("lut", k) markers, and luts is the stacked
    uint32[2 * n_lut, 128] LUT array (None when every position is
    arithmetic).  pallas_call forbids captured vector constants, so
    the LUT rides as a kernel INPUT (this module's fast cores and the
    pallas_ext salted/nested kernels); the heavy kernel families
    (krb5/pdf/7z/pbkdf2/keccak) instead run the segment mux UNBOUNDED
    -- up to ~2 ops per contiguous run per position, noise next to
    their per-candidate work -- via segment_tables below."""
    proc, luts = [], []
    for cs in charsets:
        segs = charset_segments(cs)
        if len(segs) <= MAX_SEGMENTS:
            proc.append(segs)
        else:
            proc.append(("lut", len(luts)))
            luts.append(charset_lut(cs))
    luts_np = (np.concatenate(luts, axis=0).astype(np.uint32)
               if luts else None)
    return proc, luts_np


def segment_tables(charsets: Sequence[bytes]) -> list:
    """Unbounded per-position segment lists: correct for ANY charset
    (segment_mux reconstructs arbitrary orders with one compare+select
    per contiguous run).  The heavy kernel families use this so Markov
    and scrambled custom charsets stay kernel-eligible without LUT
    input plumbing."""
    return [charset_segments(cs) for cs in charsets]


def md5_init_lanes(shape):
    """MD5 initial state as lane-replicated word tuples -- shared by
    the kernel bodies that chain raw compressions (krb5 HMAC tower,
    PDF Algorithm 2) rather than the one-shot digest cores above."""
    return tuple(jnp.full(shape, jnp.uint32(int(w)))
                 for w in md5_ops.INIT)


def md5_compress_lanes(state, m):
    """One MD5 compression on lane-replicated word tuples (state 4,
    m 16) with the Davies-Meyer feed-forward."""
    out = md5_ops.md5_rounds(*state, m)
    return tuple(x + s for x, s in zip(out, state))


def take_lanes(x, idx):
    """x[s, idx[s, l]] for (sub, 128) tiles: the hardware's native
    per-sublane gather, the lookup of every kernel body."""
    return jnp.take_along_axis(x, idx, axis=1)


def select_lanes(x, idx):
    """take_lanes with no gather, for a kernel body run as plain XLA
    (make_tile_reprobe): 128 compare-selects a lane.  The TPU compiler
    wraps every XLA gather's indices in a custom call, and a program's
    custom calls are what a device trace reads as its kernel."""
    at = idx[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, idx.shape + (128,), idx.ndim)
    return jnp.sum(jnp.where(at, x[:, None, :], jnp.zeros((), x.dtype)),
                   axis=-1, dtype=x.dtype)


def gather256(lo, hi, idx, take=take_lanes):
    """Per-sublane 256-entry lookup: table halves lo/hi uint32[sub, 128]
    with the ENTRY INDEX along lanes, idx uint32[sub, 128] in 0..255 ->
    values uint32[sub, 128].  The hardware's native per-sublane
    `take_along_axis` gather + a half select -- the S-box layout proven
    by the bcrypt/krb5 kernels; shared by the RC4 kernels (krb5, pdf)
    and the LUT charset decode."""
    idx7 = (idx & jnp.uint32(127)).astype(jnp.int32)
    glo = take(lo, idx7)
    ghi = take(hi, idx7)
    return jnp.where(idx < jnp.uint32(128), glo, ghi)


def swap256(lo, hi, pos, val, lane):
    """table[pos] = val via lane-iota compare + select (no scatter);
    lane is the int32 lane-index iota of the tile."""
    at = lane == (pos & jnp.uint32(127)).astype(jnp.int32)
    lo = jnp.where((pos < jnp.uint32(128)) & at, val, lo)
    hi = jnp.where((pos >= jnp.uint32(128)) & at, val, hi)
    return lo, hi


def _lut_byte(digit, lo_row, hi_row, take=take_lanes):
    """Lane-axis LUT lookup for int32 digit tiles of shape (sub, 128):
    rows are (128,) uint32 halves of the 256-entry table."""
    shape = digit.shape
    return gather256(jnp.broadcast_to(lo_row[None, :], shape),
                     jnp.broadcast_to(hi_row[None, :], shape),
                     digit.astype(jnp.uint32), take)


def kernel_eligible(engine_name: str, gen, n_targets: int) -> bool:
    """One kernel-eligibility predicate for engine selection and bench.
    Non-CORES names (nested double-hash, mysql41) dispatch to the
    extended-kernel module."""
    if engine_name not in CORES:
        from dprf_tpu.ops import pallas_ext
        return pallas_ext.nested_eligible(engine_name, gen, n_targets)
    if not 1 <= n_targets <= MAX_TARGETS:
        return False
    if not hasattr(gen, "charsets"):
        return False
    if engine_name in ("sha256", "sha-256") or engine_name in WIDE_BLOCK:
        # The statically-unrolled SHA-256 graph (and the even larger
        # 80-round SHA-512 pair graph) compiles fine through Mosaic's
        # path but takes XLA:CPU many minutes, so these kernels are
        # TPU-only; off-TPU (tests, --device cpu fallback) they use
        # the XLA pipeline.  The kernel bodies themselves are
        # validated eagerly via emulate_mask_kernel.
        import jax as _jax
        if _jax.default_backend() != "tpu":
            return False
    widen = CORES[engine_name][3]
    max_len = (27 if widen
               else 111 if engine_name in WIDE_BLOCK   # 128-byte block
               else 55)
    return gen.length <= max_len and mask_supported(gen.charsets)


def kernel_probe_rows(twords: np.ndarray, fp: Optional[float] = None):
    """Target digest words uint32[N, W] -> the PR 14 blocked-Bloom
    probe bitmap in the kernel's lane-major layout.

    The bit layout is targets/probe.bloom_fill -- the SAME bits the XLA
    ProbeTable path sets -- transposed so the BLOCK index runs along
    the 128-lane axis: row g*BLOCK_WORDS + w, lane b holds word w of
    block g*128 + b.  A lane's whole 512-bit block then gathers with
    one take_along_axis per (group, word) pair, the proven S-box
    idiom, and the k double-hashed probes resolve inside registers.

    Sized by DPRF_PALLAS_PROBE_FP (NOT the XLA path's
    DPRF_TARGETS_FP_BUDGET): a superstep window drains through a tiny
    device-resident hit buffer, so false maybes must be rare per
    *window*, not merely per batch.  Capped at KERNEL_PROBE_GROUPS
    groups so the gather tree stays bounded.

    Returns (rows uint32[n_grp * BLOCK_WORDS, 128], block_bits, k,
    n_grp, fp_est)."""
    from dprf_tpu.targets import probe as probe_mod
    if fp is None:
        fp = envreg.get_float("DPRF_PALLAS_PROBE_FP")
    n = int(twords.shape[0])
    if n > MAX_TARGETS:
        raise ValueError(f"kernel path supports <= {MAX_TARGETS} targets")
    max_bits = KERNEL_PROBE_GROUPS * 128 * probe_mod.BLOCK_BITS
    m_bits, k, fp_est = probe_mod.kernel_bloom_geometry(n, fp, max_bits)
    words = probe_mod.bloom_fill(np.ascontiguousarray(twords), m_bits, k)
    bw = probe_mod.BLOCK_WORDS
    n_blocks = m_bits // probe_mod.BLOCK_BITS
    block_bits = n_blocks.bit_length() - 1
    n_grp = max(1, n_blocks // 128)
    if n_blocks < 128:
        # pad to one full 128-block group: block indices stay below
        # n_blocks, so the zero lanes are never addressed
        pad = np.zeros(128 * bw, np.uint32)
        pad[:words.size] = words
        words = pad
    rows = words.reshape(n_grp, 128, bw).transpose(0, 2, 1)
    return (np.ascontiguousarray(rows).reshape(n_grp * bw, 128),
            block_bits, k, n_grp, fp_est)


def probe_block_found(digest, rows, valid, block_bits: int, k: int,
                      n_grp: int, shape, take=take_lanes):
    """In-kernel blocked-Bloom probe over kernel_probe_rows state: a
    lane survives iff all k double-hashed bits of its block are set.
    Real hits always survive (their bits were set from the matching
    target's own digest words); the caller treats survivors as
    sentinel-tagged maybes and verifies each with one host oracle
    hash, so a false positive can never surface as a hit."""
    from dprf_tpu.targets.probe import BLOCK_BITS, BLOCK_WORDS, _GOLDEN
    h1 = digest[0]
    h2 = digest[1] | jnp.uint32(1)
    # the alternating probe pairs of targets/probe.bloom_fill
    h3 = digest[2] if len(digest) > 3 else h1
    h4 = (digest[3] | jnp.uint32(1)) if len(digest) > 3 else h2
    if block_bits:
        block = ((h1 * jnp.uint32(_GOLDEN))
                 >> jnp.uint32(32 - block_bits)).astype(jnp.int32)
    else:
        block = jnp.zeros(shape, jnp.int32)
    lane_idx = block & 127
    grp = block >> 7
    # gather the lane's full 512-bit block: one per-sublane gather per
    # (group, word), selected by the lane's group index
    bw = []
    for w in range(BLOCK_WORDS):
        acc = None
        for g in range(n_grp):
            row = jnp.broadcast_to(rows[g * BLOCK_WORDS + w][None, :],
                                   shape)
            got = take(row, lane_idx)
            acc = got if acc is None else jnp.where(grp == g, got, acc)
        bw.append(acc)
    found = valid
    for j in range(k):
        i = j >> 1
        a, b = (h3, h4) if j & 1 else (h1, h2)
        g = a + jnp.uint32(2 * i + 1) * b
        bit = g & jnp.uint32(BLOCK_BITS - 1)
        widx = (bit >> jnp.uint32(5)).astype(jnp.int32)
        word = bw[0]
        for w in range(1, BLOCK_WORDS):
            word = jnp.where(widx == w, bw[w], word)
        found = found & (((word >> (bit & jnp.uint32(31)))
                          & jnp.uint32(1)) == 1)
    return found


# piecewise charset lookup shared with the generator's XLA mux
_decode_byte = segment_mux


def lane_digit_count(radices, lane_bound: int) -> int:
    """K: the low mask positions a lane index below lane_bound has
    digits in -- the least K with prod(radices[-K:]) >= lane_bound
    (all of them where the whole keyspace is smaller)."""
    k, span = 0, 1
    while k < len(radices) and span < lane_bound:
        k += 1
        span *= radices[-k]
    return k


def _quotient(n, r: int):
    """n // r for int32 n in [0, 2^14) and a radix r in [2, 256], with
    no division: (n * m) >> sh, m = ceil(2^sh / r).  Exact because
    n * (m * r - 2^sh) < 2^14 * r <= 2^sh (Granlund and Montgomery's
    condition), and n * m < 2^31."""
    sh = 14 + (r - 1).bit_length()
    return (n * (-(-(1 << sh) // r))) >> sh


def decode_candidate_bytes(radices, seg_tables, length: int, base, start,
                           lane, lane_bound: int, luts=None,
                           take=take_lanes):
    """Candidate bytes of keyspace index digits(base) + start + lane,
    per mask position -- the shared decode of every mask kernel body,
    an odometer with no division on the vector unit.

    start is the SCALAR part of the index (a tile's first candidate:
    pid * tile, plus the window offset), lane the int32 tile of
    per-lane parts in [0, lane_bound), lane_bound static.  Three steps:

    - scalar, once a tile: base digits + start by the mixed-radix add
      (one scalar div/rem a position) give the digits of the tile's
      first candidate, and a second scalar odometer their successor in
      the upper positions;
    - a lane index below lane_bound has digits only in the low
      K = lane_digit_count positions.  They are the same for every
      tile: quotients by an exact reciprocal multiply and shift
      (_quotient), no division;
    - in the low positions tile digit + lane digit + carry < 2 r: one
      compare, one conditional subtract.  The carry out of them, c0,
      is all an upper position sees of the lane: its byte is a select
      between the bytes of the tile's digit and of the successor's,
      both scalars.

    seg_tables entries are segment lists (arithmetic mux, any length)
    or ("lut", k) markers resolving into `luts` rows [2k, 2k+2)
    (position_tables; lane must then be a (sub, 128) tile -- every
    kernel body's is).  An index past the keyspace wraps (the carry
    out of position 0 is dropped); callers mask such lanes."""
    if not 2 <= lane_bound <= 1 << 14:
        raise ValueError("lane_bound outside [2, 2^14]: the range "
                         "_quotient is exact in")
    lut_arr = luts[...] if luts is not None else None
    k = lane_digit_count(radices, lane_bound)
    low = range(length - 1, length - 1 - k, -1)
    upper = range(length - 1 - k, -1, -1)

    def is_lut(p):
        return isinstance(seg_tables[p], tuple) and seg_tables[p][0] == "lut"

    def byte_of(p, digit):
        if is_lut(p):
            row = 2 * seg_tables[p][1]
            return _lut_byte(digit, lut_arr[row], lut_arr[row + 1], take)
        return _decode_byte(digit, seg_tables[p])

    # scalar side: the digits of the tile's first candidate
    tdig: list = [None] * length
    carry = start
    for p in range(length - 1, -1, -1):
        s = base[p] + carry
        tdig[p] = jax.lax.rem(s, jnp.int32(radices[p]))
        carry = jax.lax.div(s, jnp.int32(radices[p]))

    # lane side: the low digits of the lane index
    ldig = {}
    q, span = lane, 1
    for p in low:
        r = radices[p]
        span *= r
        if r == 1:
            ldig[p] = 0
        elif p == length - k and span >= lane_bound:
            ldig[p] = q                 # what is left is below r
        else:
            nq = _quotient(q, r)
            ldig[p] = q - nq * r
            q = nq

    byts: list = [None] * length
    c0 = None                  # carry out of the positions below
    for p in low:
        s = tdig[p] + ldig[p]
        if c0 is not None:
            s = s + c0.astype(jnp.int32)
        c0 = s >= radices[p]
        byts[p] = byte_of(p, jnp.where(c0, s - radices[p], s))
    # the upper positions' digits where c0 is set: the tile's, plus one
    carry = 1
    for p in upper:
        s = tdig[p] + carry
        wrap = s == radices[p]
        succ = jnp.where(wrap, 0, s)
        carry = wrap.astype(jnp.int32)
        if is_lut(p):           # a lane lookup: on the digit's tile
            byts[p] = byte_of(p, jnp.where(c0, succ, tdig[p]))
        else:
            byts[p] = jnp.where(c0, byte_of(p, succ), byte_of(p, tdig[p]))
    return [jnp.broadcast_to(b, lane.shape).astype(jnp.uint32)
            for b in byts]


def _pack_message(byts, length: int, shape, big_endian: bool,
                  widen_utf16: bool, block_words: int = 16):
    """Candidate bytes -> the padded single-block message words
    (16 words / 64-byte block by default; 32 words / 128-byte block
    with a 128-bit length field for the SHA-512 family)."""
    def put(m, q, byte):
        shift = 8 * (3 - q % 4) if big_endian else 8 * (q % 4)
        m[q // 4] = m[q // 4] | (byte << jnp.uint32(shift))

    m = [jnp.zeros(shape, jnp.uint32) for _ in range(block_words)]
    stride = 2 if widen_utf16 else 1        # UTF-16LE: byte p -> pos 2p
    for p, byte in enumerate(byts):
        put(m, stride * p, byte)
    msg_len = stride * length
    put(m, msg_len, jnp.uint32(0x80))
    bitlen = jnp.full(shape, jnp.uint32(8 * msg_len))
    if big_endian:
        m[block_words - 1] = bitlen   # 64/128-bit BE length, low word
    else:
        m[14] = bitlen       # 64-bit LE length, low word
    return m


def _build_kernel_body(engine_name: str, radices, seg_tables, length: int,
                       target, sub: int, probe=None, take=take_lanes):
    """The kernel math as a PURE function of (pid, base digits, n_valid
    [, offset]) -> (count, hit_lane) scalars.  Shared verbatim by the
    pallas_call wrapper (TPU) and by emulate_mask_kernel (eager CPU
    validation -- XLA:CPU cannot compile the statically-unrolled
    SHA-256 graph in reasonable time, so correctness tests drive this
    body op-by-op).  ``kernel_body.found_lanes`` is the same math up
    to the per-lane (found, lane) tiles, before the reduction to one
    lane a tile: make_tile_reprobe compacts those;
    ``kernel_body.hashed_lanes`` stops at the digest words, which is
    all a bulk list's kernel runs (target None).

    probe: the (block_bits, k, n_grp) geometry from kernel_probe_rows
    for a multi-target job -- the compare runs the blocked probe
    (`tables` holds the probe rows) and every survivor is a maybe the
    caller verifies on the host; None for a single target.

    take: the lane lookup of the probe and the charset LUT decode
    (take_lanes in a kernel; select_lanes where the body runs as
    plain XLA).  Same values either way.

    An `offset` scalar (the sharded/superstep window start) shifts
    both the decoded keyspace index and the validity bound, so ONE
    compiled kernel serves every window of a superstep; hit_lane stays
    tile-relative (the caller adds tile * pid + offset back)."""
    core, n_words, big_endian, widen = CORES[engine_name]
    tile = sub * 128
    # target None: the bulk-list kernel, whose body ends at the digest
    # (hashed_lanes); the probe runs behind it on a table no tile can
    # hold (make_mask_digest_fn)
    multi, tw = False, None
    if target is not None:
        target = np.asarray(target)
        multi = target.ndim == 2 and target.shape[0] > 1
        if multi and probe is None:
            raise ValueError("a multi-target kernel needs its probe "
                             "geometry (kernel_probe_rows)")
        if not multi:
            # plain python ints: jnp scalars here would be captured
            # closure constants, which pallas_call rejects
            tw = [int(w) for w in target.reshape(-1)]
            if len(tw) != n_words:
                raise ValueError(f"{engine_name}: expected {n_words} "
                                 "target words")

    def hashed_lanes(pid, base, luts=None, offset=None):
        """(digest words, lane, window-relative index) of one tile:
        the decode, pack and hash every variant of the body shares."""
        shape = (sub, 128)
        lane = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        # the tile's first index (pid * tile, plus the window offset)
        # stays a scalar: the decode adds it to the base digits once a
        # tile, on the scalar unit
        start = pid * tile
        if offset is not None:
            start = start + offset
        gidx = lane + start
        byts = decode_candidate_bytes(radices, seg_tables, length,
                                      base, start, lane, tile, luts,
                                      take)
        m = _pack_message(byts, length, shape, big_endian, widen,
                          32 if engine_name in WIDE_BLOCK else 16)
        return core(m, shape), lane, gidx

    def found_lanes(pid, base, n_valid, tables=None, luts=None,
                    offset=None):
        shape = (sub, 128)
        digest, lane, gidx = hashed_lanes(pid, base, luts, offset)
        valid = gidx < n_valid
        if not multi:
            found = valid
            for got, want in zip(digest, tw):
                found = found & (got == jnp.uint32(want))
        else:
            found = probe_block_found(digest, tables, valid, *probe,
                                      shape, take)
        return found, lane

    def kernel_body(pid, base, n_valid, tables=None, luts=None,
                    offset=None):
        found, lane = found_lanes(pid, base, n_valid, tables, luts,
                                  offset)
        count = jnp.sum(found.astype(jnp.int32))
        # single-hit extraction: max lane among hits (-1 if none); the
        # caller rescans any tile whose count exceeds 1.
        hit_lane = jnp.max(jnp.where(found, lane, -1))
        return count, hit_lane

    kernel_body.found_lanes = found_lanes
    kernel_body.hashed_lanes = hashed_lanes
    return kernel_body


def _build_kernel(engine_name: str, radices, seg_tables, length: int,
                  target, sub: int, multi: bool = False,
                  has_lut: bool = False, with_offset: bool = False,
                  probe=None):
    """pallas_call kernel wrapper around the pure body.  Optional
    positional inputs follow (base, n_valid) in a fixed order: the
    window offset scalar (sharded/superstep callers), then the probe
    rows (multi-target), then the charset LUT rows (masks
    with positions past the segment budget -- pallas_call forbids
    captured vector constants, so the LUT is a real input)."""
    body = _build_kernel_body(engine_name, radices, seg_tables, length,
                              target, sub, probe=probe)

    # Mosaic requires output blocks of (8k, 128m) lanes (or whole-array),
    # so the two per-tile scalars are packed into one int32 --
    # (count << 16) | (hit_lane + 1) -- splat across a full (8, 128)
    # block per grid cell (~1 byte/candidate of HBM traffic at sub=32;
    # noise next to the compression rounds).  count and hit_lane+1 both
    # fit 15/16 bits because tile = sub*128 <= 16384 (sub <= 128).
    def kernel(base_ref, nvalid_ref, *rest):
        out_ref = rest[-1]
        extras = list(rest[:-1])
        offset_ref = extras.pop(0) if with_offset else None
        tables_ref = extras.pop(0) if multi else None
        luts_ref = extras.pop(0) if has_lut else None
        count, hit_lane = body(
            pl.program_id(0), base_ref, nvalid_ref[0], tables_ref,
            luts_ref,
            offset_ref[0] if offset_ref is not None else None)
        packed = (count << 16) | (hit_lane + 1)
        out_ref[...] = jnp.full((8, 128), packed, jnp.int32)

    return kernel


def emulate_mask_kernel(engine_name: str, gen, target_words: np.ndarray,
                        batch: int, base_digits, n_valid: int,
                        sub: int = SUB, offset: int = 0,
                        probe_fp: Optional[float] = None):
    """Run the kernel body eagerly (no pallas_call, no jit) over every
    grid cell; returns (counts int32[G,1], hit_lanes int32[G,1]) with
    the exact layout pallas_call produces.  Test/validation vehicle.

    offset / probe_fp mirror make_mask_pallas_fn's, so the sharded
    kernel bodies validate through the same eager loop off-TPU."""
    tile = sub * 128
    if batch % tile:
        raise ValueError(f"batch {batch} not a multiple of tile {tile}")
    target_words = np.asarray(target_words)
    multi = target_words.ndim == 2 and target_words.shape[0] > 1
    tables = probe = None
    if multi:
        rows, block_bits, k, n_grp, _ = kernel_probe_rows(
            target_words, probe_fp)
        tables = jnp.asarray(rows)
        probe = (block_bits, k, n_grp)
    seg_tables, luts_np = position_tables(gen.charsets)
    luts = jnp.asarray(luts_np) if luts_np is not None else None
    body = _build_kernel_body(engine_name, gen.radices, seg_tables,
                              gen.length, target_words, sub,
                              probe=probe)
    base = jnp.asarray(base_digits, jnp.int32)
    off = jnp.int32(offset) if offset else None
    counts, lanes = [], []
    for pid in range(batch // tile):
        c, l = body(jnp.int32(pid), base, jnp.int32(n_valid), tables,
                    luts, off)
        counts.append(int(c))
        lanes.append(int(l))
    return (np.asarray(counts, np.int32)[:, None],
            np.asarray(lanes, np.int32)[:, None])


def make_mask_pallas_fn(engine_name: str, gen, target_words: np.ndarray,
                        batch: int, sub: int = SUB,
                        interpret: bool = False,
                        with_offset: bool = False,
                        probe_fp: Optional[float] = None):
    """Build fn(base_digits int32[L], n_valid int32[1][, offset
    int32[1]]) -> (counts int32[G, 1], hit_lanes int32[G, 1]) over a
    `batch`-lane sweep.  batch must be a multiple of sub*128.

    target_words uint32[W] (single target: counts are exact hit counts)
    or uint32[N, W] (multi target: the compare is the blocked-probe
    bitmap, kernel_probe_rows, sized for probe_fp -- default
    DPRF_PALLAS_PROBE_FP -- and counts are maybe-counts; see
    reduce_tile_maybes for the caller contract).

    with_offset adds the traced window-start scalar (SMEM, like
    n_valid): candidates decode from base + offset + lane and validity
    checks against the WINDOW n_valid, so sharded shards and superstep
    iterations reuse one compiled kernel."""
    tile = sub * 128
    grid = check_batch(batch, sub)
    target_words = np.asarray(target_words)
    multi = target_words.ndim == 2 and target_words.shape[0] > 1
    n_targets = target_words.shape[0] if multi else 1
    if not kernel_eligible(engine_name, gen, n_targets):
        raise ValueError(f"{engine_name} mask job not kernel-eligible; "
                         "use the XLA path")
    seg_tables, luts_np = position_tables(gen.charsets)
    has_lut = luts_np is not None
    probe = None
    if multi:
        tables, block_bits, k, n_grp, _ = kernel_probe_rows(
            target_words, probe_fp)
        probe = (block_bits, k, n_grp)
    kernel = _build_kernel(engine_name, gen.radices, seg_tables,
                           gen.length, target_words, sub, multi=multi,
                           has_lut=has_lut, with_offset=with_offset,
                           probe=probe)
    L = gen.length
    in_specs = [
        pl.BlockSpec((L,), lambda i: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
    ]
    if with_offset:
        in_specs.append(
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM))
    if multi:
        R = tables.shape[0]
        in_specs.append(pl.BlockSpec((R, 128), lambda i: (0, 0)))
    if has_lut:
        in_specs.append(pl.BlockSpec(luts_np.shape, lambda i: (0, 0)))
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((8, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid * 8, 128), jnp.int32),
        ],
        interpret=interpret,
    )
    tables_dev = jnp.asarray(tables) if multi else None
    luts_dev = jnp.asarray(luts_np) if has_lut else None

    def fn(base_digits, n_valid, offset=None):
        args = [base_digits, n_valid]
        if with_offset:
            args.append(jnp.zeros((1,), jnp.int32)
                        if offset is None else offset)
        if multi:
            args.append(tables_dev)
        if has_lut:
            args.append(luts_dev)
        (packed,) = raw(*args)
        p = packed[::8, 0:1]          # row 0 of each tile's block
        return p >> 16, (p & 0xFFFF) - 1

    return fn


#: the bulk list's kernel, by name (make_mask_digest_fn)
DIGEST_KERNEL_NAME = "mask_digest_kernel"


def make_mask_digest_fn(engine_name: str, gen, batch: int,
                        sub: int = SUB, interpret: bool = False):
    """The kernel of a bulk target list: fn(base_digits int32[L],
    offset int32[1]) -> digest words uint32[W, batch / 128, 128],
    word-major and as the kernel's tiles wrote them (lane i, in
    row-major order, of plane w is word w of candidate base + offset +
    i).

    The body is the mask kernels' own (_build_kernel_body: decode,
    pack, hash core) and ends at the digest: a list past MAX_TARGETS
    has a probe bitmap of megabytes, which no tile can look up with
    128-lane gathers, so the probe is a stage of XLA operations behind
    the kernel (targets/probe.probe_hits_words) and the digest words
    are what crosses HBM between them: 4 * W bytes a candidate, which
    at the kernel's rate is a tenth of the chip's bandwidth."""
    grid = check_batch(batch, sub)
    if engine_name not in CORES or not kernel_eligible(engine_name, gen, 1):
        raise ValueError(f"{engine_name} mask job not kernel-eligible; "
                         "use the XLA path")
    n_words = CORES[engine_name][1]
    seg_tables, luts_np = position_tables(gen.charsets)
    body = _build_kernel_body(engine_name, gen.radices, seg_tables,
                              gen.length, None, sub)

    def kernel(base_ref, offset_ref, *rest):
        out_ref = rest[-1]
        luts_ref = rest[0] if luts_np is not None else None
        digest, _, _ = body.hashed_lanes(pl.program_id(0), base_ref,
                                         luts_ref, offset_ref[0])
        for w in range(n_words):
            out_ref[w] = digest[w]

    in_specs = [
        pl.BlockSpec((gen.length,), lambda i: (0,),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
    ]
    if luts_np is not None:
        in_specs.append(pl.BlockSpec(luts_np.shape, lambda i: (0, 0)))
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((n_words, sub, 128),
                                lambda i: (0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_words, grid * sub, 128),
                                        jnp.uint32)],
        interpret=interpret,
        # the compiled program's instruction, and its event in a device
        # trace, carry this name (beside the gathers' own custom calls
        # of the stage behind it)
        name=DIGEST_KERNEL_NAME,
    )
    luts_dev = jnp.asarray(luts_np) if luts_np is not None else None

    def fn(base_digits, offset):
        args = [base_digits, offset]
        if luts_dev is not None:
            args.append(luts_dev)
        (words,) = raw(*args)
        return words

    return fn


def make_pallas_bulk_crack_step(engine_name: str, gen, geometry,
                                batch: int, hit_capacity: int = 64,
                                survivors: int = 256,
                                interpret: bool = False,
                                with_offset: bool = False,
                                sub: Optional[int] = None):
    """Bulk-list kernel step: step(base_digits, n_valid[, offset],
    *table) -> (count, lanes int32[hit_capacity], tpos
    int32[hit_capacity], n_maybe): the kernel hashes (make_mask_digest_fn),
    the probe stage behind it (targets/probe.probe_hits_words, under
    jax.named_scope("dprf_probe")) looks every digest up in the list's
    bitmap and verifies the survivors exactly against its sorted
    table, both arguments of the program (ProbeTable.device_args):
    one executable serves every list of one `geometry`
    (ProbeTable.geometry).  count is of true hits, tpos their position
    in the sorted table; a survivor overflow inflates count past the
    buffer and the worker redrives; n_maybe is what passed the
    bitmap."""
    from dprf_tpu.targets import probe as probe_mod
    sub = SUB if sub is None else sub
    fn = make_mask_digest_fn(engine_name, gen, batch, sub=sub,
                             interpret=interpret)
    lane = jnp.arange(batch, dtype=jnp.int32).reshape(-1, 128)

    def run(base_digits, n_valid, offset, table):
        words = fn(base_digits.astype(jnp.int32),
                   jnp.reshape(offset, (1,)).astype(jnp.int32))
        with jax.named_scope("dprf_probe"):
            return probe_mod.probe_hits_words(
                words, table, geometry, offset + lane < n_valid,
                hit_capacity, survivors)

    if with_offset:
        @jax.jit
        def step(base_digits, n_valid, offset, *table):
            return run(base_digits, n_valid, offset, table)
        return step

    @jax.jit
    def step(base_digits, n_valid, *table):
        return run(base_digits, n_valid, jnp.int32(0), table)

    return step


def make_pallas_mask_crack_step(engine_name: str, gen,
                                target_words: np.ndarray, batch: int,
                                hit_capacity: int = 64,
                                interpret: bool = False,
                                with_offset: bool = False,
                                sub: Optional[int] = None):
    """Drop-in replacement for ops/pipeline.make_mask_crack_step on the
    single-target kernel path: step(base_digits, n_valid) ->
    (count, lanes, tpos).

    with_offset appends a traced window-start argument --
    step(base_digits, n_valid, offset) -- with lanes still
    batch-relative, so ops/superstep.make_loop_super_step can fuse
    `inner` invocations of ONE compiled kernel per dispatch.  `sub`
    overrides the tile sublane count (the `dprf tune` tile rung)."""
    if engine_name not in CORES:
        from dprf_tpu.ops import pallas_ext
        return pallas_ext.make_ext_mask_crack_step(
            engine_name, gen, target_words, batch, hit_capacity,
            interpret=interpret)
    sub = SUB if sub is None else sub
    tile = sub * 128
    fn = make_mask_pallas_fn(engine_name, gen, target_words, batch,
                             sub=sub, interpret=interpret,
                             with_offset=with_offset)

    if with_offset:
        @jax.jit
        def step(base_digits: jnp.ndarray, n_valid: jnp.ndarray,
                 offset: jnp.ndarray):
            counts, hit_lanes = fn(
                base_digits.astype(jnp.int32),
                jnp.reshape(n_valid, (1,)).astype(jnp.int32),
                jnp.reshape(offset, (1,)).astype(jnp.int32))
            return reduce_tile_hits(counts, hit_lanes, hit_capacity,
                                    tile)
        return step

    @jax.jit
    def step(base_digits: jnp.ndarray, n_valid: jnp.ndarray):
        counts, hit_lanes = fn(base_digits.astype(jnp.int32),
                               jnp.reshape(n_valid, (1,)).astype(jnp.int32))
        return reduce_tile_hits(counts, hit_lanes, hit_capacity, tile)

    return step


def make_pallas_multi_crack_step(engine_name: str, gen,
                                 target_words: np.ndarray, batch: int,
                                 hit_capacity: int = 64,
                                 rescan_capacity: int = 16,
                                 interpret: bool = False,
                                 with_offset: bool = False,
                                 sub: Optional[int] = None):
    """Multi-target kernel step: step(base_digits, n_valid) ->
    (n_single, maybe_lanes int32[hit_capacity],
     n_collided, collided_tiles int32[rescan_capacity]).

    Contract (see PallasMaskWorker): each maybe lane holds >= 0
    candidates that passed the in-kernel prefilter and must be
    verified by ONE host oracle hash; each collided tile (>= 2 maybes)
    must be resolved to its maybe lanes, each verified the same way:
    on the device by make_tile_reprobe, which runs this kernel's body
    over the tile (the worker rescans the tile's TILE candidates on
    the host oracle only where that re-probe disagrees or overflows).
    n_single > hit_capacity or n_collided > rescan_capacity means the
    whole batch needs the exact rescan.

    The prefilter is the blocked-probe bitmap at DPRF_PALLAS_PROBE_FP
    (kernel_probe_rows), the compare the sharded kernel step uses:
    with 1,000 uniform targets it passes about 1e-6 of all lanes
    (counted on the CPU: 16 of 16,777,216), which at the production
    tile (16,384 lanes) leaves collided tiles and window-buffer
    overflows rare enough for a 2^33-candidate window to finish.

    with_offset / sub: as make_pallas_mask_crack_step (loop-superstep
    fusion and the tune tile rung)."""
    if engine_name not in CORES:
        from dprf_tpu.ops import pallas_ext
        return pallas_ext.make_ext_multi_crack_step(
            engine_name, gen, target_words, batch, hit_capacity,
            rescan_capacity, interpret=interpret)
    sub = SUB if sub is None else sub
    tile = sub * 128
    fn = make_mask_pallas_fn(engine_name, gen, target_words, batch,
                             sub=sub, interpret=interpret,
                             with_offset=with_offset)

    if with_offset:
        @jax.jit
        def step(base_digits: jnp.ndarray, n_valid: jnp.ndarray,
                 offset: jnp.ndarray):
            counts, hit_lanes = fn(
                base_digits.astype(jnp.int32),
                jnp.reshape(n_valid, (1,)).astype(jnp.int32),
                jnp.reshape(offset, (1,)).astype(jnp.int32))
            return reduce_tile_maybes(counts, hit_lanes, hit_capacity,
                                      rescan_capacity, tile)
        return step

    @jax.jit
    def step(base_digits: jnp.ndarray, n_valid: jnp.ndarray):
        counts, hit_lanes = fn(base_digits.astype(jnp.int32),
                               jnp.reshape(n_valid, (1,)).astype(jnp.int32))
        return reduce_tile_maybes(counts, hit_lanes, hit_capacity,
                                  rescan_capacity, tile)

    return step


def make_tile_reprobe(engine_name: str, gen, target_words: np.ndarray,
                      sub: Optional[int] = None, capacity: int = 16,
                      probe_fp: Optional[float] = None):
    """The collided-tile re-probe: reprobe(base_digits int32[L],
    n_valid) -> (count int32, lanes int32[capacity]) over ONE tile of
    sub * 128 lanes that starts at base_digits, lanes tile-relative,
    unused slots -1.

    The kernel reports one lane a tile, so a tile in which two or more
    lanes passed the probe bitmap comes back as its index alone.  This
    runs the kernel's own body (_build_kernel_body: decode, pack, hash
    core, probe_block_found over the same kernel_probe_rows) over that
    tile and compacts every surviving lane, so count is the count the
    kernel saw and the caller verifies count lanes on the oracle, not
    the tile's width.  count > capacity: the buffer is truncated.

    A plain jax.jit, not a pallas_call, and with no gather in it
    (select_lanes): 16,384 lanes a collided tile, a few tiles a unit,
    is no work, and the step's kernel stays the programs' one custom
    call.  The probe rows (and charset LUT rows) are arguments of the
    jitted program, not constants of it: one executable serves every
    target list of the same probe geometry.  ``reprobe.lower`` lowers
    it for an ahead-of-time compile."""
    from dprf_tpu.ops import compare as cmp_ops

    if engine_name not in CORES:
        raise ValueError(f"{engine_name}: the tile re-probe covers the "
                         "CORES engines only")
    sub = SUB if sub is None else sub
    target_words = np.asarray(target_words)
    rows, block_bits, k, n_grp, _ = kernel_probe_rows(target_words,
                                                      probe_fp)
    seg_tables, luts_np = position_tables(gen.charsets)
    body = _build_kernel_body(engine_name, gen.radices, seg_tables,
                              gen.length, target_words, sub,
                              probe=(block_bits, k, n_grp),
                              take=select_lanes)

    @jax.jit
    def probe(base_digits, n_valid, tables, luts):
        found, _ = body.found_lanes(0, base_digits.astype(jnp.int32),
                                    n_valid.astype(jnp.int32), tables,
                                    luts)
        found = found.reshape(-1)         # row-major: the lane index
        count, lanes, _ = cmp_ops.compact_hits(
            found, jnp.zeros(found.shape, jnp.int32), capacity)
        return count, lanes

    tables_dev = jnp.asarray(rows)
    luts_dev = jnp.asarray(luts_np) if luts_np is not None else None

    def reprobe(base_digits, n_valid):
        return probe(base_digits, n_valid, tables_dev, luts_dev)

    reprobe.lower = lambda base_digits, n_valid: probe.lower(
        base_digits, n_valid, tables_dev, luts_dev)
    return reprobe


def reduce_tile_maybes(counts: jnp.ndarray, hit_lanes: jnp.ndarray,
                       hit_capacity: int, rescan_capacity: int, tile: int):
    """Per-tile probe maybe-counts -> (n_single, maybe_lanes,
    n_collided, collided_tiles) for the multi-target worker."""
    from dprf_tpu.ops import compare as cmp_ops

    c = counts[:, 0]
    single = c == 1
    collided = c > 1
    n_single = jnp.sum(single.astype(jnp.int32))
    n_collided = jnp.sum(collided.astype(jnp.int32))
    _, stiles, _ = cmp_ops.compact_hits(single, jnp.zeros_like(c),
                                        hit_capacity)
    maybe_lanes = jnp.where(
        stiles >= 0,
        stiles * tile + hit_lanes[jnp.maximum(stiles, 0), 0], -1)
    _, ctiles, _ = cmp_ops.compact_hits(collided, jnp.zeros_like(c),
                                        rescan_capacity)
    return n_single, maybe_lanes, n_collided, ctiles


def make_shard_mask_compute(engine_name: str, gen,
                            target_words: np.ndarray,
                            batch_per_device: int, hit_capacity: int,
                            sub: Optional[int] = None,
                            interpret: bool = False,
                            probe_fp: Optional[float] = None):
    """The fused kernel as a sharded compute callback: the tentpole
    bridge between this module and parallel/sharded.make_sharded_step.

    compute(offset, base_digits, n_valid) ->
        (found bool[G], payload int32[G], rel int32[G], count int32)

    -- the runtime's TILE-compute contract: per-grid-cell hit flags,
    window-relative hit lanes (offset + tile start + in-tile lane),
    and the authoritative count.  Candidate generation happens ON
    DEVICE inside the kernel from base + shard/window offset, so a
    sharded superstep's only host traffic is the base digit vector.

    Single target: found marks exactly-one-hit tiles; payload is tpos
    0; a tile holding 2+ hits can only report one lane, so the count
    is inflated past hit_capacity, the width one stride folds through:
    the runtime carries such a stride's count past the WINDOW's width
    (parallel/sharded._append_hits), whatever the window's buffer
    holds, and the workers' existing overflow redrive re-covers the
    window exactly.  Multi target
    (2..MAX_TARGETS): the compare is the blocked PR 14 probe bitmap
    (kernel_probe_rows) and every surviving lane comes back
    SENTINEL-tagged (payload == n_targets, out of range) -- the
    workers' lane decode verifies each with one oracle hash.  A tile
    holding 2+ maybes comes back tagged n_targets + 1 with the TILE'S
    first lane, and the worker re-probes that one tile on the device
    (make_tile_reprobe: its maybe lanes, one oracle hash each; the
    host rescans the tile only where the re-probe disagrees or
    overflows): at the probe's false-positive rate a collided tile
    turns up every few hundred batches, and redriving down to a
    stride-wide host rescan (n_dev x batch candidates on the oracle)
    for each would never end."""
    if engine_name not in CORES:
        raise ValueError(f"{engine_name}: sharded kernel computes "
                         "cover the CORES engines only")
    sub = SUB if sub is None else sub
    tile = sub * 128
    grid = check_batch(batch_per_device, sub)
    target_words = np.asarray(target_words)
    multi = target_words.ndim == 2 and target_words.shape[0] > 1
    sentinel = int(target_words.shape[0]) if multi else 0
    fn = make_mask_pallas_fn(
        engine_name, gen, target_words, batch_per_device, sub=sub,
        interpret=interpret, with_offset=True, probe_fp=probe_fp)
    tile_starts = jnp.arange(grid, dtype=jnp.int32) * tile

    def compute(offset, base_digits, n_valid):
        counts, hit_lanes = fn(
            base_digits.astype(jnp.int32),
            jnp.reshape(n_valid, (1,)).astype(jnp.int32),
            jnp.reshape(offset, (1,)).astype(jnp.int32))
        c = counts[:, 0]
        if multi:
            found = c >= 1
            collided = c > 1
            rel = offset + tile_starts + jnp.where(collided, 0,
                                                   hit_lanes[:, 0])
            payload = jnp.where(collided, sentinel + 1,
                                sentinel).astype(jnp.int32)
            return found, payload, rel, jnp.sum(found.astype(jnp.int32))
        found = c == 1
        rel = offset + tile_starts + hit_lanes[:, 0]
        payload = jnp.full((grid,), sentinel, jnp.int32)
        count = jnp.sum(c) + jnp.where(
            jnp.any(c > 1), jnp.int32(hit_capacity + 1), 0)
        return found, payload, rel, count

    compute.tile = tile
    compute.grid = grid
    return compute


def reduce_tile_hits(counts: jnp.ndarray, hit_lanes: jnp.ndarray,
                     hit_capacity: int, tile: int):
    """Per-tile kernel outputs -> the worker's (count, lanes, tpos)
    contract.  A tile holding 2+ hits can only report one lane, so any
    such tile forces count > hit_capacity: the worker's exact host
    rescan then recovers every hit."""
    from dprf_tpu.ops import compare as cmp_ops

    c = counts[:, 0]
    total = jnp.sum(c)
    collision = jnp.any(c > 1)
    _, tiles, _ = cmp_ops.compact_hits(c > 0, jnp.zeros_like(c),
                                       hit_capacity)
    glanes = jnp.where(
        tiles >= 0,
        tiles * tile + hit_lanes[jnp.maximum(tiles, 0), 0], -1)
    count = jnp.where(collision, jnp.int32(hit_capacity + 1), total)
    return count, glanes, jnp.zeros_like(glanes)
