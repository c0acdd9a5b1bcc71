"""Extended fused mask kernels: salted, nested, and mysql41 variants.

The hand-written kernels of pallas_mask cover only the unsalted
single-block engines; every other fast engine would otherwise stay on
the XLA pipeline, whose per-byte charset gather runs orders of
magnitude slower than the kernel decode.  The families this module covers all consume one or two 64-byte blocks
of the exact same compression cores, so they reuse pallas_mask's
decode machinery with a different message build / digest chain:

Markov/scrambled charsets decode here through the same lane-axis LUT
input as pallas_mask (position_tables): these families are in the
plain-mask speed class, where the unbounded segment mux's ~190 extra
VPU ops per worst-case Markov ?a position would have cost up to 2x.

- **salted** ``$pass.$salt`` / ``$salt.$pass`` md5/sha1/sha256
  (hashcat 10/20, 110/120, 1410/1420, plus postgres and LDAP {SSHA}
  which ride the same classes): the salt BYTES and the target digest
  are runtime SMEM scalars -- one compiled kernel per (mask,
  salt-length) serves every target, mirroring the XLA salted step's
  one-compile-for-the-hashlist design.  The salt length must be
  static (it fixes each message byte's position), and distinct salt
  lengths in a hashlist are a handful at most.
- **nested** ``outer(hex(inner(password)))`` (hashcat 2600/4500/4400/
  4700/20800/20700): the inner digest is hex-encoded in registers
  (nibble->char arithmetic, no gather) and fed to the outer
  compression.  Single- and multi-target (probe) compare both work,
  so these slot into the existing PallasMaskWorker unchanged.
- **mysql41** sha1(sha1($p)) over the RAW inner digest (hashcat 300):
  the inner digest words ARE the outer block words.

The kernel bodies follow pallas_mask's contract exactly -- pure
(pid, base digits, n_valid, [runtime scalars]) -> (count, hit_lane)
-- and reuse its packed (8, 128) output trick, tile reducers, probe
prefilter, and eligibility plumbing (pallas_mask.kernel_eligible and
the step factories dispatch here for non-CORES engine names).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dprf_tpu.ops.pallas_mask import (CORES, MAX_TARGETS, SUB,
                                      _pack_message, check_batch,
                                      decode_candidate_bytes,
                                      kernel_probe_rows,
                                      position_tables, probe_block_found,
                                      mask_supported, reduce_tile_hits,
                                      reduce_tile_maybes)

#: nested combos this kernel supports: outer(hex(inner)).  The inner
#: hex (32 or 40 bytes) must fit one outer block; sha256 inner (64
#: hex bytes) would need two-block chaining, same rule as the XLA
#: nested engines.
NESTED_COMBOS = {
    "md5(md5)": ("md5", "md5"),
    "sha1(sha1)": ("sha1", "sha1"),
    "md5(sha1)": ("md5", "sha1"),
    "sha1(md5)": ("sha1", "md5"),
    "sha256(md5)": ("sha256", "md5"),
    "sha256(sha1)": ("sha256", "sha1"),
}

#: salted base algorithms with kernel cores (sha512 is 64-bit-word,
#: no core; mssql's UTF-16LE pre-salt widening is not built yet).
SALTED_ALGOS = ("md5", "sha1", "sha256")

#: single-block message byte budget (64 - 1 pad - 8 length).
BLOCK_LIMIT = 55


def _uses_sha256(name: str) -> bool:
    return "sha256" in name


def _tpu_ok_for(name: str) -> bool:
    """sha256 stages compile through Mosaic fine but take XLA:CPU many
    minutes (statically unrolled rounds) -- TPU-only, like the plain
    sha256 kernel."""
    if not _uses_sha256(name):
        return True
    return jax.default_backend() == "tpu"


def nested_eligible(engine_name: str, gen, n_targets: int) -> bool:
    """Eligibility for the nested/mysql41 kernel path (the dispatch
    target of pallas_mask.kernel_eligible for non-CORES names)."""
    if engine_name != "mysql41" and engine_name not in NESTED_COMBOS:
        return False
    if not 1 <= n_targets <= MAX_TARGETS:
        return False
    if not hasattr(gen, "charsets"):
        return False
    if not _tpu_ok_for(engine_name):
        return False
    return gen.length <= BLOCK_LIMIT and mask_supported(gen.charsets)


def salted_eligible(engine_algo: str, order: str, gen,
                    salt_lens: Sequence[int]) -> bool:
    """Eligibility for the salted kernel path.  `salt_lens` are the
    job's ACTUAL salt lengths (each compiles its own kernel)."""
    if engine_algo not in SALTED_ALGOS or order not in ("ps", "sp"):
        return False
    if not hasattr(gen, "charsets"):
        return False
    if not _tpu_ok_for(engine_algo):
        return False
    if not salt_lens or len(set(salt_lens)) > 8:
        # a hashlist with many distinct salt lengths would compile a
        # kernel per length; past a handful the XLA step (one compile
        # total) is the better trade
        return False
    return (gen.length + max(salt_lens) <= BLOCK_LIMIT
            and mask_supported(gen.charsets))


def _hex_byts(digest, little_endian: bool):
    """Digest word arrays -> list of 8W lowercase-hex byte arrays in
    the digest's canonical byte order (registers only, no gather)."""
    shifts = (0, 8, 16, 24) if little_endian else (24, 16, 8, 0)
    out = []
    for w in digest:
        for s in shifts:
            b = (w >> jnp.uint32(s)) & jnp.uint32(0xFF)
            for nib in (b >> jnp.uint32(4), b & jnp.uint32(0xF)):
                out.append(nib + jnp.where(nib < 10, jnp.uint32(ord("0")),
                                           jnp.uint32(ord("a") - 10)))
    return out


def _digest_chain(name: str, m, shape):
    """Message words -> final digest tuple for any supported variant
    name ('md5', 'sha1(md5)', 'mysql41', ...)."""
    if name == "mysql41":
        inner = CORES["sha1"][0](m, shape)
        m2 = [jnp.zeros(shape, jnp.uint32) for _ in range(16)]
        for i, w in enumerate(inner):
            m2[i] = w
        m2[5] = jnp.full(shape, jnp.uint32(0x80000000))
        m2[15] = jnp.full(shape, jnp.uint32(160))      # 20 bytes
        return CORES["sha1"][0](m2, shape)
    if name in NESTED_COMBOS:
        outer, inner = NESTED_COMBOS[name]
        icore, iw, ibig, _ = CORES[inner]
        ocore, _, obig, _ = CORES[outer]
        d = icore(m, shape)
        hexb = _hex_byts(d, little_endian=not ibig)
        m2 = _pack_message(hexb, len(hexb), shape, obig, False)
        return ocore(m2, shape)
    return CORES[name][0](m, shape)


def variant_words(name: str) -> tuple[int, bool]:
    """(digest words, big_endian) of a variant's FINAL digest."""
    if name == "mysql41":
        return 5, True
    if name in NESTED_COMBOS:
        outer = NESTED_COMBOS[name][0]
        return CORES[outer][1], CORES[outer][2]
    return CORES[name][1], CORES[name][2]


def _inner_big_endian(name: str) -> bool:
    """Byte order of the FIRST block (what the candidate packs into)."""
    if name == "mysql41":
        return True
    if name in NESTED_COMBOS:
        return CORES[NESTED_COMBOS[name][1]][2]
    return CORES[name][2]


def _build_ext_body(name: str, radices, seg_tables, length: int,
                    target, sub: int, order: Optional[str] = None,
                    salt_len: int = 0, has_lut: bool = False,
                    probe=None):
    """Kernel math as a pure function.  Two shapes:

    - nested/mysql41 (order None): (pid, base, n_valid[, tables])
      -> (count, hit_lane); target is trace-time (uint32[W] single, or
      uint32[N, W] multi with `probe` the kernel_probe_rows geometry
      and `tables` its rows), exactly like pallas_mask.
    - salted (order 'ps'/'sp'): (pid, base, n_valid, salt, tgt)
      -> (count, hit_lane); salt bytes (int32[>=salt_len]) and target
      words (uint32[W]) are RUNTIME scalar refs, salt_len is static.
    """
    n_words, _ = variant_words(name)
    big_endian = _inner_big_endian(name)
    tile = sub * 128
    salted = order is not None
    if salted:
        if length + salt_len > BLOCK_LIMIT:
            raise ValueError("candidate+salt exceeds one block")
        multi = False
        tw = None
    else:
        target = np.asarray(target)
        multi = target.ndim == 2 and target.shape[0] > 1
        if multi:
            tw = None
        else:
            tw = [int(w) for w in target.reshape(-1)]
            if len(tw) != n_words:
                raise ValueError(f"{name}: expected {n_words} "
                                 "target words")

    def body(pid, base, n_valid, *rest):
        # rest order: [tables (multi) | salt, tgt (salted)] then, when
        # the mask has LUT positions, the charset LUT rows LAST
        rest = list(rest)
        luts = rest.pop() if has_lut else None
        shape = (sub, 128)
        lane = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        cand = decode_candidate_bytes(radices, seg_tables, length,
                                      base, pid * tile, lane, tile, luts)
        if salted:
            salt_ref, tgt_ref = rest
            salt_b = [salt_ref[j].astype(jnp.uint32)
                      for j in range(salt_len)]
            byts = cand + salt_b if order == "ps" else salt_b + cand
        else:
            byts = cand
        m = _pack_message(byts, len(byts), shape, big_endian, False)
        digest = _digest_chain(name, m, shape)
        valid = (lane + pid * tile) < n_valid
        if salted:
            found = valid
            for i, got in enumerate(digest):
                # int32 -> uint32 astype is modular, preserving the
                # bit pattern (scalar bitcast doesn't lower on Mosaic)
                want = tgt_ref[i].astype(jnp.uint32)
                found = found & (got == want)
        elif not multi:
            found = valid
            for got, want in zip(digest, tw):
                found = found & (got == jnp.uint32(want))
        else:
            found = probe_block_found(digest, rest[0], valid, *probe,
                                      shape)
        count = jnp.sum(found.astype(jnp.int32))
        hit_lane = jnp.max(jnp.where(found, lane, -1))
        return count, hit_lane

    return body


# shared packed-output factory guard (pallas_mask.check_batch)
_check_batch = check_batch


def make_ext_pallas_fn(name: str, gen, target_words, batch: int,
                       sub: int = SUB, interpret: bool = False):
    """Nested/mysql41 variant of pallas_mask.make_mask_pallas_fn:
    fn(base_digits, n_valid) -> (counts[G,1], hit_lanes[G,1])."""
    tile = sub * 128
    grid = _check_batch(batch, sub)
    target_words = np.asarray(target_words)
    multi = target_words.ndim == 2 and target_words.shape[0] > 1
    if not nested_eligible(name, gen,
                           target_words.shape[0] if multi else 1):
        raise ValueError(f"{name} mask job not ext-kernel-eligible")
    seg_tables, luts_np = position_tables(gen.charsets)
    has_lut = luts_np is not None
    probe = None
    if multi:
        tables, block_bits, k, n_grp, _ = kernel_probe_rows(target_words)
        probe = (block_bits, k, n_grp)
    body = _build_ext_body(name, gen.radices, seg_tables, gen.length,
                           target_words, sub, has_lut=has_lut,
                           probe=probe)

    def kernel(base_ref, nvalid_ref, *rest):
        out_ref = rest[-1]
        count, hit_lane = body(pl.program_id(0), base_ref,
                               nvalid_ref[0], *rest[:-1])
        out_ref[...] = jnp.full((8, 128), (count << 16) | (hit_lane + 1),
                                jnp.int32)

    L = gen.length
    in_specs = [
        pl.BlockSpec((L,), lambda i: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
    ]
    if multi:
        in_specs.append(pl.BlockSpec((tables.shape[0], 128),
                                     lambda i: (0, 0)))
    if has_lut:
        in_specs.append(pl.BlockSpec(luts_np.shape, lambda i: (0, 0)))
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * 8, 128), jnp.int32)],
        interpret=interpret,
    )
    tables_dev = jnp.asarray(tables) if multi else None
    luts_dev = jnp.asarray(luts_np) if has_lut else None

    def fn(base_digits, n_valid):
        args = [base_digits, n_valid]
        if multi:
            args.append(tables_dev)
        if has_lut:
            args.append(luts_dev)
        (packed,) = raw(*args)
        p = packed[::8, 0:1]
        return p >> 16, (p & 0xFFFF) - 1

    return fn


def make_salted_pallas_fn(algo: str, order: str, gen, batch: int,
                          salt_len: int, sub: int = SUB,
                          interpret: bool = False):
    """Salted kernel: fn(base_digits, n_valid int32[1],
    salt int32[salt_len..], target int32[W]) -> (counts, hit_lanes).
    Salt bytes and target words are runtime; one compiled fn per
    (mask, salt_len) serves every same-length target."""
    tile = sub * 128
    grid = _check_batch(batch, sub)
    if not salted_eligible(algo, order, gen, [salt_len]):
        raise ValueError(f"{algo}-{order} mask job not kernel-eligible")
    n_words, _ = variant_words(algo)
    seg_tables, luts_np = position_tables(gen.charsets)
    has_lut = luts_np is not None
    body = _build_ext_body(algo, gen.radices, seg_tables, gen.length,
                           None, sub, order=order, salt_len=salt_len,
                           has_lut=has_lut)
    SW = max(salt_len, 1)

    def kernel(base_ref, nvalid_ref, *rest):
        out_ref = rest[-1]
        count, hit_lane = body(pl.program_id(0), base_ref,
                               nvalid_ref[0], *rest[:-1])
        out_ref[...] = jnp.full((8, 128), (count << 16) | (hit_lane + 1),
                                jnp.int32)

    L = gen.length
    in_specs = [
        pl.BlockSpec((L,), lambda i: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((SW,), lambda i: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((n_words,), lambda i: (0,),
                     memory_space=pltpu.SMEM),
    ]
    if has_lut:
        in_specs.append(pl.BlockSpec(luts_np.shape, lambda i: (0, 0)))
    raw = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * 8, 128), jnp.int32)],
        interpret=interpret,
    )
    luts_dev = jnp.asarray(luts_np) if has_lut else None

    def fn(base_digits, n_valid, salt, target):
        args = [base_digits, n_valid, salt[:SW], target]
        if has_lut:
            args.append(luts_dev)
        (packed,) = raw(*args)
        p = packed[::8, 0:1]
        return p >> 16, (p & 0xFFFF) - 1

    return fn


def make_ext_mask_crack_step(name: str, gen, target_words, batch: int,
                             hit_capacity: int = 64,
                             interpret: bool = False):
    """Single-target nested/mysql41 crack step with the standard
    (count, lanes, tpos) contract."""
    tile = SUB * 128
    fn = make_ext_pallas_fn(name, gen, target_words, batch,
                            interpret=interpret)

    @jax.jit
    def step(base_digits, n_valid):
        counts, hit_lanes = fn(base_digits.astype(jnp.int32),
                               jnp.reshape(n_valid, (1,)).astype(jnp.int32))
        return reduce_tile_hits(counts, hit_lanes, hit_capacity, tile)

    return step


def make_ext_multi_crack_step(name: str, gen, target_words, batch: int,
                              hit_capacity: int = 64,
                              rescan_capacity: int = 16,
                              interpret: bool = False):
    """Multi-target nested/mysql41 crack step; contract and prefilter
    of pallas_mask.make_pallas_multi_crack_step."""
    tile = SUB * 128
    fn = make_ext_pallas_fn(name, gen, target_words, batch,
                            interpret=interpret)

    @jax.jit
    def step(base_digits, n_valid):
        counts, hit_lanes = fn(base_digits.astype(jnp.int32),
                               jnp.reshape(n_valid, (1,)).astype(jnp.int32))
        return reduce_tile_maybes(counts, hit_lanes, hit_capacity,
                                  rescan_capacity, tile)

    return step


def make_salted_crack_step(algo: str, order: str, gen, batch: int,
                           salt_len: int, hit_capacity: int = 64,
                           interpret: bool = False):
    """Salted kernel crack step:
    step(base_digits, n_valid, salt int32[SALT_MAX], target int32[W])
    -> (count, lanes, tpos) -- the SaltedMaskWorker._invoke contract
    with runtime per-target args."""
    tile = SUB * 128
    fn = make_salted_pallas_fn(algo, order, gen, batch, salt_len,
                               interpret=interpret)

    @jax.jit
    def step(base_digits, n_valid, salt, target):
        counts, hit_lanes = fn(base_digits.astype(jnp.int32),
                               jnp.reshape(n_valid, (1,)).astype(jnp.int32),
                               salt.astype(jnp.int32), target)
        return reduce_tile_hits(counts, hit_lanes, hit_capacity, tile)

    return step


def emulate_ext_kernel(name: str, gen, target_words, batch: int,
                       base_digits, n_valid: int, sub: int = SUB,
                       order: Optional[str] = None,
                       salt: Optional[bytes] = None):
    """Run a variant body eagerly per grid cell (no pallas_call) --
    the validation vehicle for sha256-stage variants off-TPU, exactly
    like pallas_mask.emulate_mask_kernel."""
    tile = sub * 128
    if batch % tile:
        raise ValueError(f"batch {batch} not a multiple of tile {tile}")
    seg_tables, luts_np = position_tables(gen.charsets)
    has_lut = luts_np is not None
    salted = order is not None
    tables = None
    if salted:
        body = _build_ext_body(name, gen.radices, seg_tables, gen.length,
                               None, sub, order=order, salt_len=len(salt),
                               has_lut=has_lut)
        target_words = np.asarray(target_words)
        extra = (jnp.asarray(np.frombuffer(salt, np.uint8)
                             .astype(np.int32)),
                 jnp.asarray(target_words.astype(np.uint32)
                             .view(np.int32)))
    else:
        target_words = np.asarray(target_words)
        multi = target_words.ndim == 2 and target_words.shape[0] > 1
        probe = None
        if multi:
            rows, block_bits, k, n_grp, _ = kernel_probe_rows(target_words)
            tables = jnp.asarray(rows)
            probe = (block_bits, k, n_grp)
        body = _build_ext_body(name, gen.radices, seg_tables, gen.length,
                               target_words, sub, has_lut=has_lut,
                               probe=probe)
        extra = (tables,) if multi else ()
    if has_lut:
        extra = extra + (jnp.asarray(luts_np),)
    base = jnp.asarray(base_digits, jnp.int32)
    counts, lanes = [], []
    for pid in range(batch // tile):
        c, l = body(jnp.int32(pid), base, jnp.int32(n_valid), *extra)
        counts.append(int(c))
        lanes.append(int(l))
    return (np.asarray(counts, np.int32)[:, None],
            np.asarray(lanes, np.int32)[:, None])
