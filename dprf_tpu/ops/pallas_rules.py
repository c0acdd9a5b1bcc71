"""Fused wordlist+rules Pallas kernel: an in-VMEM rule interpreter.

Config 3 ("on-device rule expansion") through the XLA pipeline is
gather-bound: the per-lane `take_along_axis` gathers in
rules/device.py and pack_varlen serialize exactly like the mask
decode's charset gathers did, orders of magnitude below the sha256
kernel rate.  This kernel keeps the whole chain -- word load,
rule application, varlen message pack, compression, compare -- in
VMEM/registers.

Design: a rule VIRTUAL MACHINE instead of trace-time rule unrolling.
Unrolling all R rules into one program multiplies the hash core R-fold
(~150k vector ops for best64 -- Mosaic program size explodes), so
instead the grid is (word_tile, rule) and each cell INTERPRETS its
rule's bytecode from SMEM:

- candidates ride the lanes as in the mask kernels; words are
  stored SoA -- one (8, 128) register per byte position -- so rule
  ops are vector selects;
- each interpreter step reads (opcode, p1, p2) scalars and applies
  one unified transform: a scalar-dispatched SOURCE-INDEX formula per
  position (identity, reverse, rotate, duplicate, delete, ...), one
  generic per-lane position gather (L selects per position -- L**2
  total, all vector ops), a byte-map stage (case toggles, appends,
  substitutions), then scalar-dispatched length/validity updates;
- the interpreter steps are UNROLLED to the job's longest rule, with
  shorter rules padded by NOOP opcodes (a fori_loop carrying the SoA
  byte tuple crashes the TPU backend compiler -- bisected on hardware
  r4: the same body inline compiles, the loop-carried form exits the
  remote compile helper with code 1);
- the message is packed varlen (lengths differ per lane after rules)
  and digested by the same compression cores the mask kernels share.

Semantics mirror rules/device.py (which mirrors rules/cpu.py) -- the
equivalence tests drive all three on the same words x rules.
Unsupported opcodes (PURGE's compaction sort, TITLE's separator scan)
make the JOB fall back to the XLA pipeline at worker-build time.

Cited reference behavior: SURVEY.md section A names config 3
(wordlist + best64, on-device rule expansion) as an acceptance
workload; every best64 opcode is supported here.
"""

from __future__ import annotations

import functools
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dprf_tpu.ops.pallas_mask import CORES, pallas_mode  # noqa: F401
from dprf_tpu.rules.parser import Op, Opcode
from dprf_tpu.utils import env as envreg


#: word-tile geometry: SUBW sublanes x 128 lanes of words per grid
#: cell.  Bigger tiles amortize per-cell control overhead exactly like
#: the mask kernel's SUB (r3 sweep); DPRF_RULES_SUBW overrides for
#: hardware tuning.
SUBW = envreg.get_int("DPRF_RULES_SUBW")
TILE_W = SUBW * 128
# the packed (count << 16) | (hit_lane + 1) output needs both fields
# in 16 bits (same constraint as pallas_mask's sub <= 128)
assert TILE_W <= 0xFFFF, "DPRF_RULES_SUBW > 511 overflows the packed output"

#: interpreter step budget per rule (best64's longest rule is 8 ops)
MAX_STEPS = 8

#: opcodes the kernel interprets.  PURGE needs a compaction sort and
#: TITLE/TITLE_SEP a separator scan over the ORIGINAL bytes -- both
#: are expressible but not worth the op budget until a real rule set
#: needs them; jobs containing them use the XLA pipeline.
SUPPORTED = frozenset(op for op in Opcode) - {
    Opcode.PURGE, Opcode.TITLE, Opcode.TITLE_SEP}

O = Opcode   # brevity in the interpreter tables


def rules_supported(rules: Sequence[Sequence[Op]]) -> bool:
    return all(len(ops) <= MAX_STEPS
               and all(op.opcode in SUPPORTED for op in ops)
               for ops in rules)


def encode_rules(rules: Sequence[Sequence[Op]],
                 n_steps: int = None) -> np.ndarray:
    """Rule set -> bytecode int32[R, n_steps, 3].  Unused steps stay
    all-zero = (NOOP, 0, 0), so the unrolled interpreter needs no
    per-rule step count."""
    R = len(rules)
    n_steps = (max((len(ops) for ops in rules), default=1)
               if n_steps is None else n_steps)
    bc = np.zeros((R, max(1, n_steps), 3), np.int32)
    for r, ops in enumerate(rules):
        for s, op in enumerate(ops):
            bc[r, s] = (int(op.opcode), op.p1, op.p2)
    return bc


def kernel_rules_eligible(engine_name: str, gen, n_targets: int) -> bool:
    """Whole-job eligibility for the rules kernel."""
    if engine_name not in CORES or n_targets != 1:
        return False
    if not hasattr(gen, "rules") or not hasattr(gen, "packed_words"):
        return False
    widen = CORES[engine_name][3]
    # the 0x80 pad at position max_len still fits the block (byte 55 /
    # UTF-16 byte 54), so the limits are the block limits themselves
    if gen.max_len > (27 if widen else 55):
        return False
    if engine_name in ("sha256", "sha-256"):
        import jax as _jax
        if _jax.default_backend() != "tpu":
            return False    # unrolled sha256 doesn't compile on XLA:CPU
    return rules_supported(gen.rules)


def _sel(pred, a, b):
    return jnp.where(pred, a, b)


def _interp_step(w, lens, valid, op, p1, p2, L: int, shape):
    """One rule-VM step.  w: tuple of L int32[(SUBW,128)] byte arrays
    (values 0..255), lens: int32, valid: int32 0/1 mask (SUBW,128) --
    an INT mask, not bool: a scalar-conditional select over i1 vectors
    crashes the TPU backend compiler (minimal repro, r4 probe log),
    and every opcode dispatch here is a scalar-conditional select.
    op/p1/p2 are SMEM scalars.  Returns the new (w, lens, valid)."""
    i32 = jnp.int32
    onev = jnp.ones(shape, i32)

    def eq(code):
        return op == i32(int(code))

    safe = jnp.maximum(lens, 1)

    # ---- 1. source-index formulas (per output position) -------------
    # Ops that MOVE bytes express as: out[p] = in[src(p)]; everything
    # else uses identity.  Vector formulas (len-dependent) computed
    # once per position; the scalar `op` collapses the select chain.
    def src_for(p):
        s = p * onev                                   # identity
        s = _sel(eq(O.REVERSE), lens - 1 - p, s)
        s = _sel(eq(O.DUPLICATE), _sel(p < lens, p, p - lens), s)
        s = _sel(eq(O.DUPLICATE_N), p % safe, s)
        s = _sel(eq(O.REFLECT),
                 _sel(p < lens, p, 2 * lens - 1 - p), s)
        s = _sel(eq(O.ROT_LEFT),
                 _sel(lens > 1, (p + 1) % safe, p), s)
        s = _sel(eq(O.ROT_RIGHT),
                 _sel(lens > 1, (p - 1 + safe) % safe, p), s)
        s = _sel(eq(O.DEL_FIRST), (p + 1) * onev, s)
        s = _sel(eq(O.DEL_AT) & (p1 < lens),
                 _sel(p < p1, p, p + 1) * onev, s)
        s = _sel(eq(O.EXTRACT) & (p1 < lens), (p + p1) * onev, s)
        s = _sel(eq(O.OMIT) & (p1 < lens),
                 _sel(p * onev < p1, p, p + p2), s)
        s = _sel(eq(O.INSERT) & (p1 <= lens),
                 _sel(p * onev < p1, p, p - 1), s)
        s = _sel(eq(O.PREPEND), (p - 1) * onev, s)
        s = _sel(eq(O.DUP_FIRST) & (lens > 0),
                 _sel(p * onev < p1, 0, p - p1), s)
        s = _sel(eq(O.DUP_LAST) & (lens > 0),
                 _sel(p < lens, p, lens - 1), s)
        s = _sel(eq(O.DUP_ALL), (p // 2) * onev, s)
        s = _sel(eq(O.SWAP_FRONT) & (lens >= 2),
                 i32(1 if p == 0 else (0 if p == 1 else p)) * onev, s)
        s = _sel(eq(O.SWAP_BACK) & (lens >= 2),
                 _sel(p == lens - 1, lens - 2,
                      _sel(p == lens - 2, lens - 1, p)), s)
        s = _sel(eq(O.SWAP_AT) & (p1 < lens) & (p2 < lens),
                 _sel(p * onev == p1, p2,
                      _sel(p * onev == p2, p1, p)), s)
        s = _sel(eq(O.REPL_NEXT) & (p * onev == p1) & (p1 + 1 < lens),
                 p1 + 1, s)
        s = _sel(eq(O.REPL_PREV) & (p * onev == p1) & (p1 >= 1)
                 & (p1 < lens), p1 - 1, s)
        s = _sel(eq(O.DUP_BLOCK_FRONT) & (p1 <= lens),
                 _sel(p * onev < p1, p, p - p1), s)
        s = _sel(eq(O.DUP_BLOCK_BACK) & (p1 <= lens),
                 _sel(p < lens, p, p - p1), s)
        return jnp.clip(s, 0, L - 1)

    gathered = []
    for p in range(L):
        src = src_for(p)
        acc = w[0]
        for q in range(1, L):
            acc = _sel(src == q, w[q], acc)
        gathered.append(acc)

    # ---- 2. byte-map stage -----------------------------------------
    out = []
    app_here = eq(O.APPEND)
    for p in range(L):
        g = gathered[p]
        up = (g >= 0x41) & (g <= 0x5A)
        lo = (g >= 0x61) & (g <= 0x7A)
        glow = _sel(up, g + 0x20, g)
        gup = _sel(lo, g - 0x20, g)
        gtog = _sel(up, g + 0x20, _sel(lo, g - 0x20, g))
        b = g
        b = _sel(eq(O.LOWER), glow, b)
        b = _sel(eq(O.UPPER), gup, b)
        b = _sel(eq(O.CAPITALIZE), gup if p == 0 else glow, b)
        b = _sel(eq(O.INV_CAPITALIZE), glow if p == 0 else gup, b)
        b = _sel(eq(O.TOGGLE_ALL), gtog, b)
        b = _sel(eq(O.TOGGLE_AT) & (p * onev == p1) & (p1 < lens),
                 gtog, b)
        b = _sel(app_here & (p == lens), p1 * onev, b)
        b = _sel(eq(O.PREPEND) & (p == 0), p1 * onev, b)
        b = _sel(eq(O.INSERT) & (p * onev == p1) & (p1 <= lens),
                 p2 * onev, b)
        b = _sel(eq(O.OVERWRITE) & (p * onev == p1) & (p1 < lens),
                 p2 * onev, b)
        b = _sel(eq(O.SUBSTITUTE) & (g == p1), p2 * onev, b)
        at = (p * onev == p1) & (p1 < lens)
        b = _sel(eq(O.INCR_AT) & at, (g + 1) & 0xFF, b)
        b = _sel(eq(O.DECR_AT) & at, (g - 1) & 0xFF, b)
        b = _sel(eq(O.SHIFT_LEFT) & at, (g << 1) & 0xFF, b)
        b = _sel(eq(O.SHIFT_RIGHT) & at, g >> 1, b)
        out.append(b)

    # ---- 3. length update ------------------------------------------
    grow = None   # mirror rules/device.py's growth-clamp semantics
    newlen = lens
    newlen = _sel(eq(O.DEL_FIRST) | eq(O.DEL_LAST),
                  jnp.maximum(lens - 1, 0), newlen)
    newlen = _sel(eq(O.DEL_AT) & (p1 < lens), lens - 1, newlen)
    newlen = _sel(eq(O.EXTRACT) & (p1 < lens),
                  jnp.minimum(p2, lens - p1), newlen)
    newlen = _sel(eq(O.OMIT) & (p1 < lens),
                  lens - jnp.minimum(p2, lens - p1), newlen)
    newlen = _sel(eq(O.TRUNCATE), jnp.minimum(lens, p1), newlen)
    grow_v = lens
    grow_v = _sel(eq(O.DUPLICATE) | eq(O.REFLECT) | eq(O.DUP_ALL),
                  2 * lens, grow_v)
    grow_v = _sel(eq(O.DUPLICATE_N), (p1 + 1) * lens, grow_v)
    grow_v = _sel(eq(O.INSERT) & (p1 <= lens), lens + 1, grow_v)
    grow_v = _sel(eq(O.APPEND) | eq(O.PREPEND), lens + 1, grow_v)
    grow_v = _sel((eq(O.DUP_FIRST) | eq(O.DUP_LAST)) & (lens > 0),
                  lens + p1, grow_v)
    grow_v = _sel((eq(O.DUP_BLOCK_FRONT) | eq(O.DUP_BLOCK_BACK))
                  & (p1 <= lens), lens + p1, grow_v)
    is_grow = (eq(O.DUPLICATE) | eq(O.REFLECT) | eq(O.DUP_ALL)
               | eq(O.DUPLICATE_N) | eq(O.INSERT) | eq(O.APPEND)
               | eq(O.PREPEND) | eq(O.DUP_FIRST) | eq(O.DUP_LAST)
               | eq(O.DUP_BLOCK_FRONT) | eq(O.DUP_BLOCK_BACK))
    newvalid = _sel(is_grow, valid * (grow_v <= L).astype(i32), valid)
    newlen = _sel(is_grow, jnp.minimum(grow_v, L), newlen)

    # ---- 4. rejection ops ------------------------------------------
    def contains(ch):
        m = jnp.zeros(shape, jnp.bool_)
        for q in range(L):
            m = m | ((out[q] == ch) & (q < newlen))
        return m.astype(i32)

    def count_ch(ch):
        c = jnp.zeros(shape, i32)
        for q in range(L):
            c = c + ((out[q] == ch) & (q < newlen)).astype(i32)
        return c

    def char_at(idx):
        c = jnp.zeros(shape, i32)
        for q in range(L):
            c = _sel(idx == q, out[q], c)
        return c

    newvalid = _sel(eq(O.REJ_GT),
                    newvalid * (newlen <= p1).astype(i32), newvalid)
    newvalid = _sel(eq(O.REJ_LT),
                    newvalid * (newlen >= p1).astype(i32), newvalid)
    newvalid = _sel(eq(O.REJ_NEQ_LEN),
                    newvalid * (newlen == p1).astype(i32), newvalid)
    newvalid = _sel(eq(O.REJ_CONTAIN),
                    newvalid * (1 - contains(p1)), newvalid)
    newvalid = _sel(eq(O.REJ_NOT_CONTAIN),
                    newvalid * contains(p1), newvalid)
    newvalid = _sel(eq(O.REJ_NOT_FIRST),
                    newvalid * ((newlen > 0)
                                & (out[0] == p1)).astype(i32), newvalid)
    newvalid = _sel(eq(O.REJ_NOT_LAST),
                    newvalid * ((newlen > 0)
                                & (char_at(newlen - 1) == p1))
                    .astype(i32), newvalid)
    newvalid = _sel(eq(O.REJ_NOT_AT),
                    newvalid * ((p1 < newlen)
                                & (char_at(p1 * onev) == p2))
                    .astype(i32), newvalid)
    newvalid = _sel(eq(O.REJ_LT_COUNT),
                    newvalid * (count_ch(p2) >= p1).astype(i32),
                    newvalid)

    # ---- 5. zero-tail invariant ------------------------------------
    out = tuple(_sel(p < newlen, out[p], 0) for p in range(L))
    return out, newlen, newvalid


def _pack_varlen_words(w, lens, L: int, shape, big_endian: bool,
                       widen: bool):
    """SoA bytes + per-lane lengths -> 16 single-block message words
    with Merkle-Damgard padding (0x80 at the per-lane length, 64-bit
    bit length in the tail words)."""
    m = [jnp.zeros(shape, jnp.uint32) for _ in range(16)]
    stride = 2 if widen else 1

    def put(q, byte_u32):
        word, b = divmod(q, 4)
        shift = 8 * (3 - b) if big_endian else 8 * b
        m[word] = m[word] | (byte_u32 << jnp.uint32(shift))

    for p in range(L):
        byte = _sel(p < lens, w[p], 0).astype(jnp.uint32)
        put(stride * p, byte)
    # the 0x80 pad rides its own position select: one of L+1 slots
    for p in range(L + 1):
        pad = _sel(lens == p, jnp.uint32(0x80), jnp.uint32(0))
        put(stride * p, pad)
    bitlen = (lens * (16 if widen else 8)).astype(jnp.uint32)
    if big_endian:
        m[15] = bitlen
    else:
        m[14] = bitlen
    return m


def ceil_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def step_buckets(rules) -> dict:
    """Group rule INDICES by ceil-power-of-two op count, so each
    compiled kernel unrolls only as many interpreter steps as its
    bucket needs (best64: one 8-op rule must not tax the ~50 one-op
    rules 8 unrolled steps each)."""
    out: dict = {}
    for i, ops in enumerate(rules):
        out.setdefault(ceil_pow2(max(1, len(ops))), []).append(i)
    return out


def make_rules_pallas_fn(engine_name: str, gen, target_words,
                         tiles_per_step: int, interpret: bool = False,
                         rule_indices=None, shared_words=None):
    """Build fn(tile0 int32, n_valid_local int32[1]) ->
    (counts int32[G, 1], hit_lanes int32[G, 1]) over a window of
    tiles_per_step word tiles x ALL rules of the set.

    Cell (i, j) covers words [tile0*TILE_W + i*TILE_W, ...+TILE_W)
    under rule j; output row i * R + j.  n_valid_local is the valid
    word count RELATIVE to the window start.
    """
    core, n_words_d, big_endian, widen = CORES[engine_name]
    L = gen.max_len
    all_rules = gen.rules
    rule_indices = (list(range(len(all_rules)))
                    if rule_indices is None else list(rule_indices))
    rules = [all_rules[i] for i in rule_indices]
    R = len(rules)
    if not kernel_rules_eligible(engine_name, gen, 1):
        raise ValueError("job not rules-kernel eligible")
    if np.asarray(target_words).reshape(-1).shape[0] != n_words_d:
        raise ValueError(f"expected {n_words_d} target words")
    bc_np = encode_rules(rules)
    n_steps = bc_np.shape[1]

    # a window covers tiles_per_step*TILE_W words starting at ANY word
    # (units need not be tile-aligned), so it spans tiles_per_step + 1
    # tiles from the floor-aligned tile0
    Twin = tiles_per_step + 1
    if shared_words is not None:
        w4, l3 = shared_words
        n_tiles = w4.shape[0]
        # a window needs ceil(n_words/TILE_W) + Twin padding tiles;
        # arrays shared from a narrower-window build would let the
        # host-side dynamic_slice clamp and silently shift the whole
        # window to earlier words -- rebuild instead of reusing
        if n_tiles < -(-gen.n_words // TILE_W) + Twin:
            shared_words = None
    if shared_words is None:
        # words in HBM as (n_tiles, L, SUBW, 128) int32 SoA tiles,
        # padded so the host-side dynamic_slice can never clamp for
        # any in-range start tile (a clamped start would silently
        # shift the whole window to earlier words)
        words_np, lens_np = gen.packed_words(pad_to=TILE_W)
        N = words_np.shape[0]
        padN = (-(-max(N, 1) // TILE_W) + Twin) * TILE_W
        n_tiles = padN // TILE_W
        wpad = np.zeros((padN, L), np.uint8)
        wpad[:N] = words_np[:, :L]
        lpad = np.zeros((padN,), np.int32)
        lpad[:N] = lens_np
        w4 = jnp.asarray(wpad.astype(np.int32)
                         .reshape(n_tiles, SUBW, 128, L)
                         .transpose(0, 3, 1, 2))    # (T, L, SUBW, 128)
        l3 = jnp.asarray(lpad.reshape(n_tiles, SUBW, 128))

    shape = (SUBW, 128)

    def kernel(nvalid_ref, bc_ref, tgt_ref, w_ref, l_ref, out_ref):
        i = pl.program_id(0)
        j = pl.program_id(1)
        lane = (lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + lax.broadcasted_iota(jnp.int32, shape, 1))
        w = tuple(w_ref[0, q] for q in range(L))
        lens = l_ref[0]
        # window-relative word index; valid iff inside [lo, hi) --
        # lo is the unit start's offset within its floor tile, so
        # units need not be TILE_W-aligned.  int32 0/1 mask, not bool
        # (see _interp_step)
        lane_w = lane + i * TILE_W
        valid = ((lane_w >= nvalid_ref[0])
                 & (lane_w < nvalid_ref[1])).astype(jnp.int32)

        # unrolled to the job's longest rule; padded steps are NOOPs
        # (a loop-carried SoA tuple crashes the backend compiler)
        for s in range(n_steps):
            w, lens, valid = _interp_step(w, lens, valid,
                                          bc_ref[j, s, 0],
                                          bc_ref[j, s, 1],
                                          bc_ref[j, s, 2], L, shape)
        m = _pack_varlen_words(w, lens, L, shape, big_endian, widen)
        digest = core(m, shape)
        found = valid > 0
        for i_w, got in enumerate(digest):
            # runtime target: SMEM scalars (int32 bit pattern), so one
            # compiled step serves any target of the job
            found = found & (got == tgt_ref[i_w].astype(jnp.uint32))
        count = jnp.sum(found.astype(jnp.int32))
        hit_lane = jnp.max(jnp.where(found, lane, -1))
        out_ref[...] = jnp.full((8, 128), (count << 16) | (hit_lane + 1),
                                jnp.int32)

    grid = (Twin, R)
    raw = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((2,), lambda i, j: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((R, n_steps, 3), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n_words_d,), lambda i, j: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, L, SUBW, 128), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, SUBW, 128), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((8, 128), lambda i, j: (i * R + j, 0))],
        out_shape=[jax.ShapeDtypeStruct((Twin * R * 8, 128), jnp.int32)],
        interpret=interpret,
    )
    bc_dev = jnp.asarray(bc_np)
    tgt_default = jnp.asarray(np.asarray(target_words).reshape(-1)
                              .astype(np.uint32).view(np.int32))

    def fn(tile0, lohi, words4=w4, lens3=l3, target=None):
        # words4/lens3 default to the job's arrays but are real
        # ARGUMENTS (not closure constants): a closure jnp array would
        # be baked into the lowered module as an 84 MB constant for a
        # 1M-word list
        tgt = tgt_default if target is None else target
        ws = lax.dynamic_slice(words4, (tile0, 0, 0, 0),
                               (Twin, L, SUBW, 128))
        ls = lax.dynamic_slice(lens3, (tile0, 0, 0),
                               (Twin, SUBW, 128))
        (packed,) = raw(lohi, bc_dev, tgt, ws, ls)
        p = packed[::8, 0:1]
        return p >> 16, (p & 0xFFFF) - 1

    fn.n_tiles_total = n_tiles
    fn.tiles_per_step = tiles_per_step
    fn.n_rules = R
    fn.words4 = w4
    fn.lens3 = l3
    return fn


def make_rules_crack_step(engine_name: str, gen, target_words,
                          word_batch: int, hit_capacity: int = 64,
                          interpret: bool = False, shared_words=None):
    """DeviceWordlistWorker-contract step over the rules kernels:
    step(w0, n_valid_words) -> (count, lanes int32[cap], tpos) with
    flat rule-major lanes (lane = r * word_batch + b).

    w0 may start at ANY word (WorkUnits are not tile-aligned): the
    kernels get a floor-aligned tile window one tile wider plus a
    window-relative [lo, hi) valid range, and hit lanes are rebased
    to w0.

    The rule set is bucketed by op count (step_buckets) into one
    compiled kernel per bucket -- measured 39.5 MH/s for config 3 with
    the single 8-step kernel, where the one 8-op best64 rule taxed
    every cell -- and each bucket's cells pay only their own depth.
    All buckets share the words arrays and dispatch back to back
    before one merged hit compaction."""
    from dprf_tpu.ops import compare as cmp_ops

    T = max(1, word_batch // TILE_W)
    B = T * TILE_W
    buckets = step_buckets(gen.rules)
    fns = []
    # caller-provided arrays (e.g. a worker sharing one copy across
    # wide-step sizes) are reused when their padding suffices --
    # make_rules_pallas_fn checks and rebuilds otherwise, so always
    # re-read the arrays the first bucket ACTUALLY used
    shared = shared_words
    for nsteps in sorted(buckets):
        idxs = buckets[nsteps]
        fnb = make_rules_pallas_fn(engine_name, gen, target_words, T,
                                   interpret=interpret,
                                   rule_indices=idxs,
                                   shared_words=shared)
        shared = (fnb.words4, fnb.lens3)
        fns.append((fnb, jnp.asarray(np.asarray(idxs, np.int32)),
                    len(idxs)))

    @jax.jit
    def _step(words4, lens3, tgt, w0, n_valid_words):
        tile0 = (w0 // TILE_W).astype(jnp.int32)
        lo = (w0 - tile0 * TILE_W).astype(jnp.int32)
        lohi = jnp.stack([lo, lo + n_valid_words.astype(jnp.int32)])
        cs, flats = [], []
        for fnb, orig, Rb in fns:
            counts, hit_lanes = fnb(tile0, lohi, words4, lens3, tgt)
            c = counts[:, 0]
            hl = hit_lanes[:, 0]
            rows = jnp.arange(c.shape[0], dtype=jnp.int32)
            i = rows // Rb
            j = rows % Rb
            # bucket-local rule j -> ORIGINAL rule index; in-window
            # lane rebased to the unit's word start (subtract lo)
            flats.append(jnp.take(orig, j) * B + i * TILE_W + hl - lo)
            cs.append(c)
        c_all = jnp.concatenate(cs)
        flat_all = jnp.concatenate(flats)
        total = jnp.sum(c_all)
        collision = jnp.any(c_all > 1)
        _, rows, _ = cmp_ops.compact_hits(c_all > 0,
                                          jnp.zeros_like(c_all),
                                          hit_capacity)
        lanes = jnp.where(rows >= 0, flat_all[jnp.maximum(rows, 0)], -1)
        count = jnp.where(collision, jnp.int32(hit_capacity + 1), total)
        return count, lanes, jnp.zeros_like(lanes)

    w4, l3 = shared
    tgt0 = jnp.asarray(np.asarray(target_words).reshape(-1)
                       .astype(np.uint32).view(np.int32))

    def step(w0, n_valid_words, target=tgt0):
        return _step(w4, l3, target, w0, n_valid_words)

    # AOT entry (dprf prewarm, the described-chip compile tests): the
    # SAME jitted program, with the word tables as the arguments they
    # are in production
    step.lower = (lambda w0, n_valid_words, target=tgt0, words4=w4,
                  lens3=l3: _step.lower(words4, lens3, target, w0,
                                        n_valid_words))
    step.word_batch = B
    step.words4, step.lens3 = w4, l3    # for cross-step sharing
    return step
