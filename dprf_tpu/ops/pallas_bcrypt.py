"""Pallas EksBlowfish advance kernel: vector-rate S-box gathers.

The XLA batched form (ops/blowfish.py) lowers each Feistel round's
four per-candidate S-box reads to per-lane SERIAL gathers, far below
any bandwidth or ALU limit.

The kernel reshapes the problem so the gather is the hardware's native
per-sublane dynamic gather (the same `take_along_axis` shape the Bloom
prefilter kernel proved lowers and runs on this chip):

- candidates ride the SUBLANE axis, SUBC per grid cell;
- each candidate's 4 KB S state is uint32[SUBC, 1024] in VMEM -- the
  1024-entry flat box axis rides the LANES, so one 256-entry box is
  two 128-lane chunks;
- a Feistel lookup gathers along lanes per sublane: two chunk gathers
  + a bit-8 select per box, all (SUBC, 128) vector ops, ~12 vector
  ops per round instead of 4*SUBC serial loads;
- EksBlowfish's S rewrites happen at the SAME flat position for every
  candidate (the chain index is uniform), so the "scatter" is one
  iota==pos select over the lane axis -- no scatter support needed.

The kernel advances (P, S) by a RUNTIME n_rounds of
{ExpandKey(key); ExpandKey(salt)} with everything resident in VMEM,
and is a drop-in `advance` for ChunkedEks, so the deadline-bounded
chunking, sharded workers, and worker protocols all reuse it.

P and key are carried as uint32[B, 128] lane-padded arrays (words
0..17 live in lanes 0..17) to keep every block shape (8k, 128m);
pad_p18/unpad_p18 convert at the chunk boundary (host side, once per
batch -- noise next to seconds of cost loop).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from dprf_tpu.ops import blowfish as bf_ops
from dprf_tpu.utils import env as envreg

#: candidates (sublanes) per grid cell.  VMEM per cell is
#: SUBC * (4 KB S + padded P/key) ~= SUBC * 5 KB (~320 KB at 64).
#: The per-candidate op count is SUBC-independent, so a larger cell
#: amortizes loop/control overhead; 64 won an SUBC 8..64 sweep on an
#: earlier installation and has not been re-swept on this one.
SUBC = envreg.get_int("DPRF_BCRYPT_SUBC")


def pad_p18(x: jnp.ndarray) -> jnp.ndarray:
    """uint32[B, 18] -> uint32[B, 128] (words in lanes 0..17)."""
    return jnp.pad(x, ((0, 0), (0, 110)))


def unpad_p18(x: jnp.ndarray) -> jnp.ndarray:
    return x[:, :18]


def _gather_box(S, box: int, idx):
    """S uint32[SUBC, 1024], box 0..3, idx uint32[SUBC, 128] (entry
    index 0..255, replicated along lanes) -> gathered value
    uint32[SUBC, 128].  Two per-sublane 128-lane gathers + a bit-8
    select."""
    # static slices (Mosaic has no dynamic_slice; box is a Python int)
    lo = S[:, box * 256:box * 256 + 128]
    hi = S[:, box * 256 + 128:box * 256 + 256]
    idx7 = (idx & jnp.uint32(127)).astype(jnp.int32)
    glo = jnp.take_along_axis(lo, idx7, axis=1)
    ghi = jnp.take_along_axis(hi, idx7, axis=1)
    return jnp.where(idx < 128, glo, ghi)


def _feistel_v(S, x):
    """F(x) on (SUBC, 128) lane-replicated x."""
    a = x >> jnp.uint32(24)
    b = (x >> jnp.uint32(16)) & jnp.uint32(0xFF)
    c = (x >> jnp.uint32(8)) & jnp.uint32(0xFF)
    d = x & jnp.uint32(0xFF)
    return ((_gather_box(S, 0, a) + _gather_box(S, 1, b))
            ^ _gather_box(S, 2, c)) + _gather_box(S, 3, d)


def _encrypt_v(P, S, l, r):
    """16-round Blowfish on lane-replicated (SUBC, 128) halves.
    P uint32[SUBC, 128] (words in lanes 0..17): P[..., i] reads are
    static lane slices broadcast back over the lanes."""
    def pw(i):
        return jnp.broadcast_to(P[:, i:i + 1], l.shape)

    for i in range(0, 16, 2):
        l = l ^ pw(i)
        r = r ^ _feistel_v(S, l)
        r = r ^ pw(i + 1)
        l = l ^ _feistel_v(S, r)
    return r ^ pw(17), l ^ pw(16)


def _expand_key_v(P, S, key):
    """ExpandKey (no salt -- the cost-loop form) on kernel layouts:
    P/key uint32[SUBC, 128] lane-padded, S uint32[SUBC, 1024]."""
    lane128 = lax.broadcasted_iota(jnp.int32, P.shape, 1)
    P = jnp.where(lane128 < 18, P ^ key, P)
    shape = (P.shape[0], 128)
    zero = jnp.zeros(shape, jnp.uint32)

    def p_body(i, carry):
        P, l, r = carry
        l, r = _encrypt_v(P, S, l, r)
        # uniform write positions 2i, 2i+1 (same for every candidate):
        # the l/r values are lane-replicated, so a lane-iota select IS
        # the scatter
        P = jnp.where(lane128 == 2 * i, l, P)
        P = jnp.where(lane128 == 2 * i + 1, r, P)
        return P, l, r

    P, l, r = lax.fori_loop(0, 9, p_body, (P, zero, zero))
    lane1024 = lax.broadcasted_iota(jnp.int32, S.shape, 1)

    def s_body(j, carry):
        S, l, r = carry
        l, r = _encrypt_v(P, S, l, r)
        pos = 2 * j
        lw = jnp.broadcast_to(l[:, 0:1], S.shape)
        rw = jnp.broadcast_to(r[:, 0:1], S.shape)
        S = jnp.where(lane1024 == pos, lw, S)
        S = jnp.where(lane1024 == pos + 1, rw, S)
        return S, l, r

    S, l, r = lax.fori_loop(0, 512, s_body, (S, l, r))
    return P, S


def _advance_kernel(nrounds_ref, salt18_ref, P_ref, S_ref, key_ref,
                    Pout_ref, Sout_ref):
    """Advance one SUBC-candidate block by n_rounds cost iterations."""
    P = P_ref[...]
    S = S_ref[...]
    key = key_ref[...]
    lane128 = lax.broadcasted_iota(jnp.int32, P.shape, 1)
    # salt18 as a lane-padded constant row (uniform across candidates)
    salt = jnp.zeros(P.shape, jnp.uint32)
    for i in range(18):
        salt = jnp.where(lane128 == i,
                         salt18_ref[i].astype(jnp.uint32), salt)

    def body(_, PS):
        P, S = PS
        P, S = _expand_key_v(P, S, key)
        P, S = _expand_key_v(P, S, salt)
        return P, S

    P, S = lax.fori_loop(0, nrounds_ref[0], body, (P, S))
    Pout_ref[...] = P
    Sout_ref[...] = S


@functools.lru_cache(maxsize=8)
def _advance_call(batch: int, interpret: bool, subc: int):
    grid = batch // subc

    raw = pl.pallas_call(
        _advance_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((18,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((subc, 128), lambda i: (i, 0)),
            pl.BlockSpec((subc, 1024), lambda i: (i, 0)),
            pl.BlockSpec((subc, 128), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((subc, 128), lambda i: (i, 0)),
            pl.BlockSpec((subc, 1024), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, 128), jnp.uint32),
            jax.ShapeDtypeStruct((batch, 1024), jnp.uint32),
        ],
        interpret=interpret,
    )
    return raw


@functools.lru_cache(maxsize=8)
def make_pallas_eks_advance(batch: int, interpret: bool = False,
                            subc: int = None):
    """Build `advance(P, S, key_words, salt18, n) -> (P, S)` with the
    ChunkedEks contract (18-word P/key, uint32[B, 1024] S) running the
    cost loop through the Pallas kernel.  A batch that doesn't tile
    into subc-candidate grid cells is row-padded into the kernel and
    sliced back (wordlist batches are word_batch * n_rules -- rarely a
    SUBC multiple).  Cached so the routing micro-bench and the worker
    share one compile."""
    subc = SUBC if subc is None else subc
    padded = -(-batch // subc) * subc
    raw = _advance_call(padded, interpret, subc)
    extra = padded - batch

    @jax.jit
    def advance(P, S, key_words, salt18, n):
        Pp = jnp.pad(pad_p18(P), ((0, extra), (0, 0)))
        kp = jnp.pad(pad_p18(key_words), ((0, extra), (0, 0)))
        Sp = jnp.pad(S, ((0, extra), (0, 0)))
        n1 = jnp.reshape(n, (1,)).astype(jnp.int32)
        s18 = salt18.astype(jnp.int32)
        Pp, Sp = raw(n1, s18, Pp, Sp, kp)
        return unpad_p18(Pp)[:batch], Sp[:batch]

    return advance


def make_best_eks_advance(batch: int):
    """(advance, impl) for this batch's ChunkedEks: impl "pallas", the
    compiled Pallas kernel, when the kernel path is on a real chip
    (per-round time scales linearly with batch there, where the XLA
    form's gathers serialize); else impl "xla", the donating jitted
    XLA form.  The workers publish impl (describe_worker), so a job's
    log says which one ran.

    Mosaic raises lowering errors at the first CALL, not at build, so
    the kernel is run here for 1 round on zero state before being
    returned: a lowering failure raises at worker construction with
    the compiler's message, not mid-job."""
    from dprf_tpu.ops.pallas_mask import pallas_mode
    mode = pallas_mode()
    # real Mosaic only: the interpret path exists for the dedicated
    # equivalence test (make_pallas_eks_advance directly); a 2**cost
    # chain through interpreted Pallas would be slower than the oracle
    if mode is not None and not mode.get("interpret", False):
        adv = make_pallas_eks_advance(batch)
        Z = jnp.zeros
        out = adv(Z((batch, 18), jnp.uint32),
                  Z((batch, 1024), jnp.uint32),
                  Z((batch, 18), jnp.uint32),
                  Z((18,), jnp.uint32), jnp.int32(1))
        jax.device_get(out[0][0, 0])     # force the compile+run
        return adv, "pallas"
    return jax.jit(bf_ops.eks_rounds, donate_argnums=(0, 1)), "xla"
