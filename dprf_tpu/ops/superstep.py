"""Super-step: one device dispatch covering many worker batches.

Why: every dispatch enqueue / argument transfer adds fixed host
overhead.  The production workers process a WorkUnit as `unit_strides`
separate step dispatches plus one flag readback; at fast-engine rates
(~1 ms of device work per 4M-candidate batch) that fixed cost can
dominate.  How much it costs on a locally attached chip is not
measured yet (ROADMAP S2).

This module is the *production-grade* version of that bench wrapper
(dprf_tpu/bench.py make_looped_step is measurement-only: it discards
hit lanes).  A super-step wraps a worker crack step in a `lax.scan` of
`inner` iterations inside ONE jit:

  - xs carries each iteration's leading step argument, precomputed on
    host: a [inner, L] matrix of mixed-radix digit vectors for mask
    steps, or an [inner] vector of word-window starts for wordlist
    steps.  Host-side digit math is microseconds; shipping it as one
    array replaces `inner` separate small transfers.
  - n_valid is the TOTAL valid candidates (or words) across the super
    chunk; each iteration clips its own share, so partial tails are
    exact.
  - The per-iteration step outputs are returned STACKED (scan ys), so
    hit decoding on the host sees exactly the same (count, lanes, ...)
    tuples the per-batch path produces -- same overflow semantics,
    same rescan granularity (one batch), no on-device merge logic.
  - The unit-level "does the host need to look at this" flag is
    accumulated in the scan carry and returned as one scalar: a
    hitless unit still costs a single scalar readback, never a
    stacked-buffer fetch.

The scan body compiles once regardless of `inner`, carrying only an
int32 scalar.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: per-dispatch int32 lane budget: batch * inner must stay below 2^31
#: (step-internal lane arithmetic and the n_valid clip are int32).
INT32_BUDGET = (1 << 31) - 256


def max_inner(batch: int, cap: int = 512) -> int:
    """Largest power-of-two inner length whose super chunk fits int32
    arithmetic (and an optional cap)."""
    n = min(cap, INT32_BUDGET // max(1, batch))
    return 1 << (n.bit_length() - 1) if n >= 1 else 0


#: widest hit buffer a fused window carries, unless the job's own
#: per-batch capacity is wider still: keeps the window's reduce and
#: gather buffers small.
WINDOW_CAPACITY_MAX = 1024


def window_capacity(hit_capacity: int, scale: int) -> int:
    """Hit-buffer width of a fused window covering ``scale`` batches:
    the ONE width policy of every window program (wide, loop and the
    sharded superstep).  Per-candidate capacity matches the per-batch
    step's up to WINDOW_CAPACITY_MAX slots; never below the nominal
    capacity (a raised --hit-cap reaches every program unclamped), so
    ``scale`` 1 is the per-batch width itself."""
    return max(hit_capacity,
               min(hit_capacity * scale, WINDOW_CAPACITY_MAX))


def make_super_step(step, inner: int, batch: int, flag_fn=None):
    """Wrap `step(x, n_valid) -> tuple` in a device-side scan.

    Returns super_step(xs, n_valid_total) -> (flag, stacked_outputs)
    where xs[i] is iteration i's leading argument and stacked_outputs
    mirrors the step's output tuple with a leading [inner] axis.

    flag_fn(out) -> int32 scalar marks an iteration as needing host
    attention (default: out[0], the hit count).  The returned flag is
    the sum over iterations.
    """
    if inner < 1:
        raise ValueError("inner must be >= 1")
    if inner * batch > INT32_BUDGET:
        raise ValueError(
            f"inner*batch = {inner * batch} overflows int32 lane "
            f"arithmetic (max {INT32_BUDGET}); lower inner")

    @jax.jit
    def super_step(xs, n_valid):
        n_valid = jnp.asarray(n_valid, jnp.int32)

        def body(acc, xi):
            x, i = xi
            nv = jnp.clip(n_valid - i * batch, 0, batch)
            out = step(x, nv)
            f = flag_fn(out) if flag_fn is not None else out[0]
            return acc + f.astype(jnp.int32), out

        acc, outs = lax.scan(
            body, jnp.int32(0),
            (xs, jnp.arange(inner, dtype=jnp.int32)))
        return acc, outs

    return super_step


def make_loop_super_step(step, inner: int, batch: int, groups):
    """The KERNEL-path superstep: a scalar/small-buffer-carry
    ``fori_loop`` over an OFFSET-AWARE per-batch step, fusing ``inner``
    batches into one dispatch with device-resident hit accumulation --
    the sharded runtime's superstep discipline brought to the
    single-chip Pallas path.

    Why not make_super_step: the scan shape stacks every batch's
    outputs and takes a fresh leading argument per iteration.  Here
    ONE compiled kernel is invoked ``inner`` times with only the
    window offset varying (the bench fori_loop shape, carrying a few
    hundred int32s instead of stacked per-batch outputs), and
    per-batch hits fold into fixed window-relative buffers on device.

    step(x, n_valid, offset) -> tuple of scalars and buffers; `groups`
    describes the accumulation, one entry per (count, buffer) pair:

        (count_idx, buf_idx, payload_idx | None, scale, capacity)

    - out[count_idx]: the batch's authoritative count (may exceed the
      batch buffer on collision/overflow -- the inflation survives
      accumulation, so window drains keep the exact-redrive
      discipline);
    - out[buf_idx]: compacted indices, valid entries first, -1
      padding; iteration i's entries are globalized by ``+ i * scale``
      (scale = batch for lane buffers, grid for tile buffers);
    - out[payload_idx]: optional same-shape payload riding along;
    - capacity: the WINDOW buffer length for this group.

    A group with buf_idx None is a count alone: out[count_idx] summed
    over the window (a bulk list's bitmap survivors).

    Returns super_step(x, n_valid_total, *extra) -> the step's output
    tuple shape with window-relative buffers -- decodable exactly like
    a wide-mode result.  `extra` goes to every call of the step behind
    its offset: arguments of the program, the same for every batch (a
    bulk list's probe table), never constants of it.  n_valid_total is the whole window's bound; the
    offset-aware step masks validity globally, so partial tails are
    exact without per-iteration clips.
    """
    if inner < 1:
        raise ValueError("inner must be >= 1")
    if inner * batch > INT32_BUDGET:
        raise ValueError(
            f"inner*batch = {inner * batch} overflows int32 lane "
            f"arithmetic (max {INT32_BUDGET}); lower inner")

    @jax.jit
    def super_step(x, n_valid, *extra):
        n_valid = jnp.asarray(n_valid, jnp.int32)
        init = []
        for (_, bi, pi, _, cap) in groups:
            init.append(jnp.int32(0))
            if bi is None:
                continue
            init.append(jnp.full((cap,), -1, jnp.int32))
            if pi is not None:
                init.append(jnp.full((cap,), -1, jnp.int32))
        init = tuple(init)

        def body(i, carry):
            out = step(x, n_valid, (i * batch).astype(jnp.int32), *extra)
            new, at = [], 0
            for (ci, bi, pi, scale, cap) in groups:
                c_i = out[ci].astype(jnp.int32)
                if bi is None:
                    new.append(carry[at] + c_i)
                    at += 1
                    continue
                count, buf = carry[at], carry[at + 1]
                idx_i = out[bi]
                ok = idx_i >= 0
                rel = jnp.where(ok, idx_i + i * jnp.int32(scale), -1)
                slots = jnp.where(
                    ok, count + jnp.arange(idx_i.shape[0],
                                           dtype=jnp.int32), cap)
                new.append(count + c_i)
                new.append(buf.at[slots].set(rel, mode="drop"))
                at += 2
                if pi is not None:
                    pay = carry[at]
                    new.append(pay.at[slots].set(out[pi], mode="drop"))
                    at += 1
            return tuple(new)

        fin = lax.fori_loop(0, inner, body, init)
        out, at = {}, 0
        for (ci, bi, pi, _, _) in groups:
            out[ci] = fin[at]
            if bi is None:
                at += 1
                continue
            out[bi] = fin[at + 1]
            at += 2
            if pi is not None:
                out[pi] = fin[at]
                at += 1
        return tuple(out[k] for k in sorted(out))

    return super_step
