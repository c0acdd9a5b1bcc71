"""Measure the chip's peak rate of 32-bit integer vector operations:
the denominator of `mask_kernel_roofline` and `step_mfu`.

Run on the chip, by hand, once (`python benchmarks/peak_int32.py`); it
prints one JSON line and the value goes into `peaks.json` with this
file as its source.  It is not part of a measuring run.

The kernel: a Pallas grid over tiles of (ROWS, 128) int32; in each
tile `CHAINS` independent groups of four arrays run the round

    a += b;  b ^= c;  c += d;  d ^= a;  a <<= 1;  c >>= 1

(six operations a lane) `iters` times inside a `fori_loop` whose body
holds `unroll` rounds.  Every operation is an add, an xor or a shift
on full vector registers, each group's chain is independent of the
others (so the schedule can fill every slot), nothing is read or
written inside the loop, and the inputs arrive at run time, so the
compiler can fold nothing.  Operations = lanes x iters x 6.

A ceiling shows as agreement: the rate must not move when the loop is
unrolled deeper or more independent chains are given (more
instruction-level parallelism to take).  `main` measures the grid of
(chains, unroll) and reports the best rate, and whether the best three
agree within 3 %.
"""

import functools
import json
import sys
import time

ROWS = 8            # (8, 128): one vector register an array
OPS_PER_ROUND = 6


def build(chains, unroll, iters, tiles):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        x = x_ref[...]
        state = []
        for g in range(chains):
            state += [x + (4 * g), x ^ (4 * g + 1), x + (4 * g + 2),
                      x ^ (4 * g + 3)]

        def rounds(_, st):
            st = list(st)
            for _ in range(unroll):
                for g in range(chains):
                    a, b, c, d = st[4 * g:4 * g + 4]
                    a = a + b
                    b = b ^ c
                    c = c + d
                    d = d ^ a
                    a = a << 1
                    c = c >> 1
                    st[4 * g:4 * g + 4] = [a, b, c, d]
            return tuple(st)

        st = jax.lax.fori_loop(0, iters // unroll, rounds, tuple(state))
        acc = st[0]
        for s in st[1:]:
            acc = acc ^ s
        o_ref[...] = acc

    call = pl.pallas_call(
        kernel, grid=(tiles,),
        in_specs=[pl.BlockSpec((ROWS, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles * ROWS, 128), jnp.int32))
    return jax.jit(call)


def measure(chains, unroll, iters=8192, tiles=512, min_seconds=0.5):
    import jax
    import jax.numpy as jnp
    fn = build(chains, unroll, iters, tiles)
    x = jax.random.randint(jax.random.PRNGKey(chains * 100 + unroll),
                           (tiles * ROWS, 128), 0, 1 << 30, jnp.int32)
    fn(x).block_until_ready()
    calls, t0 = 0, time.perf_counter()
    while True:
        out = fn(x)
        calls += 1
        if calls % 4 == 0:
            out.block_until_ready()
            dt = time.perf_counter() - t0
            if dt >= min_seconds:
                break
    ops = (calls * tiles * ROWS * 128 * chains * (iters // unroll)
           * unroll * OPS_PER_ROUND)
    return ops / dt


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write("peak_int32.py: no TPU; nothing is measured\n")
        return 1
    grid = {}
    for chains in (2, 4, 6, 8):
        for unroll in (16, 64):
            grid[f"chains{chains}_unroll{unroll}"] = measure(chains, unroll)
            sys.stderr.write(f"{chains} {unroll} "
                             f"{grid[f'chains{chains}_unroll{unroll}']:.4e}\n")
    best = sorted(grid.values(), reverse=True)
    print(json.dumps({
        "device_kind": dev.device_kind,
        "int32_ops_per_s": best[0],
        "best_three_agree_within": (best[0] - best[2]) / best[0],
        "grid": grid}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
