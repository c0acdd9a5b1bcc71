"""Device bcrypt engine: the memory-hard / low-throughput path
(benchmark config 4).

bcrypt is salted with a per-target cost, so unlike the fast unsalted
engines one digest computation cannot serve a target list: the fused
step takes (salt_words, n_rounds, target_words) as *runtime* arguments
and the worker sweeps the keyspace once per target.  One compiled
program serves every bcrypt target of any cost.

The heavy state (4 KB of S-boxes per candidate lane) and the serial
EksBlowfish chains live in ops/blowfish.py; batches are kept small --
at cost 12 each candidate is ~4.3M Blowfish encryptions, so a batch is
seconds of device time and bigger batches only add latency, not
throughput.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from dprf_tpu.engines import register
from dprf_tpu.engines.base import Target
from dprf_tpu.engines.cpu.engines import BcryptEngine
from dprf_tpu.ops import blowfish as bf_ops
from dprf_tpu.utils import env as envreg
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops.rules_pipeline import expand_rules
from dprf_tpu.runtime.worker import (Hit, CpuWorker, word_cover_range,
                                     wordlist_lane_to_gidx)
from dprf_tpu.runtime.workunit import WorkUnit

#: default candidates per device step; bcrypt steps are seconds long
#: even at this size, and 4 KB of S-box state per lane caps usefully
#: large batches anyway (4096 lanes = 16 MB of mutating state).
DEFAULT_BATCH = 1 << 12


class RoutedCpuBcryptWorker(CpuWorker):
    """Returned by the bcrypt worker factories when the measured CPU
    oracle rate beats the device rate (run bcrypt on the winner,
    don't silently lose on the accelerator)."""

    def __init__(self, oracle, gen, targets, chunk: int = 2048):
        super().__init__(oracle, gen, targets, chunk)
        self.stride = chunk

    def warmup(self) -> None:
        pass


def measure_eks_rates(oracle, batch: int, rounds: int = 16) -> dict:
    """Head-to-head candidate-rounds/second: the device advance (best
    available form) vs the CPU oracle, both over `rounds` EksBlowfish
    cost rounds.  Rounds scale linearly (measured r3/r4), so a 16-round
    micro-bench predicts any cost."""
    from dprf_tpu.ops.pallas_bcrypt import make_best_eks_advance
    from dprf_tpu.utils.sync import hard_sync

    rng = np.random.RandomState(1)
    cand = rng.randint(97, 123, (batch, 8), dtype=np.uint8)
    kw = bf_ops.key_words_from_candidates(
        jnp.asarray(cand), jnp.full((batch,), 8, jnp.int32))
    sw = jnp.asarray(np.frombuffer(bytes(range(16)), ">u4")
                     .astype(np.uint32))
    s18 = bf_ops.salt18_words(sw)
    advance, _ = make_best_eks_advance(batch)
    P, S = bf_ops.eks_setup_begin(kw, sw)
    P, S = advance(P, S, kw, s18, jnp.int32(1))     # warm the compile
    hard_sync(S)
    t0 = time.perf_counter()
    P, S = advance(P, S, kw, s18, jnp.int32(rounds))
    hard_sync(S)
    device = batch * rounds / (time.perf_counter() - t0)

    n_cpu = 2
    cost4 = {"salt": bytes(range(16)), "cost": 4}
    t0 = time.perf_counter()
    oracle.hash_batch([bytes(cand[i]) for i in range(n_cpu)],
                      params=cost4)
    cpu = n_cpu * 16 / (time.perf_counter() - t0)
    return {"device_cand_rounds_s": device, "cpu_cand_rounds_s": cpu,
            "batch": batch, "rounds": rounds}


def _route_bcrypt(oracle, batch: int):
    """(use_cpu, rates) for a bcrypt job.  DPRF_BCRYPT_ROUTE forces
    'cpu' or 'device'; 'auto' measures on the TPU backend (off-TPU the
    device path is the test vehicle and always wins vs the pure-Python
    oracle anyway)."""
    from dprf_tpu.utils.logging import DEFAULT as log

    mode = envreg.get_str("DPRF_BCRYPT_ROUTE")
    if mode == "cpu" and oracle is None:
        log.warn("DPRF_BCRYPT_ROUTE=cpu but the job has no oracle "
                 "engine; staying on the device")
        return False, None
    if mode in ("cpu", "device"):
        log.info("bcrypt device routing forced", route=mode)
        return mode == "cpu", {"forced": mode}
    if oracle is None or jax.default_backend() != "tpu":
        return False, None
    rates = measure_eks_rates(oracle, batch)
    use_cpu = rates["cpu_cand_rounds_s"] > rates["device_cand_rounds_s"]
    log.info("bcrypt routed by measurement",
             winner="cpu" if use_cpu else "device",
             device_cand_rounds_s=f"{rates['device_cand_rounds_s']:.1f}",
             cpu_cand_rounds_s=f"{rates['cpu_cand_rounds_s']:.1f}")
    return use_cpu, rates


@register("bcrypt", device="jax")
class JaxBcryptEngine(BcryptEngine):
    """Device bcrypt.  Inherits hash parsing ($2a/$2b lines) from the
    CPU engine; hash_batch runs the EksBlowfish pipeline on device.
    Worker factories measure the device vs the CPU oracle at job start
    and route to the winner (_route_bcrypt)."""

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("bcrypt needs target params (salt, cost)")
        if any(len(c) > self.max_candidate_len for c in candidates):
            raise ValueError("bcrypt: candidate longer than 72 bytes")
        B = len(candidates)
        L = max(max((len(c) for c in candidates), default=1), 1)
        buf = np.zeros((B, L), dtype=np.uint8)
        lens = np.zeros((B,), dtype=np.int32)
        for i, c in enumerate(candidates):
            buf[i, :len(c)] = np.frombuffer(c, dtype=np.uint8)
            lens[i] = len(c)
        dw = _jit_bcrypt_batch(
            jnp.asarray(buf), jnp.asarray(lens),
            jnp.asarray(bf_ops.salt_to_words(params["salt"])),
            _n_rounds(params["cost"]))
        return bf_ops.words_to_digests(np.asarray(dw))

    def make_mask_worker(self, gen, targets, batch: int, hit_capacity: int,
                         oracle=None):
        batch = min(batch, DEFAULT_BATCH)
        use_cpu, _ = _route_bcrypt(oracle, batch)
        if use_cpu:
            return RoutedCpuBcryptWorker(oracle, gen, targets)
        return BcryptMaskWorker(self, gen, targets, batch=batch,
                                hit_capacity=hit_capacity, oracle=oracle)

    def make_wordlist_worker(self, gen, targets, batch: int,
                             hit_capacity: int, oracle=None):
        batch = min(batch, DEFAULT_BATCH)
        # route at the ACTUAL chunked-state batch (words x rules), not
        # the nominal one -- the advance the worker runs is built for
        # word_batch * n_rules rows
        state_batch = max(1, batch // gen.n_rules) * gen.n_rules
        use_cpu, _ = _route_bcrypt(oracle, state_batch)
        if use_cpu:
            return RoutedCpuBcryptWorker(oracle, gen, targets)
        return BcryptWordlistWorker(self, gen, targets, batch=batch,
                                    hit_capacity=hit_capacity, oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        return ShardedBcryptMaskWorker(
            self, gen, targets, mesh,
            batch_per_device=min(batch_per_device, DEFAULT_BATCH),
            hit_capacity=hit_capacity, oracle=oracle)

    def make_sharded_wordlist_worker(self, gen, targets, mesh,
                                     word_batch_per_device: int,
                                     hit_capacity: int, oracle=None):
        return ShardedBcryptWordlistWorker(
            self, gen, targets, mesh,
            word_batch_per_device=max(1, min(word_batch_per_device,
                                             DEFAULT_BATCH // gen.n_rules)),
            hit_capacity=hit_capacity, oracle=oracle)


_jit_bcrypt_batch = jax.jit(bf_ops.bcrypt_batch)

#: per-dispatch wall budget for the chunked cost loop.  A cost-12
#: batch is minutes of device time; bounded dispatches are what lets
#: the host report progress and renew its lease (on_chunk) while one
#: runs.  20 s is the default WorkUnit length (--unit-seconds) and
#: far under the 300 s lease timeout; it has not been tuned on the
#: chip.
DEFAULT_DISPATCH_S = envreg.get_float("DPRF_BCRYPT_DISPATCH_S")


class ChunkedEks:
    """Drives the EksBlowfish 2**cost main loop in budget-bounded
    dispatches, carrying the (P, S) state on device between them.

    The very first dispatch is a single untimed round that absorbs the
    advance fn's JIT compile; the next chunk is small (16 rounds) to
    calibrate seconds/round for the current (batch, impl); later
    chunks grow toward `dispatch_s`, capped at 8x per step so one
    optimistic estimate cannot jump straight past the budget.  Once
    calibrated, a total that fits one dispatch with headroom is issued
    sync-free so consecutive batches pipeline.
    State buffers are donated to the advance dispatch, so the 4 KB/lane
    S-boxes are updated in place rather than copied each chunk.
    """

    CALIBRATE_ROUNDS = 16
    GROWTH_CAP = 8

    def __init__(self, dispatch_s: float = None, advance=None):
        """`advance(P, S, key_words, salt18, n) -> (P, S)` defaults to
        the jitted single-chip eks_rounds; the sharded workers pass
        their shard_map'd equivalent."""
        self.dispatch_s = (DEFAULT_DISPATCH_S if dispatch_s is None
                           else dispatch_s)
        self._advance = (advance if advance is not None else
                         jax.jit(bf_ops.eks_rounds, donate_argnums=(0, 1)))
        self._per_round: Optional[float] = None   # EMA, seconds/round
        # Carried across run() calls: once calibrated, later batches
        # start at the budget-sized chunk instead of re-paying the
        # 8x ramp (a few synced dispatches per batch, thousands of
        # batches).
        self._last_chunk = self.CALIBRATE_ROUNDS

    def _next_chunk(self, remaining: int, last_chunk: int) -> int:
        if self._per_round is None:
            return min(remaining, self.CALIBRATE_ROUNDS)
        want = max(1, int(self.dispatch_s / self._per_round))
        return min(remaining, want, last_chunk * self.GROWTH_CAP)

    def run(self, P, S, key_words, salt18, total_rounds: int,
            on_chunk=None):
        """Advance (P, S) by `total_rounds`; returns the final state.
        `on_chunk(done, total)` is called after each dispatch (progress
        / lease-renewal hook)."""
        from dprf_tpu.utils.sync import hard_sync

        done = 0
        if self._per_round is None and done < total_rounds:
            # warm the advance fn's compile with a 1-round dispatch so
            # the first EMA sample doesn't fold seconds of JIT time
            # into seconds/round and starve the ramp (ADVICE r3)
            P, S = self._advance(P, S, key_words, salt18, jnp.int32(1))
            hard_sync(S)
            done += 1
            if on_chunk is not None:
                on_chunk(done, total_rounds)
        elif (self._per_round is not None
              and (total_rounds - done) * self._per_round
              <= 0.75 * self.dispatch_s):
            # the whole remaining chain fits one calibrated dispatch
            # with budget headroom: issue it WITHOUT a host sync so
            # batch N+1's begin/cost-loop can overlap batch N's finish
            # (the worker's hit readback is the natural per-batch sync
            # point).  No EMA update -- nothing was measured.
            P, S = self._advance(P, S, key_words, salt18,
                                 jnp.int32(total_rounds - done))
            if on_chunk is not None:
                on_chunk(total_rounds, total_rounds)
            return P, S
        while done < total_rounds:
            chunk = self._next_chunk(total_rounds - done,
                                     self._last_chunk)
            t0 = time.perf_counter()
            P, S = self._advance(P, S, key_words, salt18,
                                 jnp.int32(chunk))
            # a true fence: the EMA must be calibrated on execution
            # time, never on enqueue time
            hard_sync(S)
            dt = time.perf_counter() - t0
            per = dt / chunk
            self._per_round = (per if self._per_round is None
                               else 0.5 * self._per_round + 0.5 * per)
            done += chunk
            # remaining-clamped tails must not shrink the carried ramp
            self._last_chunk = max(self._last_chunk, chunk)
            if on_chunk is not None:
                on_chunk(done, total_rounds)
        return P, S


def make_bcrypt_mask_chunk_fns(gen, batch: int, hit_capacity: int = 64):
    """Chunked-variant device functions for the mask sweep:

    begin(base_digits, salt_words) -> (key_words, P, S)
    finish(P, S, n_valid, target) -> (count, lanes, _)

    The cost loop between them runs through ChunkedEks.run, so no
    single dispatch carries the whole 2**cost chain."""
    flat = gen.flat_charsets
    length = gen.length

    @jax.jit
    def begin(base_digits, salt_words):
        cand = gen.decode_batch(base_digits, flat, batch)
        lens = jnp.full((batch,), length, jnp.int32)
        kw = bf_ops.key_words_from_candidates(cand, lens)
        P, S = bf_ops.eks_setup_begin(kw, salt_words)
        return kw, P, S

    @jax.jit
    def finish(P, S, n_valid, target):
        dwords = bf_ops.bcrypt_digest_words(P, S)
        found = bf_ops.compare_digest_words(dwords, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return begin, finish


def make_bcrypt_wordlist_chunk_fns(gen, word_batch: int,
                                   hit_capacity: int = 64):
    """Chunked-variant device functions for the wordlist(+rules) sweep:

    begin(w0, n_valid_words, salt_words) -> (key_words, valid, P, S)
    finish(P, S, valid, target) -> (count, lanes, _)
    """
    B, L = word_batch, gen.max_len
    words_np, lens_np = gen.packed_words(pad_to=B,
                                         min_size=gen.n_words + B - 1)
    words_dev = jnp.asarray(words_np)
    lens_dev = jnp.asarray(lens_np)
    rules = gen.rules

    @jax.jit
    def begin(w0, n_valid_words, salt_words):
        wslice = lax.dynamic_slice(words_dev, (w0, 0), (B, L))
        lslice = lax.dynamic_slice(lens_dev, (w0,), (B,))
        base_valid = jnp.arange(B, dtype=jnp.int32) < n_valid_words
        cw, cl, cv = expand_rules(rules, wslice, lslice, base_valid, L)
        kw = bf_ops.key_words_from_candidates(cw, cl)
        P, S = bf_ops.eks_setup_begin(kw, salt_words)
        return kw, cv, P, S

    @jax.jit
    def finish(P, S, valid, target):
        dwords = bf_ops.bcrypt_digest_words(P, S)
        found = bf_ops.compare_digest_words(dwords, target) & valid
        n = valid.shape[0]
        return cmp_ops.compact_hits(found, jnp.zeros((n,), jnp.int32),
                                    hit_capacity)

    return begin, finish


def _n_rounds(cost: int) -> jnp.ndarray:
    """2**cost as the device loop trip count.  Cost 31 (valid in the
    bcrypt format, ~2e9 rounds) would overflow the int32 loop bound --
    reject it with a pointer to the CPU path rather than wrapping to a
    zero-iteration loop that yields silent false negatives."""
    if not 4 <= cost <= 30:
        raise ValueError(
            f"bcrypt cost {cost} outside the device engine's range 4..30 "
            "(2**31 rounds exceeds the int32 loop bound; use --device=cpu)")
    return jnp.int32(1 << cost)


def _target_args(target: Target):
    """Target -> (salt_words, n_rounds, target_words) device args."""
    return (jnp.asarray(bf_ops.salt_to_words(target.params["salt"])),
            _n_rounds(target.params["cost"]),
            jnp.asarray(bf_ops.digest_to_words(target.digest)))


def make_bcrypt_mask_step(gen, batch: int, hit_capacity: int = 64):
    """step(base_digits int32[L], n_valid, salt_words uint32[4],
    n_rounds int32, target uint32[6]) -> (count, lanes, _)."""
    flat = gen.flat_charsets
    length = gen.length

    @jax.jit
    def step(base_digits, n_valid, salt_words, n_rounds, target):
        cand = gen.decode_batch(base_digits, flat, batch)
        lens = jnp.full((batch,), length, jnp.int32)
        dwords = bf_ops.bcrypt_batch(cand, lens, salt_words, n_rounds)
        found = bf_ops.compare_digest_words(dwords, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step


def _make_sharded_eks_advance(mesh):
    """Shard_map'd ChunkedEks advance: each chip advances its own lane
    slice of the (key_words, P, S) state; no collectives -- the chains
    are per-lane serial.  State stays sharded on device between
    dispatches."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dprf_tpu.parallel.mesh import SHARD_AXIS

    sharded = shard_map(
        bf_ops.eks_rounds, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)), check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1))


def make_sharded_bcrypt_mask_chunk_fns(gen, mesh, batch_per_device: int,
                                       hit_capacity: int = 64):
    """Multi-chip chunked bcrypt mask sweep (config 4 at pod scale):
    chip c owns lane slice [c*B, (c+1)*B) of the super-batch; the cost
    loop runs through ChunkedEks with the state sharded across chips,
    so no dispatch -- single- or multi-chip -- carries the whole
    2**cost chain.

    begin(base_digits, salt_words) -> (key_words, P, S)   [sharded]
    finish(P, S, n_valid, target) ->
        (total, counts[n_dev], lanes[n_dev, cap] super-batch-global, _)
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dprf_tpu.parallel.mesh import SHARD_AXIS

    flat = gen.flat_charsets
    length = gen.length
    B = batch_per_device

    def begin_fn(base_digits, salt_words):
        dev = lax.axis_index(SHARD_AXIS)
        offset = (dev * B).astype(jnp.int32)
        cand = gen.decode_batch(base_digits, flat, B, lane_offset=offset)
        lens = jnp.full((B,), length, jnp.int32)
        kw = bf_ops.key_words_from_candidates(cand, lens)
        Pst, Sst = bf_ops.eks_setup_begin(kw, salt_words)
        return kw, Pst, Sst

    begin = jax.jit(shard_map(
        begin_fn, mesh=mesh, in_specs=(P(), P()),
        out_specs=(P(SHARD_AXIS),) * 3, check_vma=False))

    def finish_fn(Pst, Sst, n_valid, target):
        dev = lax.axis_index(SHARD_AXIS)
        offset = (dev * B).astype(jnp.int32)
        dwords = bf_ops.bcrypt_digest_words(Pst, Sst)
        lane_global = offset + jnp.arange(B, dtype=jnp.int32)
        found = (bf_ops.compare_digest_words(dwords, target)
                 & (lane_global < n_valid))
        count, lanes, tpos = cmp_ops.compact_hits(
            found, jnp.zeros((B,), jnp.int32), hit_capacity)
        lanes = jnp.where(lanes >= 0, lanes + offset, lanes)
        total = lax.psum(count, SHARD_AXIS)
        # replicated hit buffers (see parallel/sharded.py)
        return (total[None],
                lax.all_gather(count, SHARD_AXIS),
                lax.all_gather(lanes, SHARD_AXIS),
                lax.all_gather(tpos, SHARD_AXIS))

    finish_sm = shard_map(
        finish_fn, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P()),
        out_specs=(P(), P(), P(), P()), check_vma=False)

    @jax.jit
    def finish(Pst, Sst, n_valid, target):
        total, counts, lanes, tpos = finish_sm(Pst, Sst, n_valid, target)
        return total[0], counts, lanes, tpos

    begin.super_batch = mesh.devices.size * B
    return begin, finish


def make_sharded_bcrypt_wordlist_chunk_fns(gen, mesh, word_batch: int,
                                           hit_capacity: int = 64):
    """Multi-chip chunked bcrypt wordlist sweep: chip c expands+hashes
    words [w0 + c*B, w0 + (c+1)*B), cost loop chunked via ChunkedEks
    (state sharded).  Lanes come back as super-batch flat indices
    r*(n_dev*B) + global word lane (the same convention as
    ops/rules_pipeline.make_sharded_wordlist_crack_step).

    begin(w0, n_valid_words, salt_words) -> (key_words, valid, P, S)
    finish(P, S, valid, target) -> (total, counts, lanes, _)
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dprf_tpu.parallel.mesh import SHARD_AXIS

    n_dev = mesh.devices.size
    B, L = word_batch, gen.max_len
    words_np, lens_np = gen.packed_words(
        pad_to=n_dev * B, min_size=gen.n_words + n_dev * B - 1)
    words_dev = jnp.asarray(words_np)
    lens_dev = jnp.asarray(lens_np)
    rules = gen.rules

    def begin_fn(w0, n_valid_words, salt_words):
        dev = lax.axis_index(SHARD_AXIS)
        my_w0 = w0 + (dev * B).astype(jnp.int32)
        wslice = lax.dynamic_slice(words_dev, (my_w0, 0), (B, L))
        lslice = lax.dynamic_slice(lens_dev, (my_w0,), (B,))
        word_lane = (dev * B).astype(jnp.int32) + jnp.arange(
            B, dtype=jnp.int32)
        base_valid = word_lane < n_valid_words
        cw, cl, cv = expand_rules(rules, wslice, lslice, base_valid, L)
        kw = bf_ops.key_words_from_candidates(cw, cl)
        Pst, Sst = bf_ops.eks_setup_begin(kw, salt_words)
        return kw, cv, Pst, Sst

    begin = jax.jit(shard_map(
        begin_fn, mesh=mesh, in_specs=(P(), P(), P()),
        out_specs=(P(SHARD_AXIS),) * 4, check_vma=False))

    def finish_fn(Pst, Sst, valid, target):
        dev = lax.axis_index(SHARD_AXIS)
        dwords = bf_ops.bcrypt_digest_words(Pst, Sst)
        found = bf_ops.compare_digest_words(dwords, target) & valid
        n = valid.shape[0]
        count, lanes, tpos = cmp_ops.compact_hits(
            found, jnp.zeros((n,), jnp.int32), hit_capacity)
        r = lanes // B
        b = lanes % B
        glanes = r * (n_dev * B) + dev * B + b
        lanes = jnp.where(lanes >= 0, glanes, lanes)
        total = lax.psum(count, SHARD_AXIS)
        # replicated hit buffers (see parallel/sharded.py)
        return (total[None],
                lax.all_gather(count, SHARD_AXIS),
                lax.all_gather(lanes, SHARD_AXIS),
                lax.all_gather(tpos, SHARD_AXIS))

    finish_sm = shard_map(
        finish_fn, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(), P(), P(), P()), check_vma=False)

    @jax.jit
    def finish(Pst, Sst, valid, target):
        total, counts, lanes, tpos = finish_sm(Pst, Sst, valid, target)
        return total[0], counts, lanes, tpos

    begin.super_words = n_dev * B
    return begin, finish


class _BcryptWorkerBase:
    """Per-target keyspace sweep shared by the mask/wordlist workers."""

    def __init__(self, engine, gen, targets: Sequence[Target],
                 batch: int, hit_capacity: int, oracle):
        self.engine = engine
        self.gen = gen
        self.targets = list(targets)
        self.hit_capacity = hit_capacity
        self.oracle = oracle
        self.batch = batch
        self._targs = [_target_args(t) for t in self.targets]

    def _best_chunker(self, batch: int, dispatch_s) -> "ChunkedEks":
        """ChunkedEks over the best single-chip advance for `batch`
        lanes.  Publishes what that is (describe_worker): the compiled
        Pallas kernel sets ``advance_impl = "pallas"``, ``_interpret =
        False`` and its compile's cost and cache classification; the
        XLA form sets ``advance_impl = "xla"`` and nothing else."""
        from dprf_tpu.compilecache import compile_observer, observe_compile
        from dprf_tpu.ops.pallas_bcrypt import make_best_eks_advance

        with compile_observer(self.engine.name, publish=False) as obs:
            advance, self.advance_impl = make_best_eks_advance(batch)
        if self.advance_impl == "pallas":
            self._interpret = False
            self.compile_seconds, self.compile_cache = obs.seconds, obs.cache
            observe_compile(self.engine.name, obs.seconds, obs.cache)
        return ChunkedEks(dispatch_s, advance=advance)

    def _rescan(self, start: int, end: int, ti: int) -> list[Hit]:
        if self.oracle is None:
            raise RuntimeError(
                f"hit buffer overflow (> {self.hit_capacity}) and no "
                "oracle engine to rescan with; raise hit_capacity")
        sub = WorkUnit(-1, start, end - start)
        hits = CpuWorker(self.oracle, self.gen,
                         [self.targets[ti]]).process(sub)
        return [Hit(ti, h.cand_index, h.plaintext) for h in hits]


class BcryptMaskWorker(_BcryptWorkerBase):
    """Single-chip mask sweep, chunked: the cost loop of every batch is
    split over budget-bounded dispatches (ChunkedEks), so a cost-12
    batch does not ride in one dispatch that is minutes long and gives
    the host no point at which to report progress or renew a lease."""

    def __init__(self, engine, gen, targets, batch: int = DEFAULT_BATCH,
                 hit_capacity: int = 64, oracle=None,
                 dispatch_s: float = None):
        super().__init__(engine, gen, targets, batch, hit_capacity, oracle)
        self.stride = batch
        self.begin, self.finish = make_bcrypt_mask_chunk_fns(
            gen, batch, hit_capacity)
        self.chunker = self._best_chunker(batch, dispatch_s)

    def process(self, unit: WorkUnit) -> list[Hit]:
        hits: list[Hit] = []
        for ti in range(len(self.targets)):
            salt_w, n_rounds, tgt = self._targs[ti]
            salt18 = bf_ops.salt18_words(salt_w)
            total = int(n_rounds)
            for bstart in range(unit.start, unit.end, self.stride):
                n_valid = min(self.stride, unit.end - bstart)
                base = jnp.asarray(self.gen.digits(bstart), dtype=jnp.int32)
                kw, P, S = self.begin(base, salt_w)
                P, S = self.chunker.run(P, S, kw, salt18, total)
                count, lanes, _ = self.finish(P, S, jnp.int32(n_valid), tgt)
                count = int(count)
                if count == 0:
                    continue
                if count > self.hit_capacity:
                    hits.extend(self._rescan(
                        bstart, min(bstart + self.stride, unit.end), ti))
                    continue
                for lane in np.asarray(lanes):
                    if lane < 0:
                        continue
                    gidx = bstart + int(lane)
                    hits.append(Hit(ti, gidx, self.gen.candidate(gidx)))
        return hits
    # this sweep overlaps internally (queue-then-decode); an
    # inherited submit() would bypass the override
    process._serial_only = True


class ShardedBcryptMaskWorker(_BcryptWorkerBase):
    """Multi-chip bcrypt mask worker (keyspace DP over the mesh),
    chunked: the cost loop runs in budget-bounded dispatches with the
    EksBlowfish state sharded across chips (see BcryptMaskWorker)."""

    def __init__(self, engine, gen, targets, mesh,
                 batch_per_device: int = DEFAULT_BATCH,
                 hit_capacity: int = 64, oracle=None,
                 dispatch_s: float = None):
        super().__init__(engine, gen, targets,
                         mesh.devices.size * batch_per_device,
                         hit_capacity, oracle)
        self.mesh = mesh
        self.stride = self.batch          # one super-batch per sweep
        self.begin, self.finish = make_sharded_bcrypt_mask_chunk_fns(
            gen, mesh, batch_per_device, hit_capacity)
        self.chunker = ChunkedEks(dispatch_s,
                                  advance=_make_sharded_eks_advance(mesh))

    def process(self, unit: WorkUnit) -> list[Hit]:
        hits: list[Hit] = []
        for ti in range(len(self.targets)):
            salt_w, n_rounds, tgt = self._targs[ti]
            salt18 = bf_ops.salt18_words(salt_w)
            total_rounds = int(n_rounds)
            for bstart in range(unit.start, unit.end, self.stride):
                n_valid = min(self.stride, unit.end - bstart)
                base = jnp.asarray(self.gen.digits(bstart), dtype=jnp.int32)
                kw, P, S = self.begin(base, salt_w)
                P, S = self.chunker.run(P, S, kw, salt18, total_rounds)
                total, counts, lanes, _ = self.finish(
                    P, S, jnp.int32(n_valid), tgt)
                if int(total) == 0:
                    continue
                if (np.asarray(counts) > self.hit_capacity).any():
                    hits.extend(self._rescan(
                        bstart, min(bstart + self.stride, unit.end), ti))
                    continue
                for lane in np.asarray(lanes).ravel():
                    if lane < 0:
                        continue
                    gidx = bstart + int(lane)
                    hits.append(Hit(ti, gidx, self.gen.candidate(gidx)))
        return hits
    # this sweep overlaps internally (queue-then-decode); an
    # inherited submit() would bypass the override
    process._serial_only = True


class ShardedBcryptWordlistWorker(_BcryptWorkerBase):
    """Multi-chip bcrypt wordlist worker.  Super-batch lanes follow the
    sharded wordlist convention: lane = r * super_words + word lane."""

    def __init__(self, engine, gen, targets, mesh,
                 word_batch_per_device: int = 1 << 9,
                 hit_capacity: int = 64, oracle=None,
                 dispatch_s: float = None):
        super().__init__(engine, gen, targets,
                         mesh.devices.size * word_batch_per_device
                         * gen.n_rules, hit_capacity, oracle)
        self.mesh = mesh
        self.begin, self.finish = make_sharded_bcrypt_wordlist_chunk_fns(
            gen, mesh, word_batch_per_device, hit_capacity)
        self.chunker = ChunkedEks(dispatch_s,
                                  advance=_make_sharded_eks_advance(mesh))
        self.super_words = self.begin.super_words
        self.word_batch = self.super_words
        self.stride = self.super_words * gen.n_rules

    def process(self, unit: WorkUnit) -> list[Hit]:
        R = self.gen.n_rules
        w_start, w_end = word_cover_range(unit, R)
        hits: list[Hit] = []
        for ti in range(len(self.targets)):
            salt_w, n_rounds, tgt = self._targs[ti]
            salt18 = bf_ops.salt18_words(salt_w)
            total_rounds = int(n_rounds)
            for ws in range(w_start, w_end, self.super_words):
                nw = min(self.super_words, w_end - ws,
                         self.gen.n_words - ws)
                if nw <= 0:
                    break
                kw, cv, P, S = self.begin(jnp.int32(ws), jnp.int32(nw),
                                          salt_w)
                P, S = self.chunker.run(P, S, kw, salt18, total_rounds)
                total, counts, lanes, _ = self.finish(P, S, cv, tgt)
                if int(total) == 0:
                    continue
                if (np.asarray(counts) > self.hit_capacity).any():
                    start = max(unit.start, ws * R)
                    end = min(unit.end, (ws + nw) * R)
                    hits.extend(self._rescan(start, end, ti))
                    continue
                for lane in np.asarray(lanes).ravel():
                    if lane < 0:
                        continue
                    gidx = wordlist_lane_to_gidx(int(lane), ws,
                                                 self.super_words, R)
                    if not unit.start <= gidx < unit.end:
                        continue
                    hits.append(Hit(ti, gidx, self.gen.candidate(gidx)))
        return hits
    # this sweep overlaps internally (queue-then-decode); an
    # inherited submit() would bypass the override
    process._serial_only = True


class BcryptWordlistWorker(_BcryptWorkerBase):
    """Single-chip wordlist(+rules) sweep, chunked like the mask
    worker (see BcryptMaskWorker)."""

    def __init__(self, engine, gen, targets, batch: int = DEFAULT_BATCH,
                 hit_capacity: int = 64, oracle=None,
                 dispatch_s: float = None):
        super().__init__(engine, gen, targets, batch, hit_capacity, oracle)
        self.word_batch = max(1, batch // gen.n_rules)
        self.stride = self.word_batch * gen.n_rules
        self.begin, self.finish = make_bcrypt_wordlist_chunk_fns(
            gen, self.word_batch, hit_capacity)
        # the chunked state batch is rules x words (expand_rules rows)
        self.chunker = self._best_chunker(self.stride, dispatch_s)

    def process(self, unit: WorkUnit) -> list[Hit]:
        R = self.gen.n_rules
        w_start, w_end = word_cover_range(unit, R)
        hits: list[Hit] = []
        for ti in range(len(self.targets)):
            salt_w, n_rounds, tgt = self._targs[ti]
            salt18 = bf_ops.salt18_words(salt_w)
            total = int(n_rounds)
            for ws in range(w_start, w_end, self.word_batch):
                nw = min(self.word_batch, w_end - ws, self.gen.n_words - ws)
                if nw <= 0:
                    break
                kw, cv, P, S = self.begin(jnp.int32(ws), jnp.int32(nw),
                                          salt_w)
                P, S = self.chunker.run(P, S, kw, salt18, total)
                count, lanes, _ = self.finish(P, S, cv, tgt)
                count = int(count)
                if count == 0:
                    continue
                if count > self.hit_capacity:
                    start = max(unit.start, ws * R)
                    end = min(unit.end, (ws + nw) * R)
                    hits.extend(self._rescan(start, end, ti))
                    continue
                for lane in np.asarray(lanes):
                    if lane < 0:
                        continue
                    gidx = wordlist_lane_to_gidx(int(lane), ws,
                                                 self.word_batch, R)
                    if not unit.start <= gidx < unit.end:
                        continue
                    hits.append(Hit(ti, gidx, self.gen.candidate(gidx)))
        return hits
    # this sweep overlaps internally (queue-then-decode); an
    # inherited submit() would bypass the override
    process._serial_only = True
