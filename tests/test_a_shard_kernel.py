"""Kernel shard-compute coverage: the fused Pallas kernel as the
sharded superstep's per-shard compute (parallel/sharded.
make_sharded_kernel_mask_step), the single-chip loop superstep
(PallasMaskWorker SUPER_MODE="loop"), the eager kernel emulator vs the
pallas_call interpret path, probe tables on wordlist / combinator
workers, and the knob-sweep tune surface (sweep_values +
lookup_tuned_value / record_tuned_value).

Everything runs md5 in interpret mode on the conftest's 8 virtual CPU
devices (chip numbers belong to PERF.md and the ledger); parity
is always against the CpuWorker oracle, exact hit sets, so the kernel
path's sentinel/overflow disciplines are exercised end to end.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# kernel-pipeline compiles: full suite / tier-1, excluded from the
# <5-min smoke tier (tools/check_markers.py enforces a tier decision)
pytestmark = pytest.mark.compileheavy

from dprf_tpu.engines import get_engine
from dprf_tpu.engines.base import Target
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.parallel import make_mesh
from dprf_tpu.parallel.worker import ShardedMaskWorker
from dprf_tpu.runtime.worker import CpuWorker, PallasMaskWorker
from dprf_tpu.runtime.workunit import WorkUnit

SUB = 32          # conftest pins DPRF_PALLAS_SUB=32; passed explicitly
TILE = SUB * 128  # so these shapes hold even without the env knob


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest should fake 8 CPU devices"
    return make_mesh(8)


def _md5_targets(gen, idxs):
    return [Target(str(i), hashlib.md5(gen.candidate(i)).digest())
            for i in idxs]


def _cpu_hits(gen, targets, unit):
    return sorted((h.target_index, h.cand_index, h.plaintext)
                  for h in CpuWorker(get_engine("md5", device="cpu"),
                                     gen, targets).process(unit))


def _process_noted(worker, unit):
    """(hits, coverage notes) of one unit through the worker."""
    from dprf_tpu.telemetry import coverage
    notes = []
    coverage.install_collector(
        lambda name, start, end, attrs: notes.append((name, start, end)))
    try:
        hits = worker.process(unit)
    finally:
        coverage.install_collector(None)
    return hits, notes


# ---------------------------------------------------------------------------
# sharded kernel compute: make_sharded_kernel_mask_step through
# ShardedMaskWorker(kernel={...})


def test_sharded_kernel_single_target_parity(mesh):
    """Single-target kernel shard compute: exact in-kernel compare, no
    probe, no oracle -- a plant at the LAST keyspace index must survive
    the window n_valid masking of the final partial stride."""
    gen = MaskGenerator("?d?d?d?d?d")       # 100000
    targets = _md5_targets(gen, [gen.keyspace - 1])
    w = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                          mesh, batch_per_device=TILE, hit_capacity=16,
                          kernel={"interpret": True, "sub": SUB})
    assert "+kernel" in w.ATTACK
    unit = WorkUnit(0, 0, gen.keyspace)
    got = sorted((h.target_index, h.cand_index, h.plaintext)
                 for h in w.process(unit))
    assert got == _cpu_hits(gen, targets, unit)
    assert got[0][1] == gen.keyspace - 1


def test_sharded_kernel_multi_probe_boundaries(mesh):
    """Multi-target kernel shard compute: plants at shard edges, the
    superstep window edge, and the last index.  Kernel hits come back
    as SENTINEL-tagged blocked-probe survivors; the worker must
    resolve each with one oracle hash and match the CPU oracle
    exactly (no false positive may surface, no real hit may drop)."""
    gen = MaskGenerator("?d?d?d?d?d")       # 100000
    B = 8 * 128                 # sub=8 tile: 12 strides of 8192, so
    stride = 8 * B              # the superstep (SUPER_MIN=8) engages
    plant = [0, B - 1, B, stride - 1, stride,           # shard edges
             2 * stride - 1, 2 * stride,                # window edge
             gen.keyspace - 1]                          # last index
    targets = _md5_targets(gen, plant)
    w = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                          mesh, batch_per_device=B, hit_capacity=16,
                          oracle=get_engine("md5", device="cpu"),
                          kernel={"interpret": True, "sub": 8})
    assert "+kernel" in w.ATTACK
    pend = w.submit(WorkUnit(0, 0, gen.keyspace))
    kinds = [k for k, _, _ in pend.queued]
    assert "sshard" in kinds       # fused windows actually dispatched
    got = sorted((h.target_index, h.cand_index, h.plaintext)
                 for h in pend.resolve())
    assert got == _cpu_hits(gen, targets,
                            WorkUnit(0, 0, gen.keyspace))
    assert [g[1] for g in got] == plant


def test_sharded_kernel_multi_collided_tiles_rescan_one_tile(mesh):
    """Two (and three) probe survivors INSIDE one tile: the tile can
    report one lane only, so it comes back tagged as collided and the
    worker re-sweeps exactly that tile (on the device since PR 27:
    tests/test_tile_reprobe.py) -- in a fused window and in the
    per-batch tail, with a tile cut by the unit's end.  Every plant
    once, no window redrive, no stride-wide host rescan."""
    gen = MaskGenerator("?d?d?d?d?d")       # 100000
    B = 8 * 128
    stride = 8 * B
    plant = [5, 6,                          # one tile, fused window
             3 * B + 10, 3 * B + 500, 3 * B + 1000,    # three in one
             9 * stride + 7,                # a lone single beside them
             gen.keyspace - 3, gen.keyspace - 1]       # cut last tile
    targets = _md5_targets(gen, plant)
    w = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                          mesh, batch_per_device=B, hit_capacity=16,
                          oracle=get_engine("md5", device="cpu"),
                          kernel={"interpret": True, "sub": 8})
    unit = WorkUnit(0, 0, gen.keyspace)
    hits, notes = _process_noted(w, unit)
    got = sorted((h.target_index, h.cand_index, h.plaintext)
                 for h in hits)
    assert got == _cpu_hits(gen, targets, unit)
    rescans = sorted(n[1:] for n in notes if n[0] == "rescan")
    last_tile = (gen.keyspace - 1) // B * B
    assert rescans == [(0, B), (3 * B, 4 * B),
                       (last_tile, gen.keyspace)]
    assert not [n for n in notes if n[0] == "redrive"]


def test_sharded_kernel_overflow_redrives_exactly(monkeypatch):
    """More survivors in one shard's window than the WINDOW's buffer
    holds (hit_capacity x inner slots): the buffer truncates but the
    count stays over it, and the worker must redrive that window and
    report every hit exactly once."""
    monkeypatch.setenv("DPRF_SHARD_SUPER_CAP", "8")
    gen = MaskGenerator("?d?d?d?d?d")       # 100000
    B = 4 * 8 * 128             # four sub=8 tiles a shard and stride
    stride = 2 * B              # 12 strides: one window of 8, a tail
    # shard 0's slices of the window: a plant in three tiles of six
    # strides, 18 survivors into 2 x 8 = 16 slots
    plant = sorted([i * stride + t * 1024 + 11 * i + t
                    for i in range(6) for t in range(3)]
                   + [gen.keyspace - 1])
    targets = _md5_targets(gen, plant)
    w = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                          make_mesh(2), batch_per_device=B,
                          hit_capacity=2,
                          oracle=get_engine("md5", device="cpu"),
                          kernel={"interpret": True, "sub": 8})
    hits, notes = _process_noted(w, WorkUnit(0, 0, gen.keyspace))
    assert sorted(h.cand_index for h in hits) == plant
    assert len(hits) == len(set(h.cand_index for h in hits))
    assert ("window", 0, 8 * stride) in notes
    assert [n[1:] for n in notes if n[0] == "redrive"] \
        == [(0, 8 * stride)]


def test_sharded_kernel_resume_resplit(mesh):
    """A sweep interrupted mid-keyspace resumes under a DIFFERENT
    shard count (mesh of 4): the kernel compute decodes from base +
    offset, so the union of the two partial sweeps must equal one
    full-oracle sweep."""
    gen = MaskGenerator("?d?d?d?d?d")       # 100000
    cut = 8 * TILE + 517            # mid-stride, mid-batch
    plant = [0, cut - 1, cut, cut + 1, gen.keyspace - 1]
    targets = _md5_targets(gen, plant)
    oracle = get_engine("md5", device="cpu")
    w8 = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                           mesh, batch_per_device=TILE, hit_capacity=16,
                           oracle=oracle,
                           kernel={"interpret": True, "sub": SUB})
    first = w8.process(WorkUnit(0, 0, cut))
    w4 = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                           make_mesh(4), batch_per_device=TILE,
                           hit_capacity=16, oracle=oracle,
                           kernel={"interpret": True, "sub": SUB})
    rest = w4.process(WorkUnit(0, cut, gen.keyspace - cut))
    got = sorted(h.cand_index for h in first + rest)
    assert got == plant


# ---------------------------------------------------------------------------
# eager kernel emulator vs the pallas_call interpret path


def test_emulate_matches_pallas_call_offset():
    """emulate_mask_kernel runs the kernel body eagerly; its output
    must match make_mask_pallas_fn(interpret=True) bit for bit,
    including the traced window-offset argument the sharded / loop
    supersteps rely on."""
    from dprf_tpu.ops import pallas_mask

    gen = MaskGenerator("?l?l?l")           # 17576
    batch, offset, n_valid = 2 * TILE, TILE, TILE + 321
    idx = offset + 100      # valid iff offset + lane < WINDOW n_valid
    tw = np.frombuffer(hashlib.md5(gen.candidate(idx)).digest(),
                       dtype="<u4").astype(np.uint32)
    ec, el = pallas_mask.emulate_mask_kernel(
        "md5", gen, tw, batch, gen.digits(0), n_valid, sub=SUB,
        offset=offset)
    fn = pallas_mask.make_mask_pallas_fn(
        "md5", gen, tw, batch, sub=SUB, interpret=True,
        with_offset=True)
    pc, pl = fn(jnp.asarray(gen.digits(0), jnp.int32),
                jnp.full((1,), n_valid, jnp.int32),
                jnp.full((1,), offset, jnp.int32))
    np.testing.assert_array_equal(ec, np.asarray(pc))
    np.testing.assert_array_equal(el, np.asarray(pl))
    assert int(ec.sum()) == 1               # exactly the planted hit


def test_emulate_matches_pallas_call_probe():
    """Multi-target blocked-probe compare: emulator and pallas_call
    agree on maybe-counts and lanes, and every planted target is a
    survivor (real hits can never be filtered)."""
    from dprf_tpu.ops import pallas_mask

    gen = MaskGenerator("?l?l?l")
    batch, n_valid = 2 * TILE, 2 * TILE
    plant = [0, 77, TILE - 1, TILE, batch - 1]
    tw = np.stack([np.frombuffer(hashlib.md5(gen.candidate(i)).digest(),
                                 dtype="<u4").astype(np.uint32)
                   for i in plant])
    ec, el = pallas_mask.emulate_mask_kernel(
        "md5", gen, tw, batch, gen.digits(0), n_valid, sub=SUB,
        probe_fp=1e-4)
    fn = pallas_mask.make_mask_pallas_fn(
        "md5", gen, tw, batch, sub=SUB, interpret=True,
        with_offset=True, probe_fp=1e-4)
    pc, pl = fn(jnp.asarray(gen.digits(0), jnp.int32),
                jnp.full((1,), n_valid, jnp.int32),
                jnp.full((1,), 0, jnp.int32))
    np.testing.assert_array_equal(ec, np.asarray(pc))
    np.testing.assert_array_equal(el, np.asarray(pl))
    assert int(ec.sum()) >= len(plant)      # probes may add FPs, never drop


# ---------------------------------------------------------------------------
# single-chip loop superstep (PallasMaskWorker SUPER_MODE="loop")


def test_loop_superstep_single_target_parity():
    """The loop superstep fuses `inner` kernel batches per dispatch;
    hits at batch boundaries inside the window, the window's last
    index, and the keyspace's last index (the per-batch remainder)
    must decode to the same global indices as the per-batch path."""
    gen = MaskGenerator("?d?d?d?d")     # 10000 over a sub=8 tile of
    b = 8 * 128                         # 1024: 9 strides, so the loop
    plant = [0, b, 8 * b - 1,           # (SUPER_MIN=8) engages
             gen.keyspace - 1]
    eng = get_engine("md5", device="jax")
    got = []
    for i in plant:
        targets = _md5_targets(gen, [i])
        w = PallasMaskWorker(eng, gen, targets, batch=b,
                             hit_capacity=16, interpret=True, sub=8)
        assert w.SUPER_MODE == "loop"
        # the fusion window really opens for this keyspace/stride
        assert w._super_inner(gen.keyspace // w.stride) >= 2
        hits = w.process(WorkUnit(0, 0, gen.keyspace))
        got.append(sorted(h.cand_index for h in hits))
    assert got == [[i] for i in plant]


def test_loop_superstep_multi_matches_perbatch():
    """Multi-target loop supersteps (Bloom maybes + collided-tile
    rescan buffers accumulated across the window) against the CPU
    oracle, with two targets INSIDE one tile to force the collided
    path through the window accumulation."""
    gen = MaskGenerator("?d?d?d?d")         # 10000, sub=8 tile
    b = 8 * 128
    plant = [10, 11, b + 5, 2 * b - 1, gen.keyspace - 1]
    targets = _md5_targets(gen, plant)
    w = PallasMaskWorker(get_engine("md5", device="jax"), gen, targets,
                         batch=b, hit_capacity=16,
                         oracle=get_engine("md5", device="cpu"),
                         interpret=True, sub=8)
    assert w._super_inner(gen.keyspace // w.stride) >= 2
    unit = WorkUnit(0, 0, gen.keyspace)
    got = sorted((h.target_index, h.cand_index, h.plaintext)
                 for h in w.process(unit))
    assert got == _cpu_hits(gen, targets, unit)


# ---------------------------------------------------------------------------
# probe tables on the wordlist / combinator families


@pytest.fixture()
def low_probe_floor(monkeypatch):
    monkeypatch.setenv("DPRF_TARGETS_PROBE_MIN", "4")


def _full_sweep(worker, keyspace, unit=8192):
    hits = []
    for s in range(0, keyspace, unit):
        hits.extend(worker.process(WorkUnit(-1, s, min(unit,
                                                       keyspace - s))))
    return sorted((h.target_index, h.cand_index) for h in hits)


@pytest.fixture(scope="module")
def word_case():
    """(gen, targets, oracle, expected hits) -- the CPU oracle sweep
    runs once for both the device and the sharded parity test."""
    from dprf_tpu.bench import _synthetic_words
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.rules.parser import load_rules
    gen = WordlistRulesGenerator(_synthetic_words(256),
                                 load_rules("best64"), max_len=24)
    K = gen.keyspace
    idxs = sorted({0, 7, gen.n_rules + 3, K // 3, K // 2 + 1,
                   K - gen.n_rules, K - 1})
    oracle = get_engine("md5", device="cpu")
    raws = sorted(set(oracle.hash_batch([gen.candidate(i)
                                         for i in idxs])))
    targets = [oracle.parse_target(d.hex()) for d in raws]
    want = _full_sweep(CpuWorker(oracle, gen, targets), K)
    return gen, targets, oracle, want


@pytest.fixture(scope="module")
def combi_case():
    from dprf_tpu.bench import _synthetic_words
    from dprf_tpu.generators.combinator import CombinatorGenerator
    gen = CombinatorGenerator(_synthetic_words(128),
                              _synthetic_words(128), max_len=24)
    K = gen.keyspace
    idxs = sorted({0, 5, K // 4, K // 2, K - 1, 999})
    oracle = get_engine("md5", device="cpu")
    raws = sorted(set(oracle.hash_batch([gen.candidate(i)
                                         for i in idxs])))
    targets = [oracle.parse_target(d.hex()) for d in raws]
    want = _full_sweep(CpuWorker(oracle, gen, targets), K)
    return gen, targets, oracle, want


def test_wordlist_probe_parity(low_probe_floor, word_case):
    from dprf_tpu.runtime.worker import DeviceWordlistWorker
    gen, targets, oracle, want = word_case
    w = DeviceWordlistWorker(get_engine("md5", device="jax"), gen,
                             targets, batch=4096, oracle=oracle)
    assert "+probe" in w.ATTACK
    assert _full_sweep(w, gen.keyspace) == want


def test_combinator_probe_parity(low_probe_floor, combi_case):
    from dprf_tpu.runtime.worker import DeviceCombinatorWorker
    gen, targets, oracle, want = combi_case
    w = DeviceCombinatorWorker(get_engine("md5", device="jax"), gen,
                               targets, batch=4096, oracle=oracle)
    assert "+probe" in w.ATTACK
    assert _full_sweep(w, gen.keyspace) == want


def test_sharded_wordlist_probe_parity(mesh, low_probe_floor,
                                       word_case):
    from dprf_tpu.parallel.worker import ShardedWordlistWorker
    gen, targets, oracle, want = word_case
    w = ShardedWordlistWorker(get_engine("md5", device="jax"), gen,
                              targets, mesh, word_batch_per_device=32,
                              oracle=oracle)
    assert "+probe" in w.ATTACK
    assert _full_sweep(w, gen.keyspace) == want


def test_sharded_combinator_probe_parity(mesh, low_probe_floor,
                                         combi_case):
    from dprf_tpu.parallel.worker import ShardedCombinatorWorker
    gen, targets, oracle, want = combi_case
    w = ShardedCombinatorWorker(get_engine("md5", device="jax"), gen,
                                targets, mesh, batch_per_device=512,
                                oracle=oracle)
    assert "+probe" in w.ATTACK
    assert _full_sweep(w, gen.keyspace) == want


# ---------------------------------------------------------------------------
# knob-sweep tune surface


class _FakeWorker:
    """Deterministic worker for sweep_values: advances an injected
    clock by unit_len / speed per process() call."""

    stride = 64

    def __init__(self, speed, clock_cell, seen_units):
        self.speed = speed
        self._clock = clock_cell
        self._seen = seen_units

    def process(self, unit):
        self._seen.append(unit.length)
        self._clock[0] += unit.length / self.speed
        return []


def test_sweep_values_picks_fastest_and_skips_failures():
    from dprf_tpu.tune import sweep_values

    t = [0.0]
    seen = []
    speeds = {2: 100.0, 4: 500.0, 8: None}   # 8 fails to build

    def mk(v):
        if speeds[v] is None:
            raise RuntimeError("no such tile")
        return _FakeWorker(speeds[v], t, seen)

    res = sweep_values(mk, [2, 8, 4], keyspace=1 << 20,
                       probe_seconds=0.5, unit_strides=16,
                       clock=lambda: t[0], label="inner")
    assert res.batch == 4                    # the fastest value wins
    assert res.rate_hs == pytest.approx(500.0, rel=0.05)
    errs = [p for p in res.swept if p.error]
    assert [p.batch for p in errs] == [8]    # failure recorded, skipped
    # unit_strides actually sized the probe units (fusion engages)
    assert max(seen) == _FakeWorker.stride * 16


def test_sweep_values_all_fail_raises():
    from dprf_tpu.tune import sweep_values

    def mk(v):
        raise RuntimeError("nope")

    with pytest.raises(ValueError, match="every rung"):
        sweep_values(mk, [1, 2], keyspace=1024,
                     clock=lambda: 0.0)


def test_tuned_value_cache_roundtrip():
    from dprf_tpu.tune import (TuneResult, lookup_tuned_value,
                               record_tuned_value)

    res = TuneResult(32, 1.5e6, 0.25, [], source="swept")
    record_tuned_value("md5", "inner", "mask", "jax", res,
                       extras={"hit_cap": 64})
    assert lookup_tuned_value("md5", "inner", attack="mask",
                              device="jax",
                              extras={"hit_cap": 64}) == 32
    # the knob forks the key: neither another knob nor the plain
    # batch lookup may alias it
    assert lookup_tuned_value("md5", "sub", attack="mask",
                              device="jax",
                              extras={"hit_cap": 64}) is None
    assert lookup_tuned_value("md5", "inner", attack="mask",
                              device="jax",
                              extras={"hit_cap": 128}) is None
