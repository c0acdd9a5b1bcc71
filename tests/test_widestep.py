"""Wide-step dispatch (MaskWorkerBase.SUPER_MODE == "wide"): Pallas
workers fuse multi-batch WorkUnits by rebuilding their own step at
inner*stride lanes -- the same single-pallas_call program shape as a
plain batch, with a longer (sequential) grid -- instead of
scan-wrapping the step (ops/superstep.py).  It is the fused shape of
the rules kernel, the keccak kernel and the pallas_ext engines;
PallasMaskWorker's CORES engines default to "loop" and are pinned to
"wide" here so the shape stays covered on the md5 kernel.  These tests
pin: wide == per-batch bit-identical hits (single target, multi
target, wordlist+rules), window-sized overflow rescan, capacity
scaling, and that a wide program which fails to build raises.
"""

import hashlib

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from dprf_tpu import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.generators.wordlist import WordlistRulesGenerator
from dprf_tpu.ops.pallas_mask import TILE
from dprf_tpu.runtime.worker import PallasMaskWorker, PallasWordlistWorker
from dprf_tpu.runtime.workunit import WorkUnit
from dprf_tpu.rules.parser import parse_rule

pytestmark = pytest.mark.smoke


@pytest.fixture(scope="module")
def md5_jax():
    return get_engine("md5", device="jax")


def _hits(hits):
    return sorted((h.target_index, h.cand_index, h.plaintext)
                  for h in hits)


def _tgts(eng, plants):
    return [eng.parse_target(hashlib.md5(p).hexdigest()) for p in plants]


def _pallas_worker(eng, gen, targets, **kw):
    kw.setdefault("batch", TILE)
    kw.setdefault("oracle", get_engine("md5"))
    w = PallasMaskWorker(eng, gen, targets, interpret=True, **kw)
    w.SUPER_MODE = "wide"
    return w


@pytest.mark.parametrize("plant_idx", [8 * TILE - 1,   # last wide lane
                                       8 * TILE + 5])  # per-batch tail
def test_wide_single_matches_per_batch(md5_jax, monkeypatch, plant_idx):
    """12 strides: one wide chunk of 8 + per-batch tail of 4
    (SUPER_MIN = 8); hits at the wide/tail boundary must decode to the
    same global indices on both paths."""
    gen = MaskGenerator("?l?l?l?l")
    unit = WorkUnit(0, 0, 12 * TILE)
    plant = gen.candidate(plant_idx)
    w = _pallas_worker(md5_jax, gen, _tgts(md5_jax, [plant]))
    got = _hits(w.process(unit))
    assert got == [(0, plant_idx, plant)]
    assert any(k > TILE for k in getattr(w, "_wide_cache", {})), \
        "wide dispatch never engaged"
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = _pallas_worker(md5_jax, gen, _tgts(md5_jax, [plant]))
    assert got == _hits(w2.process(unit))
    assert not getattr(w2, "_wide_cache", {})


def test_wide_multi_target_matches_per_batch(md5_jax, monkeypatch):
    """Bloom multi-target kernel through the wide path: maybes verify
    against the oracle exactly as per-batch."""
    gen = MaskGenerator("?l?l?l?l")
    plants = [gen.candidate(3), gen.candidate(5 * TILE + 77),
              gen.candidate(9 * TILE + 1)]
    targets = _tgts(md5_jax, plants) + _tgts(md5_jax, [b"zzzz"])
    unit = WorkUnit(0, 0, 12 * TILE)
    w = _pallas_worker(md5_jax, gen, targets)
    got = _hits(w.process(unit))
    assert {h[2] for h in got} == set(plants)
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = _pallas_worker(md5_jax, gen, targets)
    assert got == _hits(w2.process(unit))


def test_wide_offset_unit(md5_jax):
    """Wide chunks of a unit not starting at 0 decode global indices
    from the chunk base, not the unit base."""
    gen = MaskGenerator("?l?l?l?l")
    start = 2 * TILE + 31
    unit = WorkUnit(1, start, 10 * TILE)
    plant_idx = start + 7 * TILE + 11
    plant = gen.candidate(plant_idx)
    w = _pallas_worker(md5_jax, gen, _tgts(md5_jax, [plant]))
    assert _hits(w.process(unit)) == [(0, plant_idx, plant)]


def test_wide_overflow_redrives_per_batch(md5_jax):
    """A wide result whose count exceeds its (scaled) buffer re-runs
    the window through the per-batch DEVICE step (collision sentinels
    fire on any two-hit tile, so wide overflow must not mean a
    whole-window host rescan) -- and still finds hits anywhere in the
    window."""
    gen = MaskGenerator("?l?l?l?l")
    plant_idx = 3 * TILE + 123           # beyond the first stride
    plant = gen.candidate(plant_idx)
    # no oracle: a host rescan would raise; the device redrive must not
    w = PallasMaskWorker(md5_jax, gen, _tgts(md5_jax, [plant]),
                         batch=TILE, oracle=None, interpret=True)
    unit = WorkUnit(0, 0, 8 * TILE)
    fake = (np.int32(9999), np.full((4,), -1, np.int32),
            np.zeros((4,), np.int32))
    hits = w._batch_hits(0, fake, unit, window=8 * TILE)
    assert _hits(hits) == [(0, plant_idx, plant)]


def test_wordlist_wide_overflow_redrives_per_batch():
    """Same for the rules kernel: an overflowed wide word window
    re-runs per word_batch on device, decoding with the per-batch
    lane stride."""
    from dprf_tpu.ops.pallas_rules import TILE_W

    eng = get_engine("md5", device="jax")
    words = [b"w%06d" % i for i in range(4 * TILE_W)]
    rules = [parse_rule(":"), parse_rule("u")]
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    wi = 2 * TILE_W + 17
    plant = words[wi].upper()
    targets = [get_engine("md5").parse_target(
        hashlib.md5(plant).hexdigest())]
    w = PallasWordlistWorker(eng, gen, targets,
                             batch=TILE_W * gen.n_rules,
                             oracle=None, interpret=True)
    unit = WorkUnit(0, 0, gen.keyspace)
    fake = (np.int32(9999), np.full((4,), -1, np.int32),
            np.zeros((4,), np.int32))
    hits = w._window_hits(0, 4 * TILE_W, fake, unit,
                          lane_wb=4 * TILE_W)
    assert _hits(hits) == [(0, wi * gen.n_rules + 1, plant)]


def test_wordlist_wide_shared_eviction():
    """Building a wide size whose window outgrows the shared arrays'
    padding rebuilds+replaces them and evicts cached steps holding
    the old copy (at most one wide wordlist copy in HBM)."""
    from dprf_tpu.ops.pallas_rules import TILE_W

    eng = get_engine("md5", device="jax")
    words = [b"q%06d" % i for i in range(8 * TILE_W)]
    rules = [parse_rule(":"), parse_rule("u")]
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    targets = [get_engine("md5").parse_target("ff" * 16)]
    w = PallasWordlistWorker(eng, gen, targets,
                             batch=TILE_W * gen.n_rules,
                             oracle=None, interpret=True)
    s1 = w._wide_step(2 * TILE_W)
    assert 2 * TILE_W in w._wide_cache
    s2 = w._wide_step(8 * TILE_W)    # outgrows s1's padding
    assert s2.words4 is not s1.words4
    assert 2 * TILE_W not in w._wide_cache, "stale copy not evicted"
    assert w._wide_cache[8 * TILE_W] is s2
    s3 = w._wide_step(4 * TILE_W)    # fits s2's padding: reuses
    assert s3.words4 is s2.words4


def test_wide_capacity_scales_with_inner(md5_jax):
    """hit_capacity=1 per batch would overflow on >1 hit per window;
    the wide step's scaled buffer holds one hit per stride without a
    rescan (no oracle provided -- a rescan would raise)."""
    gen = MaskGenerator("?l?l?l?l")
    plants = [gen.candidate(i * TILE + i) for i in range(4)]
    # single-target kernel: sweep one plant per worker, no oracle
    for i, p in enumerate(plants):
        w = PallasMaskWorker(md5_jax, gen, _tgts(md5_jax, [p]),
                             batch=TILE, hit_capacity=1, oracle=None,
                             interpret=True)
        got = _hits(w.process(WorkUnit(0, 0, 8 * TILE)))
        assert got == [(0, i * TILE + i, p)]


def test_wide_build_failure_raises(md5_jax):
    gen = MaskGenerator("?l?l?l?l")
    plant = gen.candidate(9 * TILE + 9)
    w = _pallas_worker(md5_jax, gen, _tgts(md5_jax, [plant]))

    def boom(batch):
        raise RuntimeError("no wide program on this backend")

    w._make_step = boom
    with pytest.raises(RuntimeError, match="no wide program"):
        w.process(WorkUnit(0, 0, 12 * TILE))
    # no degrade flag, and never another fused shape behind its back
    assert not hasattr(w, "_wide_disabled")
    assert not getattr(w, "_super_cache", None)
    assert not getattr(w, "_loop_cache", None)


@pytest.mark.compileheavy    # interpret-mode rules-kernel wide build
def test_wordlist_wide_matches_per_batch(monkeypatch):
    """PallasWordlistWorker wide dispatch: flat rule-major lanes are
    decoded with the WIDE word stride (lane = r * n_words + b), so a
    hit deep in the window must map to the right (word, rule)."""
    from dprf_tpu.ops.pallas_rules import TILE_W

    eng = get_engine("md5", device="jax")
    cpu = get_engine("md5")
    rng = np.random.default_rng(11)
    alpha = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
    words = [bytes(alpha[rng.integers(0, 26, 6)])
             for _ in range(8 * TILE_W)]
    rules = [parse_rule(":"), parse_rule("u")]
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    wi = 5 * TILE_W + 321
    plant = words[wi].upper()              # rule 1 on word wi
    targets = [cpu.parse_target(hashlib.md5(plant).hexdigest())]
    w = PallasWordlistWorker(eng, gen, targets,
                             batch=TILE_W * gen.n_rules,
                             oracle=cpu, interpret=True)
    unit = WorkUnit(0, 0, gen.keyspace)
    got = _hits(w.process(unit))
    assert got == [(0, wi * gen.n_rules + 1, plant)]
    assert any(k > TILE_W for k in getattr(w, "_wide_cache", {})), \
        "wordlist wide dispatch never engaged"
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = PallasWordlistWorker(eng, gen, targets,
                              batch=TILE_W * gen.n_rules,
                              oracle=cpu, interpret=True)
    assert got == _hits(w2.process(unit))
    # all wide sizes share ONE device copy of the packed wordlist
    # (built at the largest window; narrower windows reuse it)
    s_big = w._wide_cache[8 * TILE_W]
    s_small = w._make_step(4 * TILE_W)
    assert s_small.words4 is s_big.words4
    assert s_small.lens3 is s_big.lens3


def test_salted_wide_matches_per_batch(monkeypatch):
    """PallasSaltedMaskWorker fuses its per-target sweep into wide
    kernel dispatches; hits and indices must match the per-batch path
    and the wide kernels must actually be built."""
    from dprf_tpu.engines.device.salted import PallasSaltedMaskWorker

    monkeypatch.setenv("DPRF_PALLAS", "1")
    gen = MaskGenerator("?l?l?l?l")
    cpu = get_engine("md5-ps", device="cpu")
    dev = get_engine("md5-ps", device="jax")
    plants = [(8 * TILE - 1, b"na"), (9 * TILE + 5, b"clsalt")]
    targets = []
    for idx, salt in plants:
        d = cpu.hash_batch([gen.candidate(idx)],
                           params={"salt": salt})[0]
        targets.append(cpu.parse_target(d.hex() + ":" + salt.decode()))
    unit = WorkUnit(0, 0, 12 * TILE)
    w = dev.make_mask_worker(gen, targets, batch=TILE,
                             hit_capacity=8, oracle=cpu)
    assert isinstance(w, PallasSaltedMaskWorker)
    got = _hits(w.process(unit))
    assert {(t, i) for t, i, _ in got} == {(0, 8 * TILE - 1),
                                          (1, 9 * TILE + 5)}
    assert any(sb > TILE for _, sb in w._wide_ksteps), \
        "wide salted kernels never engaged"
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = dev.make_mask_worker(gen, targets, batch=TILE,
                              hit_capacity=8, oracle=cpu)
    assert got == _hits(w2.process(unit))
    assert not w2._wide_ksteps
