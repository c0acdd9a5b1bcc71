"""Super-step dispatch: fused many-batch device dispatches must be
bit-identical to the per-batch path (hits, overflow semantics, unit
boundaries), and the pipelined Coordinator must behave like the serial
one.

SURVEY.md section 3: the hot loop's host<->device link cost is part of
the production path; these tests pin the correctness of the machinery
that amortizes it (ops/superstep.py + worker submit/resolve +
Coordinator depth-2 pipelining).
"""

import hashlib

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from dprf_tpu import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.generators.wordlist import WordlistRulesGenerator
from dprf_tpu.ops.superstep import (make_super_step, max_inner,
                                    window_capacity)
from dprf_tpu.runtime.worker import (DeviceMaskWorker,
                                     DeviceWordlistWorker,
                                     submit_or_process)
from dprf_tpu.runtime.workunit import WorkUnit

pytestmark = pytest.mark.smoke


def _hits_tuple(hits):
    return sorted((h.target_index, h.cand_index, h.plaintext)
                  for h in hits)


def _md5_targets(eng, plants):
    return [eng.parse_target(hashlib.md5(p).hexdigest()) for p in plants]


# -- factory ----------------------------------------------------------------

def test_max_inner_int32_budget():
    assert max_inner(1 << 22, 512) == 256       # 512 * 4M > 2^31
    assert max_inner(1 << 18, 512) == 512
    assert max_inner(1 << 31, 512) == 0


@pytest.mark.parametrize("hit_capacity, scale, width", [
    (64, 1, 64), (64, 2, 128), (64, 16, 1024), (64, 256, 1024),
    (2, 1, 2), (2, 16, 32), (2, 256, 512),
    # a raised --hit-cap reaches every program unclamped
    (2048, 1, 2048), (2048, 16, 2048), (2048, 256, 2048),
    # a window narrower than one batch keeps the batch's buffer
    (64, 0, 64),
])
def test_window_capacity_is_what_the_wide_and_loop_sites_computed(
        hit_capacity, scale, width):
    """The one width policy: per-candidate capacity matches the
    per-batch step's up to 1,024 slots, never under the nominal
    capacity (the expression the wide words, wide mask and loop
    programs each carried until it moved here)."""
    assert window_capacity(hit_capacity, scale) == width
    assert width == max(hit_capacity,
                        min(hit_capacity * max(1, scale), 1024))


def test_super_step_stacks_and_clips():
    """A fake step records its (x, nv) arguments via its outputs; the
    wrapper must slice xs per iteration, clip n_valid exactly, and sum
    the flag function over iterations."""
    batch = 10

    def step(x, nv):
        return jnp.asarray(nv), x * 2, jnp.stack([x[0], nv])

    ss = make_super_step(step, inner=4, batch=batch)
    xs = jnp.arange(8, dtype=jnp.int32).reshape(4, 2)
    flag, (nvs, x2, pairs) = ss(xs, jnp.int32(25))
    # nv per iteration: 10, 10, 5, 0 -- flag (default out[0]) sums them
    assert int(flag) == 25
    assert [int(v) for v in np.asarray(nvs)] == [10, 10, 5, 0]
    assert np.asarray(x2).tolist() == (np.arange(8).reshape(4, 2) * 2).tolist()
    assert np.asarray(pairs)[:, 0].tolist() == [0, 2, 4, 6]


def test_super_step_custom_flag():
    def step(x, nv):
        return jnp.int32(0), jnp.asarray(nv)

    ss = make_super_step(step, inner=3, batch=5,
                         flag_fn=lambda out: out[1])
    flag, _ = ss(jnp.zeros((3, 1), jnp.int32), jnp.int32(12))
    assert int(flag) == 12


def test_super_step_rejects_int32_overflow():
    with pytest.raises(ValueError):
        make_super_step(lambda x, nv: (nv,), inner=512, batch=1 << 22)


# -- mask workers -----------------------------------------------------------

@pytest.fixture
def md5_jax():
    return get_engine("md5", device="jax")


def _mask_worker(eng, gen, targets, batch, **kw):
    return DeviceMaskWorker(eng, gen, targets,
                            oracle=get_engine("md5"), batch=batch, **kw)


def test_mask_super_matches_per_batch(md5_jax, monkeypatch):
    """Plants inside super chunks, in the per-batch tail, and across
    chunk boundaries must decode to identical hits either way."""
    gen = MaskGenerator("?l?l?l?l")          # keyspace 456976
    batch = 1 << 12
    # 40 strides: super chunks 32 + per-batch tail 8 (SUPER_MIN=8)
    unit = WorkUnit(0, 0, 40 * batch)
    plants = [b"aaaa",                       # index 0
              gen.candidate(32 * batch - 1),  # last lane of chunk
              gen.candidate(32 * batch),      # first tail batch lane
              gen.candidate(40 * batch - 1)]  # very last unit lane
    targets = _md5_targets(md5_jax, plants)
    w_super = _mask_worker(md5_jax, gen, targets, batch)
    got = _hits_tuple(w_super.process(unit))
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w_plain = _mask_worker(md5_jax, gen, targets, batch)
    assert got == _hits_tuple(w_plain.process(unit))
    assert {h[2] for h in got} == set(plants)


def test_mask_super_partial_tail(md5_jax):
    """Unit end mid-batch after super chunks: n_valid masking must
    exclude out-of-unit candidates."""
    gen = MaskGenerator("?l?l?l?l")
    batch = 1 << 12
    end = 8 * batch + 100
    inside = gen.candidate(end - 1)
    outside = gen.candidate(end)             # 1 past the unit
    targets = _md5_targets(md5_jax, [inside, outside])
    w = _mask_worker(md5_jax, gen, targets, batch)
    hits = w.process(WorkUnit(0, 0, end))
    assert _hits_tuple(hits) == [(0, end - 1, inside)]


def test_mask_super_offset_unit(md5_jax, monkeypatch):
    """Units not starting at 0 decode global indices correctly."""
    gen = MaskGenerator("?l?l?l?l")
    batch = 1 << 12
    start = 13 * batch + 7
    unit = WorkUnit(3, start, 16 * batch)
    plant = gen.candidate(start + 9 * batch + 5)
    targets = _md5_targets(md5_jax, [plant])
    w = _mask_worker(md5_jax, gen, targets, batch)
    got = _hits_tuple(w.process(unit))
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = _mask_worker(md5_jax, gen, targets, batch)
    assert got == _hits_tuple(w2.process(unit)) != []


def test_mask_super_multi_target(md5_jax, monkeypatch):
    """1k-list-style multi-target compare through the super path."""
    gen = MaskGenerator("?l?l?l?l")
    batch = 1 << 12
    plants = [gen.candidate(i * 37777) for i in range(5)]
    targets = _md5_targets(md5_jax, plants) + _md5_targets(
        md5_jax, [b"zzzz"])
    unit = WorkUnit(0, 0, 48 * batch)
    w = _mask_worker(md5_jax, gen, targets, batch)
    got = _hits_tuple(w.process(unit))
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = _mask_worker(md5_jax, gen, targets, batch)
    assert got == _hits_tuple(w2.process(unit))
    assert len(got) == sum(gen.index_of(p) < unit.end for p in plants)


def test_mask_super_overflow_rescan(md5_jax):
    """count > hit_capacity inside a super ROW falls back to the exact
    oracle rescan of that one batch -- same granularity as per-batch."""
    gen = MaskGenerator("?l?l?l?l")
    batch = 1 << 12
    # 3 plants inside one batch of a super chunk, capacity 2
    base = 17 * batch
    plants = [gen.candidate(base + i) for i in (1, 2, 3)]
    targets = _md5_targets(md5_jax, plants)
    w = _mask_worker(md5_jax, gen, targets, batch, hit_capacity=2)
    hits = w.process(WorkUnit(0, 0, 32 * batch))
    assert {h.plaintext for h in hits} == set(plants)


def test_superstep_disabled_env(md5_jax, monkeypatch):
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    gen = MaskGenerator("?l?l?l")
    w = _mask_worker(md5_jax, gen, _md5_targets(md5_jax, [b"cat"]),
                     1 << 10)
    pu = w.submit(WorkUnit(0, 0, gen.keyspace))
    assert all(kind == "batch" for kind, _, _ in pu.queued)
    assert _hits_tuple(pu.resolve()) == [(0, gen.index_of(b"cat"), b"cat")]


def test_super_build_failure_raises(md5_jax):
    """A fused program the compiler refuses raises with its message:
    the worker runs the one shape its mode names and does not degrade
    to another."""
    gen = MaskGenerator("?l?l?l?l")
    batch = 1 << 12
    plant = gen.candidate(9 * batch + 4)
    w = _mask_worker(md5_jax, gen, _md5_targets(md5_jax, [plant]), batch)

    def broken_super_step(inner):
        raise RuntimeError("compiler says no")

    w._super_step = broken_super_step
    with pytest.raises(RuntimeError, match="compiler says no"):
        w.process(WorkUnit(0, 0, 16 * batch))
    assert not hasattr(w, "_super_disabled")


def test_submit_or_process_wraps_sync_workers():
    from dprf_tpu.runtime.worker import CpuWorker

    gen = MaskGenerator("?l?l?l")
    oracle = get_engine("md5")
    w = CpuWorker(oracle, gen, _md5_targets(oracle, [b"dog"]))
    p = submit_or_process(w, WorkUnit(0, 0, gen.keyspace))
    assert [h.plaintext for h in p.resolve()] == [b"dog"]


# -- pallas kernel path -----------------------------------------------------

def test_pallas_super_matches_plain(md5_jax, monkeypatch):
    from dprf_tpu.ops.pallas_mask import TILE
    from dprf_tpu.runtime.worker import PallasMaskWorker

    gen = MaskGenerator("?l?l?l?l")
    plants = [gen.candidate(5), gen.candidate(9 * TILE + 17)]
    targets = _md5_targets(md5_jax, plants)
    unit = WorkUnit(0, 0, 10 * TILE)
    w = PallasMaskWorker(md5_jax, gen, targets[:1], batch=TILE,
                         oracle=get_engine("md5"), interpret=True)
    got = _hits_tuple(w.process(unit))
    assert got == [(0, 5, plants[0])]
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = PallasMaskWorker(md5_jax, gen, targets[:1], batch=TILE,
                          oracle=get_engine("md5"), interpret=True)
    assert got == _hits_tuple(w2.process(unit))


# -- wordlist workers -------------------------------------------------------

def _words(n, length=6):
    rng = np.random.default_rng(7)
    alpha = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
    return [bytes(alpha[rng.integers(0, 26, length)]) for _ in range(n)]


def test_wordlist_super_matches_per_batch(monkeypatch):
    from dprf_tpu.rules.parser import parse_rules

    eng = get_engine("md5", device="jax")
    oracle = get_engine("md5")
    words = _words(4096)
    rules = parse_rules([":", "u", "$1", "r"])
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    # plant: word 3000 under rule 1 (uppercase)
    plant = words[3000].upper()
    targets = _md5_targets(eng, [plant, b"nope.."])
    # word_batch 128 -> 32 windows; super covers 32, unit = whole space
    w = DeviceWordlistWorker(eng, gen, targets, batch=128 * gen.n_rules,
                             oracle=oracle)
    unit = WorkUnit(0, 0, gen.keyspace)
    got = _hits_tuple(w.process(unit))
    assert (0, 3000 * gen.n_rules + 1, plant) in got
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = DeviceWordlistWorker(eng, gen, targets, batch=128 * gen.n_rules,
                              oracle=oracle)
    assert got == _hits_tuple(w2.process(unit))


def test_wordlist_super_unaligned_unit(monkeypatch):
    """Rule-unaligned unit boundaries: out-of-unit hits filtered the
    same way on both paths."""
    from dprf_tpu.rules.parser import parse_rules

    eng = get_engine("md5", device="jax")
    words = _words(2048)
    rules = parse_rules([":", "l", "u"])
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    plant_g = 500 * 3 + 2
    targets = _md5_targets(eng, [gen.candidate(plant_g)])
    unit = WorkUnit(0, 100, plant_g + 2 - 100)
    w = DeviceWordlistWorker(eng, gen, targets, batch=64 * 3,
                             oracle=get_engine("md5"))
    got = _hits_tuple(w.process(unit))
    assert [g for _, g, _ in got] == [plant_g]
    monkeypatch.setenv("DPRF_SUPERSTEP", "0")
    w2 = DeviceWordlistWorker(eng, gen, targets, batch=64 * 3,
                              oracle=get_engine("md5"))
    assert got == _hits_tuple(w2.process(unit))


# -- pipelined coordinator --------------------------------------------------

def test_coordinator_pipelined_run(md5_jax, tmp_path):
    from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
    from dprf_tpu.runtime.dispatcher import Dispatcher

    gen = MaskGenerator("?l?l?l?l")
    batch = 1 << 12
    plants = [gen.candidate(i) for i in (3, 99999, 420000)]
    targets = _md5_targets(md5_jax, plants)
    worker = _mask_worker(md5_jax, gen, targets, batch)
    disp = Dispatcher(gen.keyspace, unit_size=16 * batch)
    spec = JobSpec("md5", "jax", "mask", "?l?l?l?l", gen.keyspace, "t")
    coord = Coordinator(spec, targets, disp, worker,
                        oracle=get_engine("md5"))
    res = coord.run()
    assert sorted(res.found.values()) == sorted(plants)
    # stopped early (all found) or exhausted -- either way every
    # completed unit is journaled consistently
    assert res.tested <= gen.keyspace


def test_coordinator_pipeline_depth_overlap(md5_jax):
    """The coordinator must submit ahead: at least two units in flight
    before the first resolve (observable via submit call order)."""
    from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
    from dprf_tpu.runtime.dispatcher import Dispatcher

    gen = MaskGenerator("?l?l?l")
    worker = _mask_worker(md5_jax, gen,
                          _md5_targets(md5_jax, [b"zzz"]), 1 << 10)
    events = []
    orig_submit = worker.submit

    class _Spy:
        def __init__(self, pu, start):
            self.pu, self.start = pu, start

        def resolve(self):
            events.append(("resolve", self.start))
            return self.pu.resolve()

    def spy_submit(unit):
        events.append(("submit", unit.start))
        return _Spy(orig_submit(unit), unit.start)

    worker.submit = spy_submit
    disp = Dispatcher(gen.keyspace, unit_size=1 << 12)
    spec = JobSpec("md5", "jax", "mask", "?l?l?l", gen.keyspace, "t")
    Coordinator(spec, _md5_targets(md5_jax, [b"zzz"]), disp, worker,
                oracle=get_engine("md5")).run()
    kinds = [k for k, _ in events]
    assert kinds[:3] == ["submit", "submit", "resolve"]
