"""The unified sharded runtime (parallel/sharded.py): superstep
semantics, shard-boundary hit parity, overflow redrive, and resume /
re-split of a sharded session under a DIFFERENT device count.

The per-batch compat contract is covered by tests/test_parallel.py;
this file exercises what the runtime added -- on-device candidate
generation across fused windows, the device-resident hit buffer, and
the one-collective-per-superstep discipline -- at hit-placement edges
(shard boundaries, window boundaries, the last keyspace index).
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# device-pipeline compiles: full suite / tier-1, excluded from the <5-min
# smoke tier (tools/check_markers.py enforces an explicit tier decision)
pytestmark = pytest.mark.compileheavy

from dprf_tpu.engines import get_engine
from dprf_tpu.engines.base import Target
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.parallel import make_mesh
from dprf_tpu.parallel.worker import ShardedMaskWorker, shard_super_cap
from dprf_tpu.runtime.dispatcher import Dispatcher
from dprf_tpu.runtime.worker import CpuWorker, submit_or_process
from dprf_tpu.runtime.workunit import WorkUnit
from dprf_tpu.telemetry import coverage


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
    notes = []
    coverage.install_collector(
        lambda name, start, end, attrs: notes.append((name, start, end)))
    try:
        hits = worker.process(unit)
    finally:
        coverage.install_collector(None)
    return hits, notes


def test_superstep_hits_at_every_boundary(mesh):
    """One unit big enough to fuse superstep windows plus a per-batch
    remainder; plants sit at shard boundaries, window boundaries, and
    the LAST keyspace index -- the sharded sweep must equal the CPU
    oracle exactly."""
    gen = MaskGenerator("?l?l?l?l")        # 456976
    B = 1024
    stride = 8 * B
    plant = [0, B - 1, B, stride - 1, stride,           # shard edges
             8 * stride - 1, 8 * stride,                # window edge
             gen.keyspace - 1]                          # last index
    targets = _md5_targets(gen, plant)
    w = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                          mesh, batch_per_device=B, hit_capacity=16)
    unit = WorkUnit(0, 0, gen.keyspace)
    pend = w.submit(unit)
    kinds = [k for k, _, _ in pend.queued]
    # the tentpole path really ran: fused windows AND a remainder
    assert "sshard" in kinds
    got = sorted((h.target_index, h.cand_index, h.plaintext)
                 for h in pend.resolve())
    assert got == _cpu_hits(gen, targets, unit)
    assert [g[1] for g in got] == plant


def test_superstep_single_collective_shape(mesh):
    """A superstep dispatch returns ONE replicated result tuple for
    the whole window (count/lanes/tpos per shard, window-relative
    lanes) -- not one per batch."""
    from dprf_tpu.parallel.sharded import make_sharded_mask_step
    from dprf_tpu.ops.pipeline import target_words
    gen = MaskGenerator("?l?l?l?l")
    step = make_sharded_mask_step(
        get_engine("md5", device="jax"), gen,
        target_words(hashlib.md5(gen.candidate(12345)).digest(),
                     little_endian=True),
        mesh, 512)
    ss = step.superstep(4)
    window = 4 * step.super_batch
    total, counts, lanes, tpos = ss(
        jnp.asarray(gen.digits(0), dtype=jnp.int32), jnp.int32(window))
    assert int(total) == 1
    # the window's buffer is window_capacity(64, 4) wide a shard; the
    # per-batch step keeps hit_capacity
    assert counts.shape == (8,) and lanes.shape == (8, 256)
    assert step(jnp.asarray(gen.digits(0), dtype=jnp.int32),
                jnp.int32(step.super_batch))[2].shape == (8, 64)
    lanes_np = np.asarray(lanes)
    assert list(lanes_np[lanes_np >= 0]) == [12345]   # window-relative
    # cached program identity: same inner -> same compiled callable
    assert step.superstep(4) is ss


def test_superstep_overflow_redrives_exactly(mesh, monkeypatch):
    """A shard whose window collects more hits than the WINDOW's
    buffer holds (hit_capacity x inner slots) truncates the buffer but
    keeps the count over it; the worker must redrive the window
    per-batch and report every hit exactly once."""
    monkeypatch.setenv("DPRF_SHARD_SUPER_CAP", "8")
    gen = MaskGenerator("?d?d?d?d?d")       # 100000
    B = 128
    stride = 8 * B
    # shard 0's lane slices of the first window (8 strides, 16 slots):
    # two plants a stride fill the buffer, the seventeenth is past it
    plant = sorted([i * stride + k for i in range(8) for k in (0, 1)]
                   + [3 * stride + 5, gen.keyspace - 1])
    targets = _md5_targets(gen, plant)
    w = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                          mesh, batch_per_device=B, hit_capacity=2,
                          oracle=get_engine("md5", device="cpu"))
    assert w.step.superstep(8)(
        jnp.asarray(gen.digits(0), dtype=jnp.int32),
        jnp.int32(8 * stride))[2].shape == (8, 16)
    unit = WorkUnit(0, 0, gen.keyspace)
    hits, notes = _process_noted(w, unit)
    assert sorted(h.cand_index for h in hits) == plant
    assert len(hits) == len(set(h.cand_index for h in hits))
    assert [n[1:] for n in notes if n[0] == "redrive"] \
        == [(0, 8 * stride)]


@pytest.mark.parametrize("kernel", [None, {"interpret": True, "sub": 8}],
                         ids=["xla", "kernel"])
def test_superstep_window_holds_more_than_hit_capacity(kernel,
                                                       monkeypatch):
    """One shard's window collects more matches than hit_capacity and
    fewer than the window's capacity, no stride more than
    hit_capacity: they are decoded from the window's buffer, every
    plant exactly once, and NO window is swept again."""
    monkeypatch.setenv("DPRF_SHARD_SUPER_CAP", "8")
    gen = MaskGenerator("?d?d?d?d?d")       # 100000
    B = 4 * 1024                # four sub=8 tiles a shard and stride
    stride = 2 * B              # 12 strides: one window of 8, a tail
    # shard 0's slices of the window: two plants a stride, in two
    # tiles, on five strides: 10 > 4 slots a stride, < 32 a window
    plant = sorted([i * stride + t * 1024 + 7 * i + t
                    for i in range(5) for t in (0, 2)]
                   + [gen.keyspace - 1])
    targets = _md5_targets(gen, plant)
    w = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                          make_mesh(2), batch_per_device=B,
                          hit_capacity=4,
                          oracle=get_engine("md5", device="cpu"),
                          kernel=kernel)
    assert ("+kernel" in w.ATTACK) == (kernel is not None)
    unit = WorkUnit(0, 0, gen.keyspace)
    hits, notes = _process_noted(w, unit)
    got = sorted((h.target_index, h.cand_index, h.plaintext)
                 for h in hits)
    assert got == _cpu_hits(gen, targets, unit)
    assert [g[1] for g in got] == plant
    assert ("window", 0, 8 * stride) in notes
    assert not [n for n in notes if n[0] in ("redrive", "rescan")]


# ---------------------------------------------------------------------------
# the width of a window's buffer, through make_sharded_step with a
# small fake compute: span 16 a shard, 8 shards, hit_capacity 4

K, CAP, INNER = 16, 4, 8


def _fake_step(mesh, planted, sentinel_at=None):
    """make_sharded_step over a compute that finds the window-relative
    lanes in `planted`.  With `sentinel_at` it is a TILE compute (one
    grid cell a lane) that reports hit_capacity + 1 in the stride
    holding that lane: the single-target kernel's collision count."""
    from dprf_tpu.parallel.sharded import make_sharded_step
    planted = jnp.asarray(sorted(planted), jnp.int32)

    def compute(offset, n_valid):
        rel = offset + jnp.arange(K, dtype=jnp.int32)
        found = jnp.isin(rel, planted) & (rel < n_valid)
        payload = jnp.zeros((K,), jnp.int32)
        if sentinel_at is None:
            return found, payload
        collided = jnp.any(rel == sentinel_at)
        count = found.sum(dtype=jnp.int32) + jnp.where(
            collided, jnp.int32(CAP + 1), 0)
        return found, payload, rel, count

    return make_sharded_step(compute, mesh, K, 1, hit_capacity=CAP)


def _shard0(stride_i, *lanes):
    """Window-relative lanes inside shard 0's slice of a stride."""
    return [stride_i * 8 * K + lane for lane in lanes]


@pytest.mark.parametrize("inner", [1, 2, 16, 256])
def test_sharded_program_width_is_the_window_policy(mesh, inner):
    from dprf_tpu.ops.superstep import window_capacity
    step = _fake_step(mesh, [3])
    program = step if inner == 1 else step.superstep(inner)
    total, counts, lanes, _ = program(jnp.int32(inner * 8 * K))
    assert step.hit_capacity == CAP         # the per-batch width
    assert lanes.shape == (8, window_capacity(CAP, inner))
    assert int(total) == 1 and list(np.asarray(lanes[0][:2])) == [3, -1]


@pytest.mark.parametrize("case", ["fits", "stride_truncated",
                                  "collision_sentinel",
                                  "window_overflows"])
def test_no_maybe_dropped_without_the_count_saying_so(mesh, case,
                                                      monkeypatch):
    """The runtime's one rule: a stride whose own count exceeds the
    stride's width pushes the window's count past the WINDOW's width.
    `fits` is the control: the same window, one match fewer."""
    sentinel_at = None
    if case == "fits":
        # 4 in one stride, 3 more on other strides: 7 of 32 slots
        planted = (_shard0(2, 0, 5, 9, 15) + _shard0(0, 1)
                   + _shard0(7, 2, 3))
    elif case == "stride_truncated":
        # 5 matches in one stride's 4 slots, the window's total 8
        planted = (_shard0(2, 0, 5, 9, 12, 15) + _shard0(0, 1)
                   + _shard0(7, 2, 3))
    elif case == "collision_sentinel":
        # the TILE compute reports hit_capacity + 1 = 5 on one stride
        planted = _shard0(0, 1) + _shard0(7, 2, 3)
        sentinel_at = _shard0(2, 0)[0]
    else:
        # every stride within its 4 slots, the window past its own
        # (only where the policy's clamp is under hit_capacity x
        # inner: here 8, in production 1,024)
        from dprf_tpu.ops import superstep
        monkeypatch.setattr(superstep, "WINDOW_CAPACITY_MAX", 8)
        planted = (_shard0(0, 1, 2, 3) + _shard0(1, 4, 5, 6)
                   + _shard0(2, 7, 8, 9))
    step = _fake_step(mesh, planted, sentinel_at)
    total, counts, lanes, _ = step.superstep(INNER)(
        jnp.int32(INNER * 8 * K))
    counts, lanes = np.asarray(counts), np.asarray(lanes)
    width = lanes.shape[-1]
    assert width == (8 if case == "window_overflows" else CAP * INNER)
    assert (counts[1:] == 0).all()
    if case == "fits":
        assert counts[0] == len(planted) <= width
        assert sorted(lanes[0][lanes[0] >= 0]) == sorted(planted)
    else:
        assert counts[0] > width
        assert int(total) == counts[0]


def test_resume_resplit_under_different_device_count(mesh):
    """A sharded session interrupted mid-sweep resumes under a
    DIFFERENT device count (8 -> 2) and a different unit size with
    exact coverage and no overlap -- coverage is keyspace-indexed, so
    the mesh width is a per-run execution detail."""
    gen = MaskGenerator("?d?d?d?d")         # 10000
    plant = [0, 1234, 4999, 5000, 7777, gen.keyspace - 1]
    targets = _md5_targets(gen, plant)
    eng = get_engine("md5", device="jax")

    hits = []
    disp = Dispatcher(gen.keyspace, 2000)
    w8 = ShardedMaskWorker(eng, gen, targets, mesh,
                           batch_per_device=128, hit_capacity=16)
    for _ in range(3):                      # interrupt after 3 units
        unit = disp.lease("w8")
        hits.extend(submit_or_process(w8, unit).resolve())
        disp.complete(unit.unit_id, worker_id="w8")
    completed = disp.completed_intervals()
    assert sum(e - s for s, e in completed) == 6000

    # resume: different unit size AND a 2-device mesh
    disp2 = Dispatcher.from_completed(gen.keyspace, 1536, completed)
    w2 = ShardedMaskWorker(eng, gen, targets, make_mesh(2),
                           batch_per_device=128, hit_capacity=16)
    swept = []
    while True:
        unit = disp2.lease("w2")
        if unit is None:
            break
        swept.append((unit.start, unit.end))
        hits.extend(submit_or_process(w2, unit).resolve())
        disp2.complete(unit.unit_id, worker_id="w2")
    assert disp2.done()
    # resumed units never re-sweep covered ranges (no overlap)
    for s, e in swept:
        for cs, ce in completed:
            assert e <= cs or s >= ce, (swept, completed)
    # exact coverage: union of both phases is the whole keyspace
    assert sum(e - s for s, e in disp2.completed_intervals()) \
        == gen.keyspace
    assert sorted(h.cand_index for h in hits) == plant
    assert len(hits) == len(set(h.cand_index for h in hits))


def test_pertarget_sharded_workers_pipeline(mesh):
    """The per-target sharded workers are submit-based now: submit()
    enqueues every (target, batch) dispatch with ONE device-
    accumulated flag, so the remote worker loop pipelines them."""
    from dprf_tpu.engines.device.phpass import ShardedPhpassMaskWorker
    from dprf_tpu.engines.device.salted import ShardedSaltedMaskWorker
    for cls in (ShardedPhpassMaskWorker, ShardedSaltedMaskWorker,
                ShardedMaskWorker):
        assert getattr(cls.process, "_submit_based", False), cls
        assert "submit" in cls.__dict__ or any(
            "submit" in b.__dict__ for b in cls.__mro__[1:]), cls


def test_shard_super_cap_knob(monkeypatch):
    monkeypatch.setenv("DPRF_SHARD_SUPER_CAP", "100")
    assert shard_super_cap() == 64          # power-of-two clamp
    monkeypatch.setenv("DPRF_SHARD_SUPER_CAP", "junk")
    assert shard_super_cap() == 256         # registry default
    monkeypatch.setenv("DPRF_SHARD_SUPER_CAP", "1")
    assert shard_super_cap() == 2           # floor: fusing needs >= 2
