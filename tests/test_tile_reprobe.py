"""The collided-tile re-probe (ops/pallas_mask.make_tile_reprobe):
a tile in which two or more lanes passed the kernel's probe bitmap is
resolved to those lanes on the device, and the host oracle hashes the
lanes, not the tile's width.

NTLM / MD5 in interpret mode at the tests' pinned tile
(DPRF_PALLAS_SUB 32: 4,096 lanes); the plain reference is always
CpuWorker.process over the same range, exact hit sets.  A CPU run
shows results and counts, never a rate.
"""

import random
import re

import pytest

# interpret-mode kernel compiles: tier-1, outside the smoke budget
pytestmark = pytest.mark.compileheavy

from dprf_tpu.engines import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.parallel import make_mesh
from dprf_tpu.parallel.worker import ShardedMaskWorker
from dprf_tpu.runtime.worker import (CpuWorker, PallasMaskWorker,
                                     describe_worker)
from dprf_tpu.runtime.workunit import WorkUnit
from dprf_tpu.telemetry import coverage

SUB = 32
TILE = SUB * 128
BATCH = 2 * TILE        # 12 batches in ?d x5: a loop window of 8 + 4
MASK = "?d?d?d?d?d"     # 100,000

#: twins and a triple inside one tile each, in the fused window and in
#: the per-batch tail, a tile cut by the keyspace's end, and singles
PLANTS = [5, 6, 3 * TILE + 10, 3 * TILE + 500, 3 * TILE + 1000,
          9 * BATCH + 7, 10 * BATCH + 1, 10 * BATCH + 4000,
          90_000, 99_997, 99_999]


class CountingOracle:
    """The CPU oracle, with the length of every hash_batch call kept."""

    def __init__(self, inner):
        self._inner, self.calls = inner, []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def hash_batch(self, cands, **kw):
        self.calls.append(len(cands))
        return self._inner.hash_batch(cands, **kw)


def _targets(engine, gen, idxs):
    cpu = get_engine(engine, device="cpu")
    return [cpu.parse_target(d.hex()) for d in
            cpu.hash_batch([gen.candidate(i) for i in idxs])]


def _worker(engine="ntlm", idxs=PLANTS, oracle=None):
    gen = MaskGenerator(MASK)
    targets = _targets(engine, gen, idxs)
    oracle = oracle or get_engine(engine, device="cpu")
    w = PallasMaskWorker(get_engine(engine, device="jax"), gen, targets,
                         batch=BATCH, hit_capacity=16, oracle=oracle,
                         interpret=True, sub=SUB)
    return w, gen, targets


def _hits(worker, unit):
    return sorted((h.target_index, h.cand_index, h.plaintext)
                  for h in worker.process(unit))


def _cpu_hits(engine, gen, targets, unit):
    return _hits(CpuWorker(get_engine(engine, device="cpu"), gen,
                           targets), unit)


def _noted(fn):
    """fn() with the worker-side coverage notes collected."""
    notes = []
    coverage.install_collector(
        lambda name, start, end, attrs: notes.append(
            (name, start, end, attrs.get("kind"))))
    try:
        return fn(), notes
    finally:
        coverage.install_collector(None)


def case_twins_in_one_tile():
    """Two and three planted targets inside one tile are all found, in
    a fused window and in the per-batch tail, through the device."""
    w, gen, targets = _worker()
    unit = WorkUnit(0, 0, gen.keyspace)
    got, notes = _noted(lambda: _hits(w, unit))
    assert got == _cpu_hits("ntlm", gen, targets, unit)
    assert [g[1] for g in got] == sorted(PLANTS)
    assert "loop" in w.dispatches
    # 5/6, the triple, 10*BATCH+1/+4000 and the last tile's pair
    assert w.verify_counts == {"lanes": len(PLANTS), "tiles": 4,
                               "host_tiles": 0}
    rescans = sorted(n[1:] for n in notes if n[0] == "rescan")
    last = (gen.keyspace - 1) // TILE * TILE
    assert rescans == [(0, TILE, "device"),
                       (3 * TILE, 4 * TILE, "device"),
                       (10 * BATCH, 10 * BATCH + TILE, "device"),
                       (last, gen.keyspace, "device")]


def case_tile_clipped_by_unit_end():
    """A collided tile cut by the unit's end: the re-probe's n_valid
    stops at unit.end, so a third target of the same tile that lies
    behind the end is not reported, and the note says what was
    re-swept."""
    start = 6 * TILE
    idxs = [start + 3, start + 90, start + 200]
    w, gen, targets = _worker(idxs=idxs)
    unit = WorkUnit(0, start - TILE, TILE + 100)
    got, notes = _noted(lambda: _hits(w, unit))
    assert [g[1] for g in got] == idxs[:2]
    assert got == _cpu_hits("ntlm", gen, targets, unit)
    assert [n[1:] for n in notes if n[0] == "rescan"] == \
        [(start, unit.end, "device")]
    assert w.verify_counts["host_tiles"] == 0


def case_seeded_range_equals_cpu_worker():
    """Forty targets at seeded indices (some sharing a tile by chance,
    one pair by design), a seeded unit: exactly the reference's hits."""
    rng = random.Random(2700)
    idxs = sorted(set(rng.randrange(100_000) for _ in range(40))
                  | {41_000, 41_001})
    w, gen, targets = _worker("md5", idxs)
    start = rng.randrange(0, 20_000)
    unit = WorkUnit(0, start, 70_000)
    got = _hits(w, unit)
    assert got == _cpu_hits("md5", gen, targets, unit)
    assert len(got) == sum(unit.start <= i < unit.end for i in idxs)
    assert w.verify_counts["tiles"] >= 1
    assert w.verify_counts["host_tiles"] == 0


def case_oracle_hashes_maybe_lanes_only():
    """hash_batch is called once a maybe lane, one candidate a call,
    and never over a tile's width."""
    oracle = CountingOracle(get_engine("ntlm", device="cpu"))
    w, gen, _ = _worker(oracle=oracle)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert len(hits) == len(PLANTS)
    assert set(oracle.calls) == {1}
    assert len(oracle.calls) == w.verify_counts["lanes"]
    # every plant is a maybe; the filter may pass a few lanes more
    assert len(PLANTS) <= sum(oracle.calls) < TILE // 8


def case_overflow_takes_host_rescan():
    """More maybe lanes in a tile than the re-probe's buffer holds:
    the tile is rescanned on the host, with the same hits."""
    oracle = CountingOracle(get_engine("ntlm", device="cpu"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(PallasMaskWorker, "TILE_LANES", 2)
        w, gen, targets = _worker(oracle=oracle)
    unit = WorkUnit(0, 0, gen.keyspace)
    got, notes = _noted(lambda: _hits(w, unit))
    assert got == _cpu_hits("ntlm", gen, targets, unit)
    # the triple's tile overflows two slots; the three pairs fit
    assert w.verify_counts["tiles"] == 3
    assert w.verify_counts["host_tiles"] == 1
    assert (3 * TILE, 4 * TILE, "host") in [n[1:] for n in notes]
    assert max(oracle.calls) > 1            # CpuWorker hashes in chunks


def case_disagreement_takes_host_rescan():
    """A re-probe that finds fewer than the two lanes that made the
    tile collided is not believed: host rescan, same hits."""
    w, gen, targets = _worker()
    real = w._reprobe

    def one_lane(base, n_valid):
        count, lanes = real(base, n_valid)
        return count * 0 + 1, lanes.at[1:].set(-1)

    w._reprobe = one_lane
    unit = WorkUnit(0, 0, gen.keyspace)
    assert _hits(w, unit) == _cpu_hits("ntlm", gen, targets, unit)
    assert w.verify_counts["tiles"] == 0
    assert w.verify_counts["host_tiles"] == 4


def case_sharded_equals_one_chip():
    """`--devices N`: the mesh worker resolves its collided tiles
    through the same re-probe and reports what the one-chip worker
    reports, with the same counts."""
    one, gen, targets = _worker()
    mesh = ShardedMaskWorker(
        get_engine("ntlm", device="jax"), gen, targets, make_mesh(2),
        batch_per_device=TILE, hit_capacity=16,
        oracle=get_engine("ntlm", device="cpu"),
        kernel={"interpret": True, "sub": SUB})
    assert mesh._reprobe is not None and mesh._tile == TILE
    unit = WorkUnit(0, 0, gen.keyspace)
    pend = mesh.submit(unit)
    assert "sshard" in [k for k, _, _ in pend.queued]
    got, notes = _noted(lambda: sorted(
        (h.target_index, h.cand_index, h.plaintext)
        for h in pend.resolve()))
    assert got == _hits(one, unit)
    assert mesh.verify_counts == one.verify_counts
    assert {n[3] for n in notes if n[0] == "rescan"} == {"device"}


def case_ext_step_keeps_host_rescan():
    """A pallas_ext step (an engine outside CORES) has no pure body to
    re-probe with: its collided tiles stay on the host."""
    idxs = [7, 9, TILE + 1]
    w, gen, targets = _worker("md5(md5)", idxs)
    assert w._reprobe is None
    unit = WorkUnit(0, 0, 4 * TILE)
    got, notes = _noted(lambda: _hits(w, unit))
    assert got == _cpu_hits("md5(md5)", gen, targets, unit)
    assert [g[1] for g in got] == idxs
    assert w.verify_counts["tiles"] == 0
    assert w.verify_counts["host_tiles"] == 1
    assert [n[1:] for n in notes if n[0] == "rescan"] == \
        [(0, TILE, "host")]


def case_warmup_compiles_the_reprobe():
    """The re-probe compiles in the worker's warm-up, with the
    arguments a job calls it with: a job's first collided tile traces
    nothing."""
    w, gen, _ = _worker()
    traced = []
    real = w._reprobe
    w._reprobe = lambda *a: traced.append(
        [(x.shape, x.dtype, x.weak_type) for x in a]) or real(*a)
    w.warmup()
    assert len(traced) == 1 and w.compile_seconds > 0
    w.process(WorkUnit(0, 0, gen.keyspace))
    assert len(traced) == 5 and all(t == traced[0] for t in traced)


def case_describe_worker_says_what_was_verified():
    """`verify=` is a field of its own: `dispatch=` keeps the kinds it
    had, and a one-target worker, which verifies no lane, has none."""
    w, gen, _ = _worker()
    w.process(WorkUnit(0, 0, gen.keyspace))
    ran = describe_worker(w)
    assert ran["verify"] == f"lanes:{len(PLANTS)},tiles:4,host_tiles:0"
    kinds = {f.split(":")[0] for f in ran["dispatch"].split(",")}
    assert kinds == {"loop", "batch"}
    single, _, _ = _worker(idxs=PLANTS[:1])
    assert "verify" not in describe_worker(single)


CASES = {name[len("case_"):]: fn for name, fn in sorted(globals().items())
         if name.startswith("case_")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_tile_reprobe(case):
    CASES[case]()


def test_the_ran_line_carries_verify(tmp_path, capsys, monkeypatch):
    """`dprf crack` on a small NTLM list with twins in one tile: the
    job's own `ran` line says the tile was resolved on the device, and
    `dispatch=` holds only the kinds it held."""
    from dprf_tpu.cli import main as cli_main
    monkeypatch.setenv("DPRF_PALLAS", "1")
    gen = MaskGenerator(MASK)
    cpu = get_engine("ntlm", device="cpu")
    words = [gen.candidate(i) for i in (5, 6, 50_000, 70_000, 70_001)]
    hashes = tmp_path / "h.txt"
    hashes.write_text("".join(d.hex() + "\n"
                              for d in cpu.hash_batch(words)))
    rc = cli_main(["crack", "--engine", "ntlm", "-a", "mask", MASK,
                   str(hashes), "--batch", str(BATCH), "--unit-size",
                   str(8 * BATCH), "--unit-seconds", "0",
                   "--no-potfile"])
    cap = capsys.readouterr()
    ran = [ln for ln in cap.err.splitlines() if " ran " in ln]
    assert rc == 0 and len(ran) == 1, cap.err
    kv = dict(f.split("=", 1) for f in ran[0].split() if "=" in f)
    assert kv["worker"] == "PallasMaskWorker"
    assert re.fullmatch(r"lanes:\d+,tiles:\d+,host_tiles:0", kv["verify"])
    verify = dict(f.split(":") for f in kv["verify"].split(","))
    assert int(verify["tiles"]) == 2 and int(verify["lanes"]) >= 5
    assert {f.split(":")[0] for f in kv["dispatch"].split(",")} <= \
        {"probe", "batch", "loop"}
    for w in words:
        assert w.decode() in cap.out
