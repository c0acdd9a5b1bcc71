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

import numpy as np
import pytest

# interpret-mode kernel compiles: tier-1, outside the smoke budget
pytestmark = pytest.mark.compileheavy

from dprf_tpu.engines import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.parallel import make_mesh
from dprf_tpu.parallel.worker import ShardedMaskWorker
from dprf_tpu.runtime.worker import (CpuWorker, Hit, PallasMaskWorker,
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
    # 5/6, the triple, 10*BATCH+1/+4000 and the last tile's pair; the
    # fused window's two tiles in one oracle call, the tail's three
    # batches one call each for their singles and their tiles' lanes
    assert w.verify_counts == {"lanes": len(PLANTS), "batches": 5,
                               "tiles": 4, "host_tiles": 0}
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
    """hash_batch is called with the maybe lanes of a decoded window,
    its singles in one call and its re-probed tiles' lanes in one
    more, and never over a tile's width."""
    oracle = CountingOracle(get_engine("ntlm", device="cpu"))
    w, gen, _ = _worker(oracle=oracle)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert len(hits) == len(PLANTS)
    assert len(oracle.calls) == w.verify_counts["batches"] == 5
    assert sum(oracle.calls) == w.verify_counts["lanes"]
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
    assert ran["verify"] == \
        f"lanes:{len(PLANTS)},batches:5,tiles:4,host_tiles:0"
    kinds = {f.split(":")[0] for f in ran["dispatch"].split(",")}
    assert kinds == {"loop", "batch"}
    single, _, _ = _worker(idxs=PLANTS[:1])
    assert "verify" not in describe_worker(single)


def _loose_targets(engine, gen, idxs, n_fill=49):
    """The plants' targets and n_fill seeded random digests: under a
    kernel bitmap sized for LOOSE_FP the list passes false maybes."""
    rng = random.Random(4000)
    cpu = get_engine(engine, device="cpu")
    return _targets(engine, gen, idxs) + [
        cpu.parse_target(rng.randbytes(16).hex()) for _ in range(n_fill)]


#: a kernel bitmap budget loose enough that 60 targets pass about 40
#: false maybes over MASK's 100,000 candidates
LOOSE_FP = 1e-2


def case_mesh_verifies_a_window_in_one_call():
    """Four shards, planted targets and false maybes: a window's single
    maybes, every shard's together, take ONE oracle call and its
    re-probed tiles' lanes one more; the hits are the exact sweep's,
    and `verify=` counts the calls."""
    gen = MaskGenerator(MASK)
    targets = _loose_targets("ntlm", gen, PLANTS)
    oracle = CountingOracle(get_engine("ntlm", device="cpu"))
    w = ShardedMaskWorker(
        get_engine("ntlm", device="jax"), gen, targets, make_mesh(4),
        batch_per_device=TILE, hit_capacity=16, oracle=oracle,
        kernel={"interpret": True, "sub": SUB, "probe_fp": LOOSE_FP})
    windows = []
    real = w._decode_queued

    def spy(kind, start, result, unit):
        calls, tiles = len(oracle.calls), w.verify_counts["tiles"]
        out = real(kind, start, result, unit)
        _, _, lanes, tpos = (np.asarray(a) for a in result)
        single = (lanes >= 0) & (tpos != len(targets) + 1)
        windows.append((len(oracle.calls) - calls,
                        int(single.any(axis=1).sum()),
                        w.verify_counts["tiles"] - tiles))
        return out

    w._decode_queued = spy
    unit = WorkUnit(0, 0, gen.keyspace)
    assert _hits(w, unit) == _cpu_hits("ntlm", gen, targets, unit)
    assert w.verify_counts["host_tiles"] == 0
    # (oracle calls, shards with single maybes, re-probed tiles)
    assert all(calls == (shards > 0) + (tiles > 0)
               for calls, shards, tiles in windows), windows
    assert max(shards for _, shards, _ in windows) >= 2, windows
    assert len(oracle.calls) == w.verify_counts["batches"]
    assert sum(oracle.calls) == w.verify_counts["lanes"] > len(PLANTS)
    said = re.fullmatch(r"lanes:(\d+),batches:(\d+),tiles:\d+,host_tiles:0",
                        describe_worker(w)["verify"])
    assert said and int(said[1]) > int(said[2])


def case_confirmed_lanes_skip_the_oracle():
    """A lane whose target pos is in range was confirmed by the device:
    it is reported as it stands and never hashed; the out-of-range
    lanes of the same buffer go to the oracle in one call."""
    oracle = CountingOracle(get_engine("ntlm", device="cpu"))
    w, gen, _ = _worker(oracle=oracle)
    pos = {int(t): p for p, t in enumerate(w._order)}
    n, base = len(w._order), 3 * TILE
    # PLANTS[2] and PLANTS[4] confirmed, PLANTS[3] and two non-targets
    # maybes, one slot unused
    lanes = np.array([10, -1, 500, 7, 1000, 9])
    tpos = np.array([pos[2], 0, n, n + 1, pos[4], n])
    hits = w._decode_lanes(base, lanes, tpos, WorkUnit(0, base, TILE))
    assert hits == [Hit(i, PLANTS[i], gen.candidate(PLANTS[i]))
                    for i in (2, 4, 3)]
    assert oracle.calls == [3]
    assert w.verify_counts["lanes"] == 3
    assert w.verify_counts["batches"] == 1


def case_verifier_keeps_lane_order():
    """_verify_probe_lanes over shuffled lanes, the plants among them:
    exactly the exact sweep's hits, in the order the lanes came, from
    one oracle call."""
    oracle = CountingOracle(get_engine("ntlm", device="cpu"))
    w, gen, targets = _worker(oracle=oracle)
    rng = random.Random(4001)
    gidxs = PLANTS + [rng.randrange(gen.keyspace) for _ in range(40)]
    rng.shuffle(gidxs)
    exact = {h.cand_index: h for h in
             CpuWorker(get_engine("ntlm", device="cpu"), gen, targets)
             .process(WorkUnit(0, 0, gen.keyspace))}
    unit = WorkUnit(0, 0, gen.keyspace)
    assert w._verify_probe_lanes(gidxs, unit) == \
        [exact[g] for g in gidxs if g in exact]
    assert oracle.calls == [len(gidxs)]
    assert w._verify_probe_lanes([], unit) == [] and len(oracle.calls) == 1


CASES = {name[len("case_"):]: fn for name, fn in sorted(globals().items())
         if name.startswith("case_")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_tile_reprobe(case):
    CASES[case]()


#: the host's verification, opened inside `decode` and `verify` only
VERIFY_STATIONS = ("readback", "reprobe", "reprobe_wait", "oracle")


@pytest.mark.parametrize("chips", [1, 2])
def test_a_list_job_names_its_verification(monkeypatch, chips):
    """A list job through `Coordinator.run`, on one chip and on a mesh:
    the host's verification opens its stations, each as often as the
    work it names happens, inside `decode` or `verify` and never inside
    `lease`, `submit` or `complete` (whose idle the benchmark reads as
    the dispatch's).  The clock the stations read moves only in the
    oracle (a second a call) and in a device readback (a quarter), so
    what they hold is exactly not `decode`'s or `verify`'s own."""
    import contextlib
    import jax
    from dprf_tpu.runtime.coordinator import Coordinator, JobSpec
    from dprf_tpu.runtime.dispatcher import Dispatcher
    from dprf_tpu.telemetry import trace as trace_mod
    from dprf_tpu.telemetry.registry import MetricsRegistry
    clock = [0.0]
    monkeypatch.setattr(trace_mod, "_perf", lambda: clock[0])

    class TickingOracle(CountingOracle):
        def hash_batch(self, cands, **kw):
            clock[0] += 1.0
            return super().hash_batch(cands, **kw)

        def verify(self, plain, target):
            clock[0] += 1.0
            return self._inner.verify(plain, target)

    real_get = jax.device_get

    def ticking_get(x):
        clock[0] += 0.25
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", ticking_get)
    opened, stack = [], []
    real_station = trace_mod.TraceRecorder.station

    def spy(self, name, unit=None):
        ctx = real_station(self, name, unit)

        @contextlib.contextmanager
        def held():
            opened.append((name, tuple(stack)))
            stack.append(name)
            try:
                with ctx:
                    yield
            finally:
                stack.pop()
        return held()

    monkeypatch.setattr(trace_mod.TraceRecorder, "station", spy)
    gen = MaskGenerator(MASK)
    targets = _targets("ntlm", gen, PLANTS)
    oracle = TickingOracle(get_engine("ntlm", device="cpu"))
    if chips == 1:
        w = PallasMaskWorker(get_engine("ntlm", device="jax"), gen,
                             targets, batch=BATCH, hit_capacity=16,
                             oracle=oracle, interpret=True, sub=SUB)
    else:
        w = ShardedMaskWorker(
            get_engine("ntlm", device="jax"), gen, targets,
            make_mesh(chips), batch_per_device=BATCH // chips,
            hit_capacity=16, oracle=oracle,
            kernel={"interpret": True, "sub": SUB})
    w.warmup()
    with_tiles = []
    real_tile_hits = w._tile_hits

    def tile_hits(pending, unit):
        with_tiles.append(bool(pending))
        return real_tile_hits(pending, unit)

    w._tile_hits = tile_hits
    rec, reg = trace_mod.get_tracer(), MetricsRegistry()
    disp = Dispatcher(gen.keyspace, 8 * BATCH, registry=reg, recorder=rec)
    spec = JobSpec(engine="ntlm", device="jax", attack="mask",
                   attack_arg=MASK, keyspace=gen.keyspace,
                   fingerprint="verify-stations")
    before = rec.station_table()
    result = Coordinator(spec, targets, disp, w, registry=reg,
                         recorder=rec, oracle=oracle).run()
    table = {name: (n - before.get(name, (0, 0.0))[0],
                    s - before.get(name, (0, 0.0))[1])
             for name, (n, s) in rec.station_table().items()}
    assert result.exhausted and len(result.found) == len(PLANTS)
    assert w.verify_counts["host_tiles"] == 0
    # one call into the oracle a station: the worker's maybe batches
    # and one re-hash a hit
    calls = w.verify_counts["batches"] + len(PLANTS)
    assert table["oracle"] == (calls, pytest.approx(1.0 * calls))
    # every unit reported: one readback each
    decoded = table["decode"][0]
    assert decoded == 2
    assert table["readback"] == (decoded, pytest.approx(0.25 * decoded))
    tiles = sum(with_tiles)
    assert tiles >= 1 and len(with_tiles) >= tiles
    assert table["reprobe"][0] == tiles
    assert table["reprobe_wait"] == (tiles, pytest.approx(0.25 * tiles))
    assert table["decode"][1] == pytest.approx(0.0)
    assert table["verify"][1] == pytest.approx(0.0)
    for name, outer in opened:
        if name in VERIFY_STATIONS:
            assert {"decode", "verify"} & set(outer), (name, outer)
            assert not {"lease", "submit", "complete"} & set(outer), \
                (name, outer)


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
    assert re.fullmatch(r"lanes:\d+,batches:\d+,tiles:\d+,host_tiles:0",
                        kv["verify"])
    verify = dict(f.split(":") for f in kv["verify"].split(","))
    assert int(verify["tiles"]) == 2 and int(verify["lanes"]) >= 5
    assert 1 <= int(verify["batches"]) < int(verify["lanes"])
    assert {f.split(":")[0] for f in kv["dispatch"].split(",")} <= \
        {"probe", "batch", "loop"}
    for w in words:
        assert w.decode() in cap.out
