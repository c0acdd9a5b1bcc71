"""A bulk target list on the compiled-kernel path (ISSUE 29): a list of
DPRF_TARGETS_PROBE_MIN digests or more on a kernel-eligible engine and
mask gets PallasMaskWorker, whose kernel hashes and whose probe stage
looks every digest up in a table that lives on the device and is an
ARGUMENT of the step.

Everything is compared with the benchmark's plain reference
(`benchmarks/reference.py`: its own mask decode, RFC 1320 MD4 over
UTF-16LE, set membership), which imports nothing of dprf_tpu: same
hits, same plaintexts, and the job's own count of bitmap survivors
equal to a count made here in plain Python from the documented bit
layout.  The kernel runs interpreted (DPRF_PALLAS=1).
"""

import os
import random
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
import reference  # noqa: E402

from dprf_tpu import get_engine  # noqa: E402
from dprf_tpu.engines.base import Target  # noqa: E402
from dprf_tpu.generators.mask import MaskGenerator  # noqa: E402
from dprf_tpu.runtime.workunit import WorkUnit  # noqa: E402

MASK = "?l?l?l?d"            # 175,760 candidates: 43 batches of 4,096
BATCH = 4096                 # one tile at the suite's DPRF_PALLAS_SUB
UNIT = 16 * BATCH            # a unit fuses into a loop program of 16
GOLDEN = 0x9E3779B1


@pytest.fixture(autouse=True)
def kernel_interpreted(monkeypatch):
    monkeypatch.setenv("DPRF_PALLAS", "1")


@pytest.fixture(scope="module")
def swept():
    """The reference's digest of every candidate of MASK, once."""
    return [reference.ntlm(reference.candidate(MASK, i))
            for i in range(reference.keyspace(MASK))]


def _list(swept, n_targets, seed, n_plants=8, n_twins=4):
    """(hash lines, {digest hex: plaintext} the reference expects): a
    seeded list with plants (two of them neighbours in one tile) and
    prefix twins: a twin equals a candidate's digest in words 0-1 and
    differs in words 2-3, so it is no hit."""
    rng = random.Random(seed)
    at = sorted(rng.sample(range(2, len(swept)), n_plants - 2)) + [0, 1]
    want = {swept[i].hex(): reference.candidate(MASK, i) for i in at}
    twins = [swept[i][:8] + rng.randbytes(8)
             for i in rng.sample(range(len(swept)), n_twins)]
    lines = [d for d in want] + [t.hex() for t in twins]
    lines += [rng.randbytes(16).hex()
              for _ in range(n_targets - len(lines))]
    rng.shuffle(lines)
    return lines, want


def _crack(tmp_path, lines, capsys, name="job"):
    """`dprf crack` on the list; -> ({hash: plaintext} of its potfile,
    its `ran` fields, its whole log)."""
    from dprf_tpu.cli import main
    hashfile = tmp_path / f"{name}.hash"
    hashfile.write_text("\n".join(lines) + "\n")
    pot = tmp_path / f"{name}.potfile"
    rc = main(["crack", MASK, str(hashfile), "--engine", "ntlm",
               "--device", "tpu", "--potfile", str(pot),
               "--batch", str(BATCH), "--unit-size", str(UNIT),
               "--unit-seconds", "0"])
    captured = capsys.readouterr()
    log = captured.err
    ran = re.search(r"info\s+ran (.*)$", log, re.M)
    assert ran, log[-2000:]
    fields = dict(f.split("=", 1) for f in ran.group(1).split()
                  if "=" in f)
    found = {h: p for h, p in reference.read_potfile(str(pot))}
    assert rc in (0, 1)
    return found, fields, log


def _counts(field):
    return {k: int(v) if v.isdigit() else v for k, v in
            (f.split(":", 1) for f in field.split(","))}


def _reference_survivors(lines, swept, m_bits, k):
    """Candidates whose k probe bits are all set, counted from the bit
    layout as targets/probe.py documents it: one 512-bit block by a
    multiplicative hash of word 0, then k double-hashed bits inside
    it, the pairs (w0, w1|1) and (w2, w3|1) in turn."""
    block_shift = 32 - ((m_bits // 512).bit_length() - 1)

    def bits_of(digest):
        w = np.frombuffer(digest, "<u4").tolist()
        block = ((w[0] * GOLDEN) & 0xFFFFFFFF) >> block_shift \
            if block_shift < 32 else 0
        pairs = ((w[0], w[1] | 1), (w[2], w[3] | 1))
        return [(block, (pairs[j & 1][0] + (2 * (j >> 1) + 1)
                         * pairs[j & 1][1]) & 511) for j in range(k)]

    have = set()
    for line in lines:
        have.update(bits_of(bytes.fromhex(line)))
    return sum(all(b in have for b in bits_of(d)) for d in swept)


@pytest.mark.parametrize("n_targets", [5_000, 100_000])
def test_bulk_list_matches_reference(n_targets, swept, tmp_path, capsys):
    lines, want = _list(swept, n_targets, seed=n_targets)
    found, ran, log = _crack(tmp_path, lines, capsys)
    # same hits, same plaintexts; a prefix twin is in neither
    assert found == want
    assert ran["worker"] == "PallasMaskWorker"
    assert ran["interpret"] == "True"
    assert "loop:" in ran["dispatch"]          # the fused program ran
    targets = _counts(ran["targets"])
    assert targets["n"] == n_targets and targets["mode"] == "device"
    built = re.search(r"built probe table .*bits=(\d+) k=(\d+)", log)
    m_bits, k = int(built.group(1)), int(built.group(2))
    # bitmap + sorted digests padded to a power of two + first words
    pad = 1 << (n_targets - 1).bit_length()
    assert targets["table_bytes"] == m_bits // 8 + 20 * pad
    verify = _counts(ran["verify"])
    assert verify["exact"] == len(want)
    assert verify["lanes"] == 0 and verify["host_tiles"] == 0
    assert verify["survivors"] == _reference_survivors(
        lines, swept, m_bits, k)
    assert verify["survivors"] >= verify["exact"]
    assert "targets:" in ran["host"]           # the station was open


def test_survivor_overflow_redrives_exactly(swept, tmp_path, capsys,
                                            monkeypatch):
    """More survivors in a batch than its buffer holds: the count is
    inflated past the window's buffer, the window is redriven a batch
    at a time and the batch that still overflows is rescanned on the
    oracle; no hit is lost and none comes twice."""
    monkeypatch.setenv("DPRF_TARGETS_SURVIVOR_CAP", "4")
    rng = random.Random(5)
    at = list(range(100, 112)) + [9000, 70000, len(swept) - 1]
    want = {swept[i].hex(): reference.candidate(MASK, i) for i in at}
    lines = list(want) + [rng.randbytes(16).hex() for _ in range(5_000)]
    rng.shuffle(lines)
    found, ran, log = _crack(tmp_path, lines, capsys)
    assert found == want
    assert ran["worker"] == "PallasMaskWorker"
    assert _counts(ran["verify"])["exact"] < len(want)   # 12 by rescan


def _targets(lines):
    return [Target(raw=h, digest=bytes.fromhex(h)) for h in lines]


def _worker(lines, **kw):
    from dprf_tpu.runtime.worker import PallasMaskWorker
    return PallasMaskWorker(
        get_engine("ntlm", "jax"), MaskGenerator(MASK), _targets(lines),
        batch=BATCH, hit_capacity=16, interpret=True, **kw)


def test_prefix_twin_passes_nothing(swept):
    """A target that shares words 0-1 with a candidate's digest and
    differs later: whether or not the bitmap passes the candidate, the
    exact compare on the device does not, with no oracle at hand."""
    rng = random.Random(2)
    twin = swept[5][:8] + b"\x00" * 8
    near = swept[6][:12] + b"\x00" * 4
    lines = [twin.hex(), near.hex(), swept[7].hex()] + [
        rng.randbytes(16).hex() for _ in range(4_200)]
    w = _worker(lines)
    hits = w.process(WorkUnit(0, 0, BATCH))
    assert [(h.cand_index, h.target_index) for h in hits] == [(7, 2)]
    assert w.verify_counts["exact"] == 1


def test_two_lists_of_one_geometry_cost_one_compile(swept, tmp_path,
                                                    capsys, fresh_cache):
    """The list is data: the second list's job loads every program the
    first one compiled."""
    from dprf_tpu import compilecache
    a, want_a = _list(swept, 5_000, seed=1)
    b, want_b = _list(swept, 5_100, seed=2)
    found, _, _ = _crack(tmp_path, a, capsys, "a")
    assert found == want_a
    first = compilecache.process_cache_counts()
    assert first["cache_misses"] > 0
    found, ran, _ = _crack(tmp_path, b, capsys, "b")
    assert found == want_b
    second = compilecache.process_cache_counts()
    assert second["cache_misses"] == first["cache_misses"]
    assert second["cache_hits"] > first["cache_hits"]
    assert ran["cache"] == "hit"


def test_table_is_an_argument_of_the_program(swept):
    """Lowered for two lists, the step is one program text: no digest
    and no bitmap word is a constant of it."""
    import jax.numpy as jnp
    a, _ = _list(swept, 5_000, seed=3)
    b, _ = _list(swept, 5_000, seed=4)
    args = (jnp.zeros((4,), jnp.int32), jnp.int32(0))
    texts = [_worker(lines).step.lower(*args).as_text()
             for lines in (a, b)]
    assert texts[0] == texts[1]
    assert len(texts[0]) < 400_000       # a 5,000-digest table is 80 kB


def test_setup_probe_raises_where_it_fell_back(swept, monkeypatch):
    """A table that cannot be built, and a host-verify table with no
    oracle to verify with, raise with the reason; neither is replaced
    by the replicated compare table."""
    from dprf_tpu.runtime.worker import DeviceMaskWorker
    from dprf_tpu.targets import probe as probe_mod
    lines, _ = _list(swept, 5_000, seed=6)

    def broken(*a, **kw):
        raise MemoryError("no room for the bitmap")

    with monkeypatch.context() as m:
        m.setattr(probe_mod, "build_probe_table", broken)
        with pytest.raises(MemoryError, match="no room"):
            _worker(lines)
        with pytest.raises(MemoryError, match="no room"):
            DeviceMaskWorker(get_engine("ntlm", "jax"),
                             MaskGenerator(MASK), _targets(lines),
                             batch=256)
    monkeypatch.setenv("DPRF_TARGETS_MAX_BYTES", "16384")
    with pytest.raises(ValueError, match="host-verify mode needs an "
                                         "oracle"):
        _worker(lines)


def test_host_verify_mode_on_the_kernel_path(swept, monkeypatch):
    """A byte budget too small for the exact table: the bitmap stays on
    the device, every survivor is one oracle hash, the `ran` line says
    host-verify."""
    from dprf_tpu.runtime.worker import describe_worker
    monkeypatch.setenv("DPRF_TARGETS_MAX_BYTES", "16384")
    lines, want = _list(swept, 5_000, seed=7)
    w = _worker(lines, oracle=get_engine("ntlm", "cpu"))
    hits = w.process(WorkUnit(0, 0, len(swept)))
    assert {lines[h.target_index]: h.plaintext for h in hits} == want
    said = describe_worker(w)
    assert said["targets"].endswith("mode:host-verify")
    counts = _counts(said["verify"])
    assert counts["exact"] == 0 and counts["lanes"] == counts["survivors"]


def test_the_ladder_routes_a_bulk_list_to_the_kernel(swept):
    from dprf_tpu.runtime.worker import (DeviceMaskWorker,
                                         PallasMaskWorker)
    lines, _ = _list(swept, 4_200, seed=8)
    eng = get_engine("ntlm", "jax")
    gen = MaskGenerator(MASK)
    w = eng.make_mask_worker(gen, _targets(lines), batch=BATCH,
                             hit_capacity=16)       # no oracle needed
    assert type(w) is PallasMaskWorker and w.probe_table is not None
    assert w.ATTACK == "mask+probe"
    # under the floor: the in-kernel probe, as before
    few = eng.make_mask_worker(gen, _targets(lines[:1000]), batch=BATCH,
                               hit_capacity=16,
                               oracle=get_engine("ntlm", "cpu"))
    assert type(few) is PallasMaskWorker and few.probe_table is None
    # a mask the kernel cannot take stays on the XLA probe pipeline
    long_mask = MaskGenerator("?l" * 28)
    x = eng.make_mask_worker(long_mask, _targets(lines), batch=BATCH,
                             hit_capacity=16)
    assert type(x) is DeviceMaskWorker and x.probe_table is not None


def test_loop_superstep_sums_a_count_and_passes_extra_arguments():
    import jax.numpy as jnp

    from dprf_tpu.ops.superstep import make_loop_super_step

    def step(x, n_valid, offset, table):
        lanes = jnp.where(jnp.arange(4) < 1, offset % 3, -1)
        return (jnp.int32(1), lanes.astype(jnp.int32),
                jnp.full((4,), 7, jnp.int32) + table[0], table.sum())

    ls = make_loop_super_step(step, 5, 10,
                              ((0, 1, 2, 10, 8), (3, None, None, 0, 0)))
    count, lanes, tpos, total = ls(jnp.int32(0), jnp.int32(50),
                                   jnp.asarray([2, 3], jnp.int32))
    assert int(count) == 5 and int(total) == 25
    assert lanes.tolist() == [0, 11, 22, 30, 41, -1, -1, -1]
    assert tpos.tolist()[:5] == [9] * 5
