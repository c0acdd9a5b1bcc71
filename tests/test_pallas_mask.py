"""Pallas MD5 mask kernel vs the oracle (interpret mode on the CPU
backend; the same kernel compiles natively on TPU).

Covers: charset segment decomposition, planted-password extraction,
n_valid masking, the tile-collision -> rescan overflow convention, and
worker-level equivalence with the XLA pipeline path.
"""

import hashlib

import numpy as np
import jax.numpy as jnp
import pytest

from dprf_tpu.engines import get_engine
from dprf_tpu.generators.mask import BUILTIN_CHARSETS, MaskGenerator
from dprf_tpu.ops.pallas_mask import (MAX_SEGMENTS, TILE, charset_segments,
                                     make_pallas_mask_crack_step,
                                     mask_supported)
from dprf_tpu.runtime.worker import PallasMaskWorker
from dprf_tpu.runtime.workunit import WorkUnit


def _target(plain: bytes) -> np.ndarray:
    return np.frombuffer(hashlib.md5(plain).digest(),
                         dtype="<u4").astype(np.uint32)


@pytest.mark.smoke
def test_charset_segments_reconstruct():
    for name, cs in BUILTIN_CHARSETS.items():
        segs = charset_segments(cs)
        assert len(segs) <= MAX_SEGMENTS, name
        # reconstruct every byte from the piecewise map
        got = []
        for d in range(len(cs)):
            delta = [dl for s, dl in segs if s <= d][-1]
            got.append(d + delta)
        assert bytes(got) == cs, name
    assert mask_supported(list(BUILTIN_CHARSETS.values()))


def _engine_target(engine_name: str, plain: bytes) -> np.ndarray:
    """Target digest words in the engine's layout, via hashlib oracles."""
    if engine_name == "md5":
        d, dt = hashlib.md5(plain).digest(), "<u4"
    elif engine_name == "sha1":
        d, dt = hashlib.sha1(plain).digest(), ">u4"
    elif engine_name == "sha256":
        d, dt = hashlib.sha256(plain).digest(), ">u4"
    elif engine_name == "sha512":
        d, dt = hashlib.sha512(plain).digest(), ">u4"
    elif engine_name == "sha384":
        d, dt = hashlib.sha384(plain).digest(), ">u4"
    else:   # ntlm: MD4 over UTF-16LE
        from dprf_tpu.engines.cpu.md4 import md4
        d, dt = md4(plain.decode("latin-1").encode("utf-16-le")), "<u4"
    return np.frombuffer(d, dtype=dt).astype(np.uint32)


@pytest.mark.parametrize("engine", ["md5", "sha1", "ntlm"])
@pytest.mark.parametrize("mask,plant", [
    ("?l?l?l?l", b"crab"),
    ("?d?d?d?d?d", b"90210"),
    ("?a?a?a", b"X& "),
    ("pre?l?d", b"prez7"),      # literals + mixed charsets
])
def test_kernel_finds_planted(engine, mask, plant):
    gen = MaskGenerator(mask)
    pidx = gen.index_of(plant)
    step = make_pallas_mask_crack_step(engine, gen,
                                       _engine_target(engine, plant),
                                       batch=TILE, interpret=True)
    base = TILE * (pidx // TILE)
    n_valid = min(TILE, gen.keyspace - base)
    bd = jnp.asarray(gen.digits(base), dtype=jnp.int32)
    count, lanes, _ = step(bd, jnp.int32(n_valid))
    assert int(count) == 1
    assert int(np.asarray(lanes)[0]) == pidx - base
    # plant masked out by n_valid -> no hit
    count2, _, _ = step(bd, jnp.int32(pidx - base))
    assert int(count2) == 0


@pytest.mark.smoke
def test_tile_collision_forces_rescan_convention():
    """Two hits in one tile can only report one lane, so the reducer
    must return count > hit_capacity (the worker then rescans exactly).
    Driven directly through reduce_tile_hits: an MD5 collision can't be
    fabricated, but the kernel's counts output can."""
    from dprf_tpu.ops.pallas_mask import reduce_tile_hits

    cap = 8
    # tile 3 holds two hits; only lane 7 was extractable
    counts = jnp.asarray([[0], [1], [0], [2]], jnp.int32)
    lanes = jnp.asarray([[-1], [5], [-1], [7]], jnp.int32)
    count, glanes, _ = reduce_tile_hits(counts, lanes, cap, tile=100)
    assert int(count) == cap + 1          # forces worker rescan
    # single-hit tiles still decode to global lanes
    counts1 = jnp.asarray([[0], [1], [0], [1]], jnp.int32)
    count1, glanes1, _ = reduce_tile_hits(counts1, lanes, cap, tile=100)
    assert int(count1) == 2
    got = sorted(int(x) for x in np.asarray(glanes1) if x >= 0)
    assert got == [105, 307]
    # capacity still exact when more hit-tiles than capacity slots
    count0, _, _ = reduce_tile_hits(counts1, lanes, 0, tile=100)
    assert int(count0) == 2


def test_worker_rescan_on_fabricated_collision():
    """End-to-end: a step reporting a tile collision must make the
    worker fall back to the oracle rescan and recover every hit."""
    gen = MaskGenerator("?l?l?l?l")
    plant = b"wasp"
    eng = get_engine("md5", device="jax")
    targets = [eng.parse_target(hashlib.md5(plant).hexdigest())]
    worker = PallasMaskWorker(eng, gen, targets, batch=TILE,
                                 hit_capacity=8,
                                 oracle=get_engine("md5"), interpret=True)
    real_step = worker.step

    def lying_step(base, n_valid):
        count, lanes, tpos = real_step(base, n_valid)
        # pretend a tile had 2 hits: overflow convention
        return jnp.int32(9), lanes, tpos

    worker.step = lying_step
    hits = worker.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.cand_index, h.plaintext) for h in hits] == \
        [(gen.index_of(plant), plant)]


@pytest.mark.parametrize("engine", ["md5", "sha1", "ntlm"])
def test_pallas_worker_matches_xla_worker(engine):
    gen = MaskGenerator("?l?l?l?l")
    plant = b"wasp"
    eng = get_engine(engine, device="jax")
    targets = [eng.parse_target(_engine_target(engine, plant).astype(
        "<u4" if eng.little_endian else ">u4").tobytes().hex())]
    oracle = get_engine(engine)
    pworker = PallasMaskWorker(eng, gen, targets, batch=TILE,
                               hit_capacity=8, oracle=oracle,
                               interpret=True)
    unit = WorkUnit(0, 0, gen.keyspace)
    phits = pworker.process(unit)
    xworker = eng.make_mask_worker(gen, targets, batch=1 << 14,
                                   hit_capacity=8, oracle=oracle)
    xhits = xworker.process(unit)
    assert [(h.target_index, h.cand_index, h.plaintext) for h in phits] == \
        [(h.target_index, h.cand_index, h.plaintext) for h in xhits]
    assert phits[0].plaintext == plant


@pytest.mark.parametrize("engine", ["md5", "sha1", "sha256", "ntlm",
                                    "sha512", "sha384"])
def test_kernel_body_emulated_finds_planted(engine):
    """Eager (no-jit) drive of the shared kernel body: the only CPU
    vehicle for the SHA-256 kernel math, whose statically-unrolled
    graph XLA:CPU cannot compile in reasonable time; also cross-checks
    the other engines against the same body the pallas_call wraps."""
    from dprf_tpu.ops.pallas_mask import emulate_mask_kernel

    gen = MaskGenerator("?l?l?l?l")
    plant = b"crab"
    pidx = gen.index_of(plant)
    tw = _engine_target(engine, plant)
    base = TILE * (pidx // TILE)
    bd = gen.digits(base)
    counts, lanes = emulate_mask_kernel(engine, gen, tw, batch=TILE,
                                        base_digits=bd,
                                        n_valid=min(TILE, gen.keyspace - base))
    assert counts.sum() == 1               # batch == TILE: a single tile
    assert base + int(lanes[0, 0]) == pidx
    # n_valid masking: plant excluded -> no hit anywhere
    counts2, _ = emulate_mask_kernel(engine, gen, tw, batch=TILE,
                                     base_digits=bd, n_valid=pidx - base)
    assert counts2.sum() == 0


def test_emulator_matches_pallas_interpret():
    """The emulator and the pallas_call path must agree tile-for-tile
    (they share the kernel body; this pins the plumbing equivalence
    that lets emulator-only SHA-256 coverage stand in for interpret
    runs)."""
    from dprf_tpu.ops.pallas_mask import emulate_mask_kernel, make_mask_pallas_fn

    gen = MaskGenerator("?l?l?l?l")
    plant = b"wasp"
    tw = _engine_target("md5", plant)
    batch = 2 * TILE
    bd = gen.digits(0)
    fn = make_mask_pallas_fn("md5", gen, tw, batch, interpret=True)
    pc, pl_ = fn(jnp.asarray(bd, jnp.int32), jnp.asarray([batch], jnp.int32))
    ec, el = emulate_mask_kernel("md5", gen, tw, batch, bd, batch)
    assert (np.asarray(pc) == ec).all()
    assert (np.asarray(pl_) == el).all()


def test_make_mask_worker_routes_to_kernel(monkeypatch):
    """With DPRF_PALLAS=1: single-target sha1 routes to the kernel;
    multi-target routes to the kernel ONLY when an oracle is available
    to verify Bloom maybes; SHA-256 stays on the XLA pipeline off-TPU
    (its unrolled kernel graph is Mosaic-only, see kernel_eligible)."""
    monkeypatch.setenv("DPRF_PALLAS", "1")
    gen = MaskGenerator("?l?l?l")
    eng = get_engine("sha1", device="jax")
    t1 = eng.parse_target(hashlib.sha1(b"abc").hexdigest())
    t2 = eng.parse_target(hashlib.sha1(b"xyz").hexdigest())
    w1 = eng.make_mask_worker(gen, [t1], batch=TILE, hit_capacity=8)
    assert isinstance(w1, PallasMaskWorker)
    w2 = eng.make_mask_worker(gen, [t1, t2], batch=TILE, hit_capacity=8)
    assert not isinstance(w2, PallasMaskWorker)      # no oracle
    w2o = eng.make_mask_worker(gen, [t1, t2], batch=TILE, hit_capacity=8,
                               oracle=get_engine("sha1"))
    assert isinstance(w2o, PallasMaskWorker) and w2o.multi
    e256 = get_engine("sha256", device="jax")
    t3 = e256.parse_target(hashlib.sha256(b"abc").hexdigest())
    w3 = e256.make_mask_worker(gen, [t3], batch=TILE, hit_capacity=8)
    assert not isinstance(w3, PallasMaskWorker)      # cpu backend


@pytest.mark.parametrize("n,width", [(2, 4), (1000, 4), (2500, 5),
                                     (8192, 8)])
def test_probe_rows_never_false_negative(n, width):
    """Every target's own digest survives the in-kernel probe over the
    rows built from the list -- a real hit can never be filtered out --
    and a uniform digest that is no target rarely does."""
    from dprf_tpu.ops.pallas_mask import (kernel_probe_rows,
                                          probe_block_found)

    rng = np.random.default_rng(7)
    tw = rng.integers(0, 1 << 32, size=(n, width),
                      dtype=np.uint64).astype(np.uint32)
    rows, block_bits, k, n_grp, fp_est = kernel_probe_rows(tw)
    assert rows.shape[1] == 128 and rows.dtype == np.uint32
    assert 0 < fp_est < 1e-4

    def survivors(words):
        # the kernel's (sub, 128) tile layout, padded with copies
        m = -(-len(words) // 128) * 128
        pad = np.concatenate([words, np.repeat(words[:1], m - len(words),
                                               axis=0)])
        shape = (m // 128, 128)
        digest = [jnp.asarray(pad[:, j].reshape(shape))
                  for j in range(width)]
        found = probe_block_found(digest, jnp.asarray(rows),
                                  jnp.ones(shape, jnp.bool_), block_bits,
                                  k, n_grp, shape)
        return np.asarray(found).reshape(-1)[:len(words)]

    assert survivors(tw).all()
    others = rng.integers(0, 1 << 32, size=(1 << 14, width),
                          dtype=np.uint64).astype(np.uint32)
    assert survivors(others).sum() <= 2


def test_probe_rows_refuse_more_than_the_kernel_cap():
    from dprf_tpu.ops.pallas_mask import MAX_TARGETS, kernel_probe_rows

    tw = np.zeros((MAX_TARGETS + 1, 4), np.uint32)
    with pytest.raises(ValueError, match="targets"):
        kernel_probe_rows(tw)


def _multi_targets(engine_name, eng, plants, n_fill=1000, seed=3):
    """Parse targets for planted passwords + n_fill random off-keyspace
    digests (Bloom fillers that can never hit)."""
    rng = np.random.default_rng(seed)
    raws = [
        _engine_target(engine_name, p).astype(
            "<u4" if eng.little_endian else ">u4").tobytes().hex()
        for p in plants]
    W = len(_engine_target(engine_name, b"x"))
    for _ in range(n_fill):
        raws.append(rng.bytes(4 * W).hex())
    return [eng.parse_target(r) for r in raws]


@pytest.mark.parametrize("engine", ["md5", "ntlm"])
def test_pallas_multi_target_matches_xla(engine):
    """The multi-target kernel path (in-kernel prefilter + oracle
    verification) must match the XLA multi-target path hit-for-hit on
    a 1k-target list, including a deliberate two-hits-in-one-tile
    collision."""
    from dprf_tpu.runtime.worker import DeviceMaskWorker

    gen = MaskGenerator("?l?l?l?l")
    # tiles: 0 holds two planted hits (collision -> tile rescan),
    # 2 and 5 hold one isolated hit each (single-maybe -> oracle verify)
    plant_idx = [7, 2000, 2 * TILE + 11, 5 * TILE + 4095]
    plants = [gen.candidate(i) for i in plant_idx]
    eng = get_engine(engine, device="jax")
    oracle = get_engine(engine)
    targets = _multi_targets(engine, eng, plants)

    pworker = PallasMaskWorker(eng, gen, targets, batch=2 * TILE,
                               hit_capacity=8, oracle=oracle,
                               interpret=True)
    assert pworker.multi
    unit = WorkUnit(0, 0, 6 * TILE)
    phits = sorted((h.target_index, h.cand_index, h.plaintext)
                   for h in pworker.process(unit))
    xworker = DeviceMaskWorker(eng, gen, targets, batch=2 * TILE,
                               hit_capacity=8, oracle=oracle)
    xhits = sorted((h.target_index, h.cand_index, h.plaintext)
                   for h in xworker.process(unit))
    assert phits == xhits
    assert [c for _, c, _ in phits] == plant_idx
    assert [p for _, _, p in phits] == plants


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["real-chip", "interpret"])
@pytest.mark.parametrize("where", ["build", "warmup"])
def test_make_mask_worker_kernel_failure_raises(monkeypatch, capsys,
                                                interpret, where):
    """A kernel that fails to build (construction) or to compile (the
    factory's warmup forces it) RAISES with the compiler's message --
    under a real-chip pallas_mode() above all: the XLA pipeline is far
    slower, so a quiet switch to it would be a wrong result that still
    passes.  No fallback warning, no DeviceMaskWorker."""
    import dprf_tpu.ops.pallas_mask as pm
    import dprf_tpu.runtime.worker as worker_mod

    monkeypatch.setattr(pm, "pallas_mode",
                        lambda: {"interpret": interpret})

    class Boom(worker_mod.PallasMaskWorker):
        def __init__(self, *a, **kw):
            if where == "build":
                raise RuntimeError("injected Mosaic lowering failure")
            self._warmed = False

        def warmup(self):
            raise RuntimeError("injected Mosaic lowering failure")

    monkeypatch.setattr(worker_mod, "PallasMaskWorker", Boom)
    gen = MaskGenerator("?l?l?l")
    eng = get_engine("sha1", device="jax")
    t1 = eng.parse_target(hashlib.sha1(b"abc").hexdigest())
    with pytest.raises(RuntimeError, match="injected Mosaic lowering"):
        eng.make_mask_worker(gen, [t1], batch=TILE, hit_capacity=8)
    assert "falling back" not in capsys.readouterr().err


def test_kind_kernel_step_raises_build_and_compile_failures():
    """The per-target-sweep helper (pdf/7z/krb5aes): same rule."""
    from dprf_tpu.engines.device._kernel_util import kind_kernel_step

    def boom():
        raise RuntimeError("mosaic says no")

    with pytest.raises(RuntimeError, match="mosaic says no"):
        kind_kernel_step(boom, lambda step: None)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        kind_kernel_step(lambda: object(), lambda step: boom())
    marker = object()
    assert kind_kernel_step(lambda: marker, lambda step: None) is marker


@pytest.mark.smoke
def test_sha512_rounds_unrolled_matches_loop_form():
    """The statically-unrolled pair-arithmetic rounds (the Mosaic
    form the kernel core uses) must be bit-identical to the fori_loop
    XLA form on random full blocks."""
    from dprf_tpu.ops import sha512 as s5

    rng = np.random.default_rng(3)
    words = jnp.asarray(rng.integers(0, 2 ** 32, (4, 32),
                                     dtype=np.uint32))
    ref = s5.sha512_compress(s5.INIT512, words)
    pairs = [(words[:, 2 * i], words[:, 2 * i + 1]) for i in range(16)]
    init = [(jnp.uint32(v >> 32), jnp.uint32(v & 0xFFFFFFFF))
            for v in s5.INIT512]
    vars8 = tuple((jnp.full((4,), h), jnp.full((4,), l))
                  for h, l in init)
    out = s5.sha512_rounds(vars8, pairs)
    got = []
    for v, iv in zip(out, init):
        h, l = s5._add64(v, iv)
        got.extend([h, l])
    assert np.array_equal(np.stack([np.asarray(g) for g in got], -1),
                          np.asarray(ref))


@pytest.mark.smoke
def test_position_tables_mixes_segments_and_luts():
    """Builtin charsets stay on the arithmetic mux; scrambled orders
    (Markov permutations) become lane-axis LUT inputs."""
    from dprf_tpu.ops.pallas_mask import position_tables

    scrambled = bytes(dict.fromkeys(
        b"qazwsxedcrfvtgbyhnujmikolp"))            # 26 letters, shuffled
    proc, luts = position_tables([BUILTIN_CHARSETS["l"], scrambled])
    assert isinstance(proc[0], list)               # arithmetic segments
    assert proc[1] == ("lut", 0)                   # LUT marker
    assert luts.shape == (2, 128)
    # LUT rows reconstruct the charset exactly
    assert bytes(int(luts.reshape(-1)[d]) for d in
                 range(len(scrambled))) == scrambled
    # all-arithmetic masks carry no LUT input
    proc2, luts2 = position_tables([BUILTIN_CHARSETS["l"]])
    assert luts2 is None and isinstance(proc2[0], list)


def test_kernel_finds_planted_markov_mask():
    """A Markov-permuted mask (arbitrary charset order at every
    position) rides the kernel via the LUT decode: planted password
    found at its exact index in interpret mode."""
    from dprf_tpu.ops.pallas_mask import position_tables

    counts = np.zeros((4, 256), np.uint64)
    rng = np.random.RandomState(11)
    counts[:, :] = rng.randint(1, 10**6, (4, 256))
    gen = MaskGenerator("?l?l?d?d", markov_counts=counts)
    proc, luts = position_tables(gen.charsets)
    assert luts is not None, \
        "the permutation should exceed the segment budget"
    plant = gen.candidate(12345)
    pidx = 12345
    step = make_pallas_mask_crack_step("md5", gen,
                                       _engine_target("md5", plant),
                                       batch=TILE, interpret=True)
    base = TILE * (pidx // TILE)
    bd = jnp.asarray(gen.digits(base), dtype=jnp.int32)
    count, lanes, _ = step(bd, jnp.int32(min(TILE, gen.keyspace - base)))
    assert int(count) == 1
    assert int(np.asarray(lanes)[0]) == pidx - base


def test_markov_worker_routes_to_kernel(monkeypatch):
    """DPRF_PALLAS=1: a Markov-ordered mask job gets the Pallas worker
    (pre-r5 it fell back to the XLA pipeline) and cracks end-to-end."""
    monkeypatch.setenv("DPRF_PALLAS", "1")
    counts = np.zeros((3, 256), np.uint64)
    rng = np.random.RandomState(7)
    counts[:, :] = rng.randint(1, 10**6, (3, 256))
    gen = MaskGenerator("?l?d?l", markov_counts=counts)
    secret = gen.candidate(404)
    eng = get_engine("md5", device="jax")
    t = eng.parse_target(hashlib.md5(secret).hexdigest())
    w = eng.make_mask_worker(gen, [t], batch=TILE, hit_capacity=8,
                             oracle=get_engine("md5", device="cpu"))
    assert isinstance(w, PallasMaskWorker)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.cand_index, h.plaintext)
            for h in hits] == [(0, 404, secret)]


@pytest.mark.smoke
def test_unbounded_segment_decode_matches_oracle():
    """The heavy kernel families (krb5/pdf/7z/pbkdf2) decode Markov/
    scrambled charsets through the UNBOUNDED segment mux
    (segment_tables): eager decode_candidate_bytes must reproduce the
    generator's candidates byte-for-byte, and the families' eligibility
    predicates must now admit such masks."""
    from dprf_tpu.ops.pallas_7z import sevenzip_kernel_eligible
    from dprf_tpu.ops.pallas_krb5 import krb5_kernel_eligible
    from dprf_tpu.ops.pallas_mask import (decode_candidate_bytes,
                                          segment_tables)
    from dprf_tpu.ops.pallas_pdf import pdf_kernel_eligible

    counts = np.zeros((3, 256), np.uint64)
    rng = np.random.RandomState(3)
    counts[:, :] = rng.randint(1, 10**6, (3, 256))
    gen = MaskGenerator("?l?l?d", markov_counts=counts)
    tabs = segment_tables(gen.charsets)
    assert any(len(t) > 16 for t in tabs)     # really past the budget
    base = jnp.asarray(gen.digits(100), jnp.int32)
    lane = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    byts = decode_candidate_bytes(gen.radices, tabs, gen.length,
                                  base, jnp.int32(0), lane, 16)
    got = np.stack([np.asarray(b) for b in byts], axis=-1).reshape(16, 3)
    want = np.stack([np.frombuffer(gen.candidate(100 + i), np.uint8)
                     for i in range(16)])
    assert (got == want).all()
    assert krb5_kernel_eligible(gen)
    assert pdf_kernel_eligible(gen, 3, 16)
    assert sevenzip_kernel_eligible(gen, 19, 2)


# -- the odometer decode (PR 33) ---------------------------------------------

def _top(mask: str, back: int = 0) -> int:
    """The keyspace's last index (every digit r - 1), less `back`."""
    return MaskGenerator(mask).keyspace - 1 - back


#: id -> (mask, Markov seed or None, unbounded segment mux?, sub, index
#: of the base digits, scalar start).  K is the count of low positions
#: a lane index below sub * 128 has digits in (lane_digit_count): 3 for
#: ?l x9 and ?a x7 at sub 128.
DECODE_CASES = {
    # all base digits r - 1: lane 0 is the last candidate and every
    # lane past it wraps as the kernel's index does
    "l9-all-top": ("?l" * 9, None, False, 128, _top("?l" * 9), 0),
    # the carry runs through all six upper positions at lane 5001, with
    # a start that is no multiple of the tile
    "l9-carry-mid-tile": ("?l" * 9, None, False, 128,
                          _top("?l" * 9, 5000 + 3 * 16384 + 777),
                          3 * 16384 + 777),
    "a7-all-top": ("?a" * 7, None, False, 128, _top("?a" * 7), 0),
    "a7-carry-mid-tile": ("?a" * 7, None, False, 128,
                          _top("?a" * 7, 9000 + 16384 + 5), 16384 + 5),
    # a unit's last tile: pid * tile + offset just under 2^28
    "l9-start-near-2^28": ("?l" * 9, None, False, 128, 26 ** 8 + 12345,
                           (1 << 28) - 16384 - 77),
    "a7-start-near-2^28": ("?a" * 7, None, False, 128,
                           95 ** 6 * 94 + 4321, (1 << 28) - 16384),
    "mixed-radices": ("?d?l?a?u?d?l", None, False, 128,
                      10 * 26 * 95 * 26 * 9 + 17, 16384 * 5 + 1),
    # radix 1 among the low positions (c, b) and the upper ones (a)
    "fixed-characters": ("?la?l?l?lb?lc?d", None, False, 128,
                         26 ** 4 * 10 * 3 + 25 * 26 * 10 + 99,
                         16384 * 7 + 333),
    # K would be 3: the mask has two positions and 100 candidates
    "shorter-than-K": ("?d?d", None, False, 128, 37, 0),
    "keyspace-under-a-tile": ("?l?l", None, False, 8, 600, 50),
    "markov-segment-mux": ("?l?d?l?l?l", 11, True, 128,
                           26 * 10 * 26 * 26 * 25 + 26 * 26 * 9, 16384),
    "markov-lut-rows": ("?l?d?l?l?l", 12, False, 128,
                        26 * 10 * 26 * 26 * 25 + 26 * 26 * 9, 16384 + 9),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_odometer_decode_matches_generator(case):
    """decode_candidate_bytes (scalar tile digits, multiply-and-shift lane
    digits, compare-and-subtract) against MaskGenerator.candidate,
    byte for byte over a whole tile; an index past the keyspace wraps,
    as the kernel's always has (`valid` masks the lane)."""
    import jax
    from dprf_tpu.ops.pallas_mask import (decode_candidate_bytes,
                                          position_tables, segment_tables)
    mask, seed, unbounded, sub, index, start = DECODE_CASES[case]
    counts = None
    if seed is not None:
        counts = np.random.RandomState(seed).randint(
            1, 10**6, (len(mask) // 2, 256)).astype(np.uint64)
    gen = MaskGenerator(mask, markov_counts=counts)
    if unbounded:
        tabs, luts = segment_tables(gen.charsets), None
        assert max(len(t) for t in tabs) > MAX_SEGMENTS
    else:
        tabs, luts = position_tables(gen.charsets)
        assert (luts is not None) == (seed is not None)
    shape = (sub, 128)
    lane = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    byts = decode_candidate_bytes(
        gen.radices, tabs, gen.length,
        jnp.asarray(gen.digits(index), jnp.int32), jnp.int32(start),
        lane, sub * 128, None if luts is None else jnp.asarray(luts))
    assert all(b.shape == shape and b.dtype == jnp.uint32 for b in byts)
    got = np.stack([np.asarray(b).reshape(-1) for b in byts], axis=-1)
    want = np.stack([
        np.frombuffer(gen.candidate((index + start + i) % gen.keyspace),
                      np.uint8) for i in range(sub * 128)])
    assert (got == want).all()


def test_lane_digit_quotients_are_exact_for_every_radix():
    """The lane digits come from _quotient, (n * m) >> sh in int32:
    exact, and free of overflow, for every radix a byte charset can
    have and every lane index a tile can hold (decode_candidate_bytes'
    own limit, 2^14)."""
    from dprf_tpu.ops.pallas_mask import _quotient
    n = np.arange(1 << 14, dtype=np.int32)
    for r in range(2, 257):
        assert (_quotient(n, r) == n // r).all(), r
        # and the product never left int32
        assert (_quotient(n.astype(np.int64), r) == n // r).all(), r


def _tile_equations(jaxpr, shape, counts=None):
    """primitive -> equations of `jaxpr` (nested ones included) whose
    result is a `shape` tile."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        nested = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                  if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        for sub_jaxpr in nested:
            _tile_equations(sub_jaxpr, shape, counts)
        if not nested and any(getattr(v.aval, "shape", None) == shape
                              for v in eqn.outvars):
            name = eqn.primitive.name
            counts[name] = counts.get(name, 0) + 1
    return counts


#: the cells' kernel bodies at sub 128: id -> (engine, mask, targets
#: (None: the bulk list's body, which ends at the digest), the most
#: tile equations the decode may be, the most the body may be).  With
#: the division form the counts were 163 of 879 (md5-mask) and 253 of
#: 746 (ntlm-1m), 27 and 21 of them `div` / `rem` (CPU count, PR 33).
BODY_COUNTS = {
    "md5-l9-one-target": ("md5", "?l" * 9, 1, 64, 790),
    "ntlm-a7-digest": ("ntlm", "?a" * 7, None, 110, 610),
    "ntlm-a7-1000-targets": ("ntlm", "?a" * 7, 1000, 110, 1020),
}


@pytest.mark.parametrize("case", sorted(BODY_COUNTS))
def test_kernel_body_has_no_vector_division(case):
    """A count that keeps the odometer in place: the cells' kernel
    bodies hold no `div` and no `rem` whose result is a tile, and the
    decode stays a small share of the body's tile equations."""
    import jax
    from dprf_tpu.ops import pallas_mask as pm
    engine, mask, n_targets, decode_max, body_max = BODY_COUNTS[case]
    sub = 128
    gen = MaskGenerator(mask)
    tabs, _ = pm.position_tables(gen.charsets)
    rng = np.random.RandomState(1)
    words = rng.randint(0, 1 << 32, (n_targets or 1, 4),
                        dtype=np.uint64).astype(np.uint32)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    base = jax.ShapeDtypeStruct((gen.length,), jnp.int32)
    if n_targets is None:
        body = pm._build_kernel_body(engine, gen.radices, tabs,
                                     gen.length, None, sub)
        jaxpr = jax.make_jaxpr(
            lambda pid, b, off: body.hashed_lanes(pid, b, None, off)[0])(
                i32, base, i32)
    elif n_targets == 1:
        body = pm._build_kernel_body(engine, gen.radices, tabs,
                                     gen.length, words[0], sub)
        jaxpr = jax.make_jaxpr(body)(i32, base, i32)
    else:
        rows, block_bits, k, n_grp, _ = pm.kernel_probe_rows(words)
        body = pm._build_kernel_body(engine, gen.radices, tabs,
                                     gen.length, words, sub,
                                     probe=(block_bits, k, n_grp))
        jaxpr = jax.make_jaxpr(body)(i32, base, i32, jnp.asarray(rows))
    shape = (sub, 128)
    whole = _tile_equations(jaxpr.jaxpr, shape)
    assert not {"div", "rem"} & set(whole), whole
    decode = _tile_equations(jax.make_jaxpr(
        lambda b, start, lane: pm.decode_candidate_bytes(
            gen.radices, tabs, gen.length, b, start, lane, sub * 128))(
                base, i32, jax.ShapeDtypeStruct(shape, jnp.int32)).jaxpr,
        shape)
    assert not {"div", "rem"} & set(decode), decode
    assert sum(decode.values()) <= decode_max, decode
    assert sum(whole.values()) <= body_max, whole
    assert sum(decode.values()) < 0.20 * sum(whole.values())
