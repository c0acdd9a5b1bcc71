"""Device bcrypt (EksBlowfish) vs the CPU oracle and OpenBSD vectors.

Covers: raw digest equivalence over random candidates, the device
hash_batch against classic $2a$05 vectors, and both fused workers
(wordlist+rules and mask) end-to-end with planted passwords.  Costs are
kept at 4-5 (16-32 rounds) so the serial chains stay test-sized; the
chain structure is identical at cost 12.
"""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dprf_tpu.engines import get_engine
from dprf_tpu.engines.cpu.bcrypt import bcrypt_hash, bcrypt_raw
from dprf_tpu.ops import blowfish as bf_ops
from dprf_tpu.runtime.workunit import WorkUnit


def _pack(cands):
    L = max(len(c) for c in cands)
    buf = np.zeros((len(cands), L), np.uint8)
    lens = np.zeros((len(cands),), np.int32)
    for i, c in enumerate(cands):
        buf[i, :len(c)] = np.frombuffer(c, np.uint8)
        lens[i] = len(c)
    return jnp.asarray(buf), jnp.asarray(lens)


def test_bcrypt_batch_matches_oracle():
    rng = random.Random(0xbc)
    cands = [bytes(rng.randrange(1, 256) for _ in range(rng.randrange(0, 24)))
             for _ in range(12)]
    salt = bytes(rng.randrange(256) for _ in range(16))
    cost = 4
    cand, lens = _pack(cands)
    dw = jax.jit(bf_ops.bcrypt_batch)(
        cand, lens, jnp.asarray(bf_ops.salt_to_words(salt)),
        jnp.int32(1 << cost))
    got = bf_ops.words_to_digests(np.asarray(dw))
    for g, c in zip(got, cands):
        assert g == bcrypt_raw(c, salt, cost), c


def test_cost_is_runtime_arg():
    """One compiled program must serve different costs (the trip count
    is a traced argument, not a constant baked into the executable)."""
    fn = jax.jit(bf_ops.bcrypt_batch)
    cand, lens = _pack([b"hunter2"])
    salt = bytes(range(16))
    sw = jnp.asarray(bf_ops.salt_to_words(salt))
    for cost in (4, 5):
        dw = fn(cand, lens, sw, jnp.int32(1 << cost))
        assert bf_ops.words_to_digests(np.asarray(dw))[0] == \
            bcrypt_raw(b"hunter2", salt, cost)


@pytest.mark.parametrize("password,line", [
    (b"U*U", "$2a$05$CCCCCCCCCCCCCCCCCCCCC.E5YPO9kmyuRGyh0XouQYb4YMJKvyOeW"),
    (b"U*U*U", "$2a$05$XXXXXXXXXXXXXXXXXXXXXOAcXxm9kjPGEMsLznoKqmqw7tc8WCx4a"),
])
@pytest.mark.smoke
def test_device_hash_batch_openbsd_vectors(password, line):
    eng = get_engine("bcrypt", device="jax")
    t = eng.parse_target(line)
    [digest] = eng.hash_batch([password], params=t.params)
    assert digest == t.digest


def test_device_hash_batch_vs_oracle_batch():
    eng = get_engine("bcrypt", device="jax")
    salt = b"0123456789abcdef"
    params = {"salt": salt, "cost": 4}
    cands = [b"", b"a", b"password", b"x" * 23]
    got = eng.hash_batch(cands, params=params)
    want = get_engine("bcrypt").hash_batch(cands, params=params)
    assert got == want


def test_device_rejects_cost_31():
    """Cost 31 is legal bcrypt but 2**31 overflows the int32 loop
    bound; the device engine must refuse loudly, not wrap to a
    zero-iteration loop (silent false negatives)."""
    from dprf_tpu.engines.device.bcrypt import _n_rounds
    with pytest.raises(ValueError, match="4..30"):
        _n_rounds(31)
    with pytest.raises(ValueError):
        get_engine("bcrypt", device="jax").hash_batch(
            [b"x"], params={"salt": b"0123456789abcdef", "cost": 31})


def test_parse_rejects_out_of_range_cost():
    with pytest.raises(ValueError):
        get_engine("bcrypt").parse_target(
            "$2b$03$KBCwKxOzLha2MUDgW0PjXeFaAPh7cxmjSZ5c00P8D0A2tzxy8Lhdy")


def test_bcrypt_wordlist_worker_finds_planted():
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.rules.parser import parse_rule

    words = [b"alpha", b"bravo", b"s3cret", b"delta", b"echo"]
    rules = [parse_rule(":"), parse_rule("u"), parse_rule("$1")]
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    cost = 4
    salt = b"fedcba9876543210"
    eng = get_engine("bcrypt", device="jax")
    # plant "S3CRET" (rule u on word 2) and "echo1" (rule $1 on word 4)
    targets = [eng.parse_target(bcrypt_hash(b"S3CRET", salt, cost)),
               eng.parse_target(bcrypt_hash(b"echo1", salt, cost))]
    worker = eng.make_wordlist_worker(gen, targets, batch=8,
                                      hit_capacity=8,
                                      oracle=get_engine("bcrypt"))
    hits = worker.process(WorkUnit(0, 0, gen.keyspace))
    got = {(h.target_index, h.plaintext) for h in hits}
    assert got == {(0, b"S3CRET"), (1, b"echo1")}
    assert {h.cand_index for h in hits} == \
        {gen.index_of(2, 1), gen.index_of(4, 2)}


def test_bcrypt_mask_worker_finds_planted():
    from dprf_tpu.generators.mask import MaskGenerator

    gen = MaskGenerator("?d?d")
    cost = 4
    salt = b"0123456789abcdef"
    eng = get_engine("bcrypt", device="jax")
    targets = [eng.parse_target(bcrypt_hash(b"42", salt, cost))]
    worker = eng.make_mask_worker(gen, targets, batch=32, hit_capacity=8,
                                  oracle=None)
    hits = worker.process(WorkUnit(0, 0, gen.keyspace))
    assert len(hits) == 1
    assert hits[0].plaintext == b"42"
    assert hits[0].target_index == 0


@pytest.mark.smoke
@pytest.mark.compileheavy    # two full EKS program compiles (~1 min)
def test_chunked_eks_matches_fused():
    """Splitting the cost loop across arbitrary dispatch boundaries must
    reproduce the one-shot eks_setup state exactly (the chunked path is
    how cost >= 10 runs in production: one dispatch per time budget, not
    one per batch -- see ChunkedEks)."""
    rng = np.random.default_rng(7)
    kw = jnp.asarray(rng.integers(0, 2**32, (4, 18), dtype=np.uint32))
    sw = jnp.asarray(rng.integers(0, 2**32, (4,), dtype=np.uint32))
    n = 32                                    # cost 5
    P1, S1 = bf_ops.eks_setup(kw, sw, jnp.int32(n))
    want = np.asarray(bf_ops.bcrypt_digest_words(P1, S1))

    salt18 = bf_ops.salt18_words(sw)
    P, S = bf_ops.eks_setup_begin(kw, sw)
    for chunk in (1, 16, 5, 10):              # uneven split of 32
        P, S = bf_ops.eks_rounds(P, S, kw, salt18, jnp.int32(chunk))
    got = np.asarray(bf_ops.bcrypt_digest_words(P, S))
    np.testing.assert_array_equal(got, want)


def test_chunked_worker_many_dispatches_finds_planted():
    """A dispatch budget far below one chunk's calibration time forces
    the worker down to 1-round dispatches; the sweep must still find the
    planted password (state carries across dispatch boundaries)."""
    from dprf_tpu.generators.mask import MaskGenerator

    gen = MaskGenerator("?d?d")
    salt = b"0123456789abcdef"
    eng = get_engine("bcrypt", device="jax")
    targets = [eng.parse_target(bcrypt_hash(b"73", salt, 4))]
    worker = eng.make_mask_worker(gen, targets, batch=128, hit_capacity=8,
                                  oracle=None)
    worker.chunker.dispatch_s = 1e-9          # force minimum chunks
    hits = worker.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, b"73")]
    # calibration chunk (16) + 1-round tail dispatches
    assert worker.chunker._per_round is not None


def test_chunked_growth_cap():
    """One optimistic per-round estimate must not jump the chunk size
    straight past the deadline: growth is capped at 8x per dispatch."""
    from dprf_tpu.engines.device.bcrypt import ChunkedEks

    c = ChunkedEks(dispatch_s=100.0)
    assert c._next_chunk(1 << 20, 16) == 16   # calibration first
    c._per_round = 1e-6                       # looks 1e8-rounds-cheap
    assert c._next_chunk(1 << 30, 16) == 128  # 16 * 8, not 1e8
    assert c._next_chunk(100, 1 << 20) == 100  # remaining clamps


def test_pallas_eks_advance_matches_xla():
    """The Pallas EksBlowfish advance kernel (ops/pallas_bcrypt.py) is
    bit-exact vs the XLA form over the ChunkedEks advance contract
    (interpret mode; on the chip the kernel runs in chip_smoke.py's
    bcrypt phase)."""
    import numpy as np
    import jax.numpy as jnp

    from dprf_tpu.ops import blowfish as bf
    from dprf_tpu.ops.pallas_bcrypt import make_pallas_eks_advance

    B = 8
    rng = np.random.RandomState(0)
    cand = rng.randint(97, 123, (B, 6), dtype=np.uint8)
    kw = bf.key_words_from_candidates(jnp.asarray(cand),
                                      jnp.full((B,), 6, jnp.int32))
    sw = jnp.asarray(np.frombuffer(bytes(range(16)), ">u4")
                     .astype(np.uint32))
    P, S = bf.eks_setup_begin(kw, sw)
    s18 = bf.salt18_words(sw)
    n = jnp.int32(2)
    P_ref, S_ref = bf.eks_rounds(P, S, kw, s18, n)
    adv = make_pallas_eks_advance(B, interpret=True, subc=8)
    P_k, S_k = adv(P, S, kw, s18, n)
    assert np.array_equal(np.asarray(P_ref), np.asarray(P_k))
    assert np.array_equal(np.asarray(S_ref), np.asarray(S_k))


def test_bcrypt_route_forced_cpu_cracks(monkeypatch):
    """DPRF_BCRYPT_ROUTE=cpu returns the routed CPU worker from the
    device factory and it still cracks a planted target."""
    from dprf_tpu.engines.device.bcrypt import RoutedCpuBcryptWorker
    from dprf_tpu.generators.mask import MaskGenerator

    monkeypatch.setenv("DPRF_BCRYPT_ROUTE", "cpu")
    gen = MaskGenerator("?d?d")
    cpu = get_engine("bcrypt", device="cpu")
    dev = get_engine("bcrypt", device="jax")
    salt = bytes(range(16))
    t = cpu.parse_target(bcrypt_hash(b"42", salt, 4))
    w = dev.make_mask_worker(gen, [t], batch=64, hit_capacity=8,
                             oracle=cpu)
    assert isinstance(w, RoutedCpuBcryptWorker)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, b"42")]


def test_bcrypt_route_forced_device(monkeypatch):
    from dprf_tpu.engines.device.bcrypt import BcryptMaskWorker
    from dprf_tpu.generators.mask import MaskGenerator

    monkeypatch.setenv("DPRF_BCRYPT_ROUTE", "device")
    gen = MaskGenerator("?d?d")
    cpu = get_engine("bcrypt", device="cpu")
    dev = get_engine("bcrypt", device="jax")
    t = cpu.parse_target(bcrypt_hash(b"xx", bytes(range(16)), 4))
    w = dev.make_mask_worker(gen, [t], batch=64, hit_capacity=8,
                             oracle=cpu)
    assert isinstance(w, BcryptMaskWorker)
    # off the chip the cost loop is the XLA form, and the job's log
    # says so: the same worker class carries either implementation
    from dprf_tpu.runtime.worker import describe_worker
    ran = describe_worker(w)
    assert ran["advance"] == "xla" and ran["interpret"] == "n/a"


def test_bcrypt_worker_says_when_its_cost_loop_is_the_kernel(monkeypatch):
    """With the kernel as its advance the worker reports advance=pallas,
    interpret=False and the kernel's compile: chip_smoke.py's bcrypt
    phase fails on anything else."""
    import jax

    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.ops import blowfish as bf_ops
    from dprf_tpu.ops import pallas_bcrypt
    from dprf_tpu.runtime.worker import describe_worker

    monkeypatch.setattr(
        pallas_bcrypt, "make_best_eks_advance",
        lambda batch: (jax.jit(bf_ops.eks_rounds), "pallas"))
    monkeypatch.setenv("DPRF_BCRYPT_ROUTE", "device")
    dev = get_engine("bcrypt", device="jax")
    t = dev.parse_target(bcrypt_hash(b"xx", bytes(range(16)), 4))
    w = dev.make_mask_worker(MaskGenerator("?d?d"), [t], batch=64,
                             hit_capacity=8, oracle=None)
    ran = describe_worker(w)
    assert ran["advance"] == "pallas" and ran["interpret"] is False
    assert ran["cache"] in ("hit", "miss", "off")


def test_measure_eks_rates_runs():
    """The routing micro-bench returns positive head-to-head rates."""
    from dprf_tpu.engines.device.bcrypt import measure_eks_rates

    cpu = get_engine("bcrypt", device="cpu")
    rates = measure_eks_rates(cpu, batch=8, rounds=2)
    assert rates["device_cand_rounds_s"] > 0
    assert rates["cpu_cand_rounds_s"] > 0
