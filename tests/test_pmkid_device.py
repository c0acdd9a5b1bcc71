"""Device PBKDF2-HMAC-SHA1 / WPA2-PMKID vs stdlib oracles.

Covers: RFC 6070 PBKDF2 vectors, random-candidate equivalence with
hashlib.pbkdf2_hmac, PMKID equivalence with the CPU oracle engine, and
the fused PMKID worker end-to-end (planted passphrase, multi-essid).
"""

import hashlib
import hmac as hmac_mod
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# device-pipeline compiles: full suite / tier-1, excluded from the <5-min
# smoke tier (tools/check_markers.py enforces an explicit tier decision)
pytestmark = pytest.mark.compileheavy

from dprf_tpu.engines import get_engine
from dprf_tpu.engines.device.pmkid import (JaxPmkidEngine,
                                           PmkidDeviceWorker)
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.ops import pack as pack_ops
from dprf_tpu.ops.hmac_sha1 import (hmac_key_states, hmac_sha1_20,
                                    pbkdf2_sha1_block, pbkdf2_sha1_pmk,
                                    pmkid_from_pmk)
from dprf_tpu.runtime.workunit import WorkUnit


def _pack_keys(keys: list) -> jnp.ndarray:
    maxlen = max(len(k) for k in keys)
    buf = np.zeros((len(keys), maxlen), dtype=np.uint8)
    for i, k in enumerate(keys):
        buf[i, :len(k)] = np.frombuffer(k, dtype=np.uint8)
    # zero padding beyond each key is exactly the HMAC key-block rule as
    # long as every key has the same length; tests use equal lengths.
    assert all(len(k) == maxlen for k in keys)
    return pack_ops.pack_raw(jnp.asarray(buf), maxlen, big_endian=True)


def _words_to_bytes(w: np.ndarray) -> bytes:
    return np.asarray(w).astype(">u4").tobytes()


def test_hmac_sha1_20_matches_stdlib():
    keys = [bytes([random.randrange(256) for _ in range(16)])
            for _ in range(32)]
    msg = bytes(range(20))
    kw = _pack_keys(keys)
    istate, ostate = hmac_key_states(kw)
    msg5 = jnp.broadcast_to(
        jnp.asarray(np.frombuffer(msg, dtype=">u4").astype(np.uint32)),
        (len(keys), 5))
    got = hmac_sha1_20(istate, ostate, msg5)
    for i, k in enumerate(keys):
        want = hmac_mod.new(k, msg, hashlib.sha1).digest()
        assert _words_to_bytes(got[i]) == want


@pytest.mark.parametrize("password,salt,iters,dk20", [
    # RFC 6070 test vectors (PBKDF2-HMAC-SHA1, dkLen=20)
    (b"password", b"salt", 1,
     "0c60c80f961f0e71f3a9b524af6012062fe037a6"),
    (b"password", b"salt", 2,
     "ea6c014dc72d6f8ccd1ed92ace1d41f0d8de8957"),
    (b"password", b"salt", 4096,
     "4b007901b765489abead49d926f721d065a429c1"),
])
def test_pbkdf2_rfc6070_vectors(password, salt, iters, dk20):
    kw = _pack_keys([password])
    istate, ostate = hmac_key_states(kw)
    t1 = pbkdf2_sha1_block(istate, ostate, salt, 1, iters)
    assert _words_to_bytes(t1[0]) == bytes.fromhex(dk20)


def test_pbkdf2_pmk_matches_hashlib():
    rng = random.Random(7)
    pws = [bytes(rng.randrange(0x21, 0x7F) for _ in range(10))
           for _ in range(8)]
    essid = b"TestNet-5G"
    got = pbkdf2_sha1_pmk(_pack_keys(pws), essid, iterations=128)
    for i, pw in enumerate(pws):
        want = hashlib.pbkdf2_hmac("sha1", pw, essid, 128, 32)
        assert _words_to_bytes(got[i]) == want


def test_full_4096_iteration_pmk():
    pw = b"password"
    essid = b"linksys"
    got = pbkdf2_sha1_pmk(_pack_keys([pw]), essid, iterations=4096)
    want = hashlib.pbkdf2_hmac("sha1", pw, essid, 4096, 32)
    assert _words_to_bytes(got[0]) == want


def test_pmkid_matches_cpu_oracle():
    oracle = get_engine("wpa2-pmkid", device="cpu")
    pw = b"hunter2hunter2"
    essid, ap, sta = b"CoffeeShop", bytes(range(6)), bytes(range(6, 12))
    pmk = hashlib.pbkdf2_hmac("sha1", pw, essid, 4096, 32)
    pmk_words = jnp.asarray(
        np.frombuffer(pmk, dtype=">u4").astype(np.uint32))[None, :]
    got = pmkid_from_pmk(pmk_words, ap, sta)
    want = oracle.hash_batch(
        [pw], params={"essid": essid, "mac_ap": ap, "mac_sta": sta})[0]
    assert _words_to_bytes(got[0]) == want


def _target_line(pw: bytes, essid: bytes, ap: bytes, sta: bytes) -> str:
    pmk = hashlib.pbkdf2_hmac("sha1", pw, essid, 4096, 32)
    pmkid = hmac_mod.new(pmk, b"PMK Name" + ap + sta,
                         hashlib.sha1).digest()[:16]
    return f"{pmkid.hex()}*{ap.hex()}*{sta.hex()}*{essid.hex()}"


def test_pmkid_device_worker_end_to_end():
    """Planted passphrases in a 100-candidate keyspace, two essids."""
    engine = get_engine("wpa2-pmkid", device="jax")
    assert isinstance(engine, JaxPmkidEngine)
    engine.iterations = 256     # keep the CPU-backend test quick
    gen = MaskGenerator("secret?d?d")
    ap, sta = bytes.fromhex("aabbccddeeff"), bytes.fromhex("112233445566")

    def line(pw, essid):
        pmk = hashlib.pbkdf2_hmac("sha1", pw, essid, 256, 32)
        pmkid = hmac_mod.new(pmk, b"PMK Name" + ap + sta,
                             hashlib.sha1).digest()[:16]
        return f"{pmkid.hex()}*{ap.hex()}*{sta.hex()}*{essid.hex()}"

    cpu = get_engine("wpa2-pmkid", device="cpu")
    targets = [cpu.parse_target(line(b"secret42", b"NetA")),
               cpu.parse_target(line(b"secret87", b"NetB")),
               cpu.parse_target(line(b"secret87", b"NetA"))]
    w = PmkidDeviceWorker(engine, gen, targets, batch=32)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    got = sorted((h.target_index, h.plaintext) for h in hits)
    assert got == [(0, b"secret42"), (1, b"secret87"), (2, b"secret87")]
    for h in hits:
        assert gen.candidate(h.cand_index) == h.plaintext


def test_jax_engine_registered_with_worker_factory():
    engine = get_engine("pmkid", device="jax")
    assert engine.salted
    assert hasattr(engine, "make_mask_worker")


def test_pallas_pmkid_worker_tpu_only_fallback(monkeypatch):
    """Off-TPU (this hermetic suite) the factory must return the XLA
    worker even when the kernel path is forced on -- the PBKDF2 kernel
    is TPU-only like the sha256 mask kernel (on the chip it runs in
    chip_smoke.py's pmkid phase)."""
    from dprf_tpu.engines.device.pmkid import (PallasPmkidWorker,
                                               PmkidDeviceWorker)
    from dprf_tpu.generators.mask import MaskGenerator

    monkeypatch.setenv("DPRF_PALLAS", "1")
    eng = get_engine("wpa2-pmkid", device="jax")
    t = eng.parse_target(
        "%s*0a1b2c3d4e5f*a0b1c2d3e4f5*%s" % ("ff" * 16,
                                            b"TestNet".hex()))
    w = eng.make_mask_worker(MaskGenerator("?l?l?l?l?l?l?l?l"), [t],
                             batch=4096, hit_capacity=8)
    assert isinstance(w, PmkidDeviceWorker)
    assert not isinstance(w, PallasPmkidWorker)


def test_pmkid_kernel_routing_heuristic(monkeypatch, caplog):
    """Many targets sharing one essid must stay on the XLA step (it
    amortizes the per-essid PBKDF2) -- checked with the backend gate
    neutralized so the heuristic itself is exercised."""
    from dprf_tpu.engines.device import pmkid as pmkid_mod
    from dprf_tpu.generators.mask import MaskGenerator

    eng = get_engine("wpa2-pmkid", device="jax")
    ts = [eng.parse_target(
        "%032x*0a1b2c3d4e5f*a0b1c2d3e4f%x*%s"
        % (i, i % 16, b"OneNet".hex())) for i in range(12)]
    # capture the decision reason: the heuristic must fire (logged
    # max_per_essid), not the backend gate
    logged = {}
    from dprf_tpu.utils import logging as dlog
    orig = dlog.DEFAULT.info
    monkeypatch.setattr(dlog.DEFAULT, "info",
                        lambda msg, **kw: logged.update(kw))
    w = pmkid_mod.maybe_pallas_pmkid_worker(
        eng, MaskGenerator("?l?l?l?l"), ts, batch=4096,
        hit_capacity=8, oracle=None)
    assert w is None
    assert logged.get("max_per_essid") == 12


def test_pmkid_lanes_matches_hashlib():
    """The kernel's shared pure body (pmkid_lanes) reproduces
    hashlib's PBKDF2-HMAC-SHA1 + HMAC PMKID bit-for-bit on an eager
    tiny batch -- key padding, chaining, PMK assembly, truncation.
    The pallas wrapper itself runs on the chip in chip_smoke.py's
    pmkid phase (planted crack at 4096 iterations)."""
    import hashlib as _hl
    import hmac as _hmac

    import jax.numpy as jnp

    from dprf_tpu.ops.pallas_pbkdf2 import pmkid_lanes

    essid, iters = b"TinyNet", 3
    ap, sta = bytes.fromhex("aabbccddeeff"), bytes.fromhex("112233445566")
    msg = b"PMK Name" + ap + sta
    msg_vals = [int(x) for x in np.frombuffer(msg, ">u4")]
    shape = (1, 128)
    # 128 distinct passphrases along the lanes, length 4
    import numpy as _np
    cands = [b"pw%02d" % i for i in range(100)] + [b"x%03d" % i
                                                   for i in range(28)]
    byts = [jnp.asarray(_np.array([c[p] for c in cands], _np.uint32)
                        .reshape(1, 128)) for p in range(4)]
    out = pmkid_lanes(byts, list(essid), len(essid), msg_vals,
                      jnp.int32(iters), shape)
    got = _np.stack([_np.asarray(w)[0] for w in out], axis=1)
    for lane_i in (0, 37, 99, 127):
        pmk = _hl.pbkdf2_hmac("sha1", cands[lane_i], essid, iters, 32)
        want = _np.frombuffer(
            _hmac.new(pmk, msg, _hl.sha1).digest()[:16], ">u4")
        assert (got[lane_i] == want).all(), lane_i


def test_pmkid_kernel_eligibility():
    from dprf_tpu.generators.mask import MaskGenerator
    from dprf_tpu.ops.pallas_pbkdf2 import pmkid_kernel_eligible

    g = MaskGenerator("?l?l?l?l?l?l?l?l")
    assert pmkid_kernel_eligible(g, [8, 12])
    assert not pmkid_kernel_eligible(g, [0])
    assert not pmkid_kernel_eligible(g, [40])


# ---------------------------------------------------------------------------
# PallasPmkidWorker's pipelined sweep on the CPU.  The compiled kernel
# is TPU-only (and its interpret mode takes minutes here), so its
# per-batch step is stood in for by a step of the same signature and
# outputs over the XLA crack step's PBKDF2 and HMAC (ops/hmac_sha1.py).

STUB_ITERS = 3
AP, STA = bytes.fromhex("aabbccddeeff"), bytes.fromhex("112233445566")
#: (charsets, batch, essid, iterations, hit capacity) -> jitted sweep,
#: shared by the tests: an XLA:CPU compile is the slow part
_stub_sweeps: dict = {}


def _stub_kernel_step(gen, batch, essid_len, hit_capacity=64,
                      interpret=False, sub=None):
    """make_pmkid_kernel_step's contract: step(base_digits, n_valid,
    iters, essid int32[essid_len], msg5 int32[5], target int32[4]) ->
    (count, lanes, tpos), and step.batch.  The XLA PBKDF2 takes the
    ESSID and the iteration count as constants, so the stand-in keeps
    one jitted sweep of each; message and digest stay arguments, as
    they are the kernel's."""

    def step(base_digits, n_valid, iters, essid, msg5, target):
        salt = bytes(np.asarray(essid).astype(np.uint8))
        assert len(salt) == essid_len
        key = (tuple(gen.charsets), batch, salt, int(iters), hit_capacity)
        if key not in _stub_sweeps:
            _stub_sweeps[key] = _stub_sweep(gen, batch, salt, int(iters),
                                            hit_capacity)
        return _stub_sweeps[key](base_digits, n_valid, msg5, target)

    step.batch = batch
    return step


def _stub_sweep(gen, batch, essid, iters, hit_capacity):
    from dprf_tpu.ops import compare as cmp_ops
    flat = gen.flat_charsets

    @jax.jit
    def sweep(base_digits, n_valid, msg5, target):
        cand = gen.decode_batch(base_digits, flat, batch)
        pmk = pbkdf2_sha1_pmk(
            pack_ops.pack_raw(cand, gen.length, big_endian=True), essid,
            iters)
        istate, ostate = hmac_key_states(
            jnp.zeros((batch, 16), jnp.uint32).at[:, :8].set(pmk))
        pmkid = hmac_sha1_20(istate, ostate, jnp.broadcast_to(
            msg5.astype(jnp.uint32), (batch, 5)))[:, :4]
        found = (jnp.all(pmkid == target.astype(jnp.uint32), axis=-1)
                 & (jnp.arange(batch) < n_valid))
        return cmp_ops.compact_hits(found, jnp.zeros(batch, jnp.int32),
                                    hit_capacity)

    return sweep


@pytest.fixture
def stub_kernel(monkeypatch):
    """(device engine, CPU oracle) at STUB_ITERS iterations, with the
    kernel step stood in for."""
    from dprf_tpu.ops import pallas_pbkdf2
    monkeypatch.setattr(pallas_pbkdf2, "make_pmkid_kernel_step",
                        _stub_kernel_step)
    eng = get_engine("wpa2-pmkid", device="jax")
    cpu = get_engine("wpa2-pmkid", device="cpu")
    monkeypatch.setattr(eng, "iterations", STUB_ITERS)
    monkeypatch.setattr(cpu, "iterations", STUB_ITERS)
    return eng, cpu


def _pmkid(pw, essid, ap=AP, sta=STA):
    pmk = hashlib.pbkdf2_hmac("sha1", pw, essid, STUB_ITERS, 32)
    return hmac_mod.new(pmk, b"PMK Name" + ap + sta,
                        hashlib.sha1).digest()[:16]


def _targets(cpu, *pairs):
    return [cpu.parse_target(f"{_pmkid(pw, essid).hex()}*{AP.hex()}*"
                             f"{STA.hex()}*{essid.hex()}")
            for pw, essid in pairs]


def _reference_hits(gen, targets, unit):
    """What hashlib.pbkdf2_hmac + hmac find in the unit, target by
    target: sorted (target, index, plaintext)."""
    out = []
    for ti, t in enumerate(targets):
        p = t.params
        for i in range(unit.start, unit.end):
            pw = gen.candidate(i)
            if _pmkid(pw, p["essid"], p["mac_ap"], p["mac_sta"]) == t.digest:
                out.append((ti, i, pw))
    return sorted(out)


def _said(hits):
    return sorted((h.target_index, h.cand_index, h.plaintext) for h in hits)


def _pallas_worker(eng, cpu, gen, targets, hit_capacity=4):
    from dprf_tpu.engines.device.pmkid import PallasPmkidWorker
    return PallasPmkidWorker(eng, gen, targets, batch=64,
                             hit_capacity=hit_capacity, oracle=cpu)


#: a unit of four whole batches of 64 and a short fifth one of 17
UNIT = WorkUnit(7, 320, 4 * 64 + 17)


@pytest.mark.parametrize("where", [323, 320 + 2 * 64 + 41, 320 + 4 * 64 + 16],
                         ids=["first-batch", "middle-batch",
                              "short-last-batch"])
def test_pallas_pmkid_submit_matches_hashlib(stub_kernel, where):
    """A plant in the first, a middle and the short last batch of a
    unit: submit().resolve() and process() say what hashlib says, and
    the unit's every batch is one counted dispatch."""
    from dprf_tpu.runtime.worker import describe_worker
    eng, cpu = stub_kernel
    gen = MaskGenerator("pw?d?d?d")
    targets = _targets(cpu, (gen.candidate(where), b"HomeNet-2G"))
    w = _pallas_worker(eng, cpu, gen, targets)
    want = _reference_hits(gen, targets, UNIT)
    assert want == [(0, where, gen.candidate(where))]
    assert _said(w.submit(UNIT).resolve()) == want
    assert _said(w.process(UNIT)) == want
    assert describe_worker(w)["dispatch"] == "batch:10"
    assert w.kdf_evals == 2 * UNIT.length


def test_pallas_pmkid_two_essid_lengths(stub_kernel):
    """Two targets of two ESSID lengths: one step a length, each
    target swept with its own arguments, one PMK a lane and target."""
    from dprf_tpu.runtime.worker import describe_worker
    eng, cpu = stub_kernel
    gen = MaskGenerator("pw?d?d?d")
    targets = _targets(cpu, (gen.candidate(400), b"NetA"),
                       (gen.candidate(401), b"HomeNet-2G"),
                       (gen.candidate(580), b"NetA"))
    w = _pallas_worker(eng, cpu, gen, targets)
    assert sorted(w._steps) == [4, 10]
    want = _reference_hits(gen, targets, UNIT)
    assert [i for _, i, _ in want] == [400, 401, 580]
    assert _said(w.process(UNIT)) == want
    assert describe_worker(w)["dispatch"] == "batch:15"
    assert w.kdf_evals == 3 * UNIT.length


def test_pallas_pmkid_overflow_goes_to_the_oracle(stub_kernel):
    """A batch with more hits than its buffer holds (the kernel's
    count then passes the capacity, as it does for a tile holding two):
    the batch is rescanned by the oracle and every hit comes back.  A
    charset that repeats a byte makes two neighbouring candidates one
    passphrase."""
    eng, cpu = stub_kernel
    gen = MaskGenerator("pw?d?d?1", custom={1: b"xx"})
    plain = gen.candidate(130)
    assert gen.candidate(131) == plain
    targets = _targets(cpu, (plain, b"HomeNet-2G"))
    unit = WorkUnit(3, 64, 128)
    calls = []
    real = cpu.hash_batch
    cpu.hash_batch = lambda *a, **kw: calls.append(1) or real(*a, **kw)
    try:
        w = _pallas_worker(eng, cpu, gen, targets, hit_capacity=1)
        got = _said(w.submit(unit).resolve())
    finally:
        del cpu.hash_batch
    assert got == _reference_hits(gen, targets, unit) == [
        (0, 130, plain), (0, 131, plain)]
    assert calls            # the oracle rescanned the batch


def test_pallas_pmkid_pipeline_depth_two(stub_kernel):
    """UnitPipeline at depth 2 holds two submitted units and resolves
    them oldest first: each unit's hits are its own."""
    from dprf_tpu.runtime.worker import UnitPipeline, _ResolvedUnit
    eng, cpu = stub_kernel
    gen = MaskGenerator("pw?d?d?d")
    targets = _targets(cpu, (gen.candidate(150), b"NetA"),
                       (gen.candidate(420), b"HomeNet-2G"))
    w = _pallas_worker(eng, cpu, gen, targets)
    units = [WorkUnit(i, 100 * i, 100) for i in range(6)]
    pipe = UnitPipeline(w, 2)
    said = {}
    for u in units:
        pipe.submit(u)
        if pipe.full:
            unit, pending, _, _ = pipe.pop()
            assert not isinstance(pending, _ResolvedUnit)
            said[unit.unit_id] = _said(pending.resolve())
    while len(pipe):
        unit, pending, _, _ = pipe.pop()
        said[unit.unit_id] = _said(pending.resolve())
    assert said == {u.unit_id: _reference_hits(gen, targets, u)
                    for u in units}
    assert said[1] == [(0, 150, gen.candidate(150))]
    assert said[4] == [(1, 420, gen.candidate(420))]


def test_ran_line_counts_kdf_evals(stub_kernel):
    """The job's `ran` line names the worker, its per-batch dispatches
    and the PBKDF2 evaluations it dispatched."""
    import io

    from dprf_tpu.cli import _log_ran
    from dprf_tpu.utils.logging import Log
    eng, cpu = stub_kernel
    gen = MaskGenerator("pw?d?d?d")
    w = _pallas_worker(eng, cpu, gen,
                       _targets(cpu, (gen.candidate(5), b"NetA")))
    w.process(UNIT)
    buf = io.StringIO()
    _log_ran(w, Log(stream=buf))
    line = buf.getvalue()
    assert "worker=PallasPmkidWorker" in line and "interpret=False" in line
    assert "dispatch=batch:5" in line
    assert f"kdf=evals:{UNIT.length}" in line
