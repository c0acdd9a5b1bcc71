"""Device PBKDF2-HMAC-SHA1 / WPA2-PMKID vs stdlib oracles.

Covers: RFC 6070 PBKDF2 vectors, random-candidate equivalence with
hashlib.pbkdf2_hmac, PMKID equivalence with the CPU oracle engine, and
the fused PMKID worker end-to-end (planted passphrase, multi-essid).
"""

import hashlib
import hmac as hmac_mod
import random

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
