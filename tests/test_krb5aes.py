"""Kerberos AES etype-17/18 engines (hashcat 19600/19700/19800/19900/
32100): RFC vectors, forward construction, device-vs-oracle workers.
"""

import hashlib
import hmac as hmac_mod
import random

import pytest

from dprf_tpu.engines import get_engine
from dprf_tpu.engines.cpu.krb5aes import (USAGE_AS_REP,
                                          USAGE_PA_TIMESTAMP,
                                          USAGE_TGS_REP_TICKET,
                                          cts_decrypt, cts_encrypt,
                                          nfold, string_to_key,
                                          usage_keys)
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.runtime.workunit import WorkUnit


@pytest.mark.smoke
def test_nfold_rfc3961_vectors():
    assert nfold(b"012345", 8).hex() == "be072631276b1955"
    assert nfold(b"password", 7).hex() == "78a07b6caf85fa"
    assert nfold(b"kerberos", 16).hex() == \
        "6b65726265726f737b9b5b2b93132b93"
    assert nfold(b"Rough Consensus, and Running Code", 8).hex() == \
        "bb6ed30870b7f0e0"
    assert nfold(b"password", 21).hex() == \
        "59e4a8ca7c0385c3c37b3f6d2000247cb6e6bd5b3e"


@pytest.mark.smoke
def test_string_to_key_rfc3962_vectors():
    """RFC 3962 appendix B (iteration counts that run fast)."""
    s = b"ATHENA.MIT.EDUraeburn"
    assert string_to_key(b"password", s, 16, iterations=1).hex() == \
        "42263c6e89f4fc28b8df68ee09799f15"
    assert string_to_key(b"password", s, 32, iterations=1).hex() == \
        "fe697b52bc0d3ce14432ba036a92e65bbb52280990a2fa27883998d72af30161"
    assert string_to_key(b"password", s, 16, iterations=2).hex() == \
        "c651bf29e2300ac27fa469d693bdda13"
    assert string_to_key(b"password", s, 32, iterations=1200).hex() == \
        "55a6ac740ad17b4846941051e1e8b0a7548d93b0ab30a8bc3ff16280382b8c2a"


@pytest.mark.smoke
def test_cts_rfc3962_vectors():
    """RFC 3962 appendix B AES-128-CBC-CS3 vectors (zero IV)."""
    key = bytes.fromhex("636869636b656e207465726979616b69")
    cases = [
        ("I would like the ",
         "c6353568f2bf8cb4d8a580362da7ff7f97"),
        ("I would like the General Gau's ",
         "fc00783e0efdb2c1d445d4c8eff7ed22"
         "97687268d6ecccc0c07b25e25ecfe5"),
        ("I would like the General Gau's C",
         "39312523a78662d5be7fcbcc98ebf5a8"
         "97687268d6ecccc0c07b25e25ecfe584"),
        ("I would like the General Gau's Chicken, please,",
         "97687268d6ecccc0c07b25e25ecfe584"
         "b3fffd940c16a18c1b5549d2f838029e"
         "39312523a78662d5be7fcbcc98ebf5"),
        ("I would like the General Gau's Chicken, please, ",
         "97687268d6ecccc0c07b25e25ecfe584"
         "9dad8bbb96c4cdc03bc103e1a194bbd8"
         "39312523a78662d5be7fcbcc98ebf5a8"),
    ]
    for pt, want in cases:
        assert cts_encrypt(key, pt.encode()).hex() == want, len(pt)
        assert cts_decrypt(key, bytes.fromhex(want)) == pt.encode()


def _der_blob(body_len: int, tag: int, fill: int) -> bytes:
    """A DER blob [tag] len <body> whose total length the filter can
    predict; body starts with a SEQUENCE so the window matches."""
    body = bytes([0x30, 0x82]) + (body_len - 2).to_bytes(2, "big") + \
        bytes((fill + i) % 256 for i in range(body_len - 4))
    total = len(body)
    assert total <= 0xFFFF
    return bytes([tag, 0x82]) + total.to_bytes(2, "big") + body


def _line(pw: bytes, tag_name: str, etype: int, usage: int,
          seed: int = 3, body_len: int = 400,
          user: str = "svc", realm: str = "EXAMPLE.COM",
          iterations: int = 4096) -> str:
    """Self-consistent hash line: run RFC 3962 forward with the true
    password and a deterministic DER plaintext, store checksum+edata.
    iterations: tests that lower it must ALSO lower the engines'
    `iterations` attribute (the line format does not carry it)."""
    rng = random.Random(seed)
    conf = bytes(rng.randrange(256) for _ in range(16))
    app_tag = {USAGE_TGS_REP_TICKET: 0x63, USAGE_AS_REP: 0x79,
               USAGE_PA_TIMESTAMP: 0x30}[usage]
    if usage == USAGE_PA_TIMESTAMP:
        inner = (b"\xa0\x11\x18\x0f20260731120000Z"
                 b"\xa1\x05\x02\x03\x01\xe2\x40")
        plain = conf + bytes([0x30, len(inner)]) + inner
    else:
        plain = conf + _der_blob(body_len, app_tag, seed)
    salt = (realm + user).encode()
    key = string_to_key(pw, salt, 16 if etype == 17 else 32,
                        iterations=iterations)
    ke, ki = usage_keys(key, usage)
    edata = cts_encrypt(ke, plain)
    chk = hmac_mod.new(ki, plain, hashlib.sha1).digest()[:12]
    return (f"${tag_name}${etype}${user}${realm}${chk.hex()}$"
            f"{edata.hex()}")


@pytest.mark.parametrize("etype", [17, 18])
def test_oracle_roundtrip_and_parse(etype):
    pw = b"Spr1ng"
    cpu = get_engine("krb5tgs-aes", device="cpu")
    t = cpu.parse_target(_line(pw, "krb5tgs", etype,
                               USAGE_TGS_REP_TICKET))
    assert t.params["etype"] == etype
    assert t.params["key_len"] == (16 if etype == 17 else 32)
    assert cpu.verify(pw, t) and not cpu.verify(b"nope", t)


def test_parse_errors():
    cpu = get_engine("krb5tgs-aes", device="cpu")
    with pytest.raises(ValueError):
        cpu.parse_target("$krb5tgs$23$a$B$" + "00" * 12 + "$" + "00" * 40)
    with pytest.raises(ValueError):
        cpu.parse_target("$krb5tgs$17$a$B$00$" + "00" * 40)   # short chk
    with pytest.raises(ValueError):
        cpu.parse_target("not-a-line")


@pytest.mark.smoke
@pytest.mark.parametrize("etype", [17, 18])
def test_mask_worker_end_to_end_tgs(etype):
    """End-to-end device mask sweep, shrunk for the smoke tier: a
    low KDF iteration count (the iteration loop is runtime-bound, not
    compile-bound -- the fori_loop body compiles once) and a tiny
    keyspace/batch.  The RFC-vector tests above pin the full-count
    math; this case proves the fused pipeline plumbing."""
    dev = get_engine("krb5tgs-aes", device="jax")
    cpu = get_engine("krb5tgs-aes", device="cpu")
    dev.iterations = cpu.iterations = 128
    gen = MaskGenerator("?d?l")
    secret = gen.candidate(174)
    t = dev.parse_target(_line(secret, "krb5tgs", etype,
                               USAGE_TGS_REP_TICKET, iterations=128))
    w = dev.make_mask_worker(gen, [t], batch=128, hit_capacity=8,
                             oracle=cpu)
    assert type(w).__name__ == "Krb5AesMaskWorker"
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.cand_index, h.plaintext)
            for h in hits] == [(0, 174, secret)]


def test_mask_worker_asrep_and_pa_fallback():
    # AS-REP big ticket: device path with the 0x79/0x7A tag mask
    dev = get_engine("krb5asrep-aes", device="jax")
    cpu = get_engine("krb5asrep-aes", device="cpu")
    gen = MaskGenerator("?d?d?d")
    s1 = gen.candidate(271)
    t1 = dev.parse_target(_line(s1, "krb5asrep", 18, USAGE_AS_REP,
                                seed=9))
    w = dev.make_mask_worker(gen, [t1], batch=256, hit_capacity=8,
                             oracle=cpu)
    assert type(w).__name__ == "Krb5AesMaskWorker"
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, s1)]

    # Pre-Auth timestamp: edata below the CTS-safe floor -> CPU worker
    # (tiny keyspace: the pure-python oracle runs the full PBKDF2+DK
    # chain per candidate)
    pa = get_engine("krb5pa", device="jax")
    pa_cpu = get_engine("krb5pa", device="cpu")
    gen2 = MaskGenerator("?d?d")
    secret = gen2.candidate(88)
    t2 = pa.parse_target(_line(secret, "krb5pa", 18,
                               USAGE_PA_TIMESTAMP, seed=4))
    w2 = pa.make_mask_worker(gen2, [t2], batch=256, hit_capacity=8,
                             oracle=pa_cpu)
    assert type(w2).__name__ == "CpuWorker"
    hits2 = w2.process(WorkUnit(0, 0, gen2.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits2] == \
        [(0, secret)]


def test_sharded_worker():
    import jax

    from dprf_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) >= 8
    dev = get_engine("krb5tgs-aes", device="jax")
    cpu = get_engine("krb5tgs-aes", device="cpu")
    gen = MaskGenerator("?d?l")
    secret = gen.candidate(133)
    t = dev.parse_target(_line(secret, "krb5tgs", 18,
                               USAGE_TGS_REP_TICKET, seed=6))
    w = dev.make_sharded_mask_worker(gen, [t], make_mesh(8),
                                     batch_per_device=32,
                                     hit_capacity=8, oracle=cpu)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, secret)]


def test_engine_listing_symmetry():
    from dprf_tpu.engines import engine_names
    for name in ("krb5tgs-aes", "krb5tgs17", "krb5tgs18", "krb5pa",
                 "krb5asrep-aes"):
        assert name in engine_names("cpu")
        assert name in engine_names("jax")


def test_wordlist_worker_device():
    """Wordlist+rules (the realistic Kerberoasting shape) on the
    device path: variable-length HMAC keys via pack_raw_varlen."""
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.rules.parser import parse_rule

    dev = get_engine("krb5tgs-aes", device="jax")
    cpu = get_engine("krb5tgs-aes", device="cpu")
    words = [b"winter", b"summer2024", b"svc-backup"]
    rules = [parse_rule(":"), parse_rule("c $!")]
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    secret = b"Summer2024!"               # rule 'c $!' on word 1
    t = dev.parse_target(_line(secret, "krb5tgs", 18,
                               USAGE_TGS_REP_TICKET, seed=13))
    w = dev.make_wordlist_worker(gen, [t], batch=16, hit_capacity=8,
                                 oracle=cpu)
    assert type(w).__name__ == "Krb5AesWordlistWorker"
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == \
        [(0, secret)]


def test_etype23_parse_hint():
    cpu23 = get_engine("krb5tgs", device="cpu")
    with pytest.raises(ValueError, match="krb5tgs-aes"):
        cpu23.parse_target("$krb5tgs$17$u$R$" + "00" * 12 + "$"
                           + "00" * 64)


def _short_line(pw: bytes, seed: int = 21) -> str:
    """A TGS line whose edata2 sits BELOW the CTS-safe device floor
    (minimal-DER short-form blob, 44-byte plaintext)."""
    rng = random.Random(seed)
    conf = bytes(rng.randrange(256) for _ in range(16))
    blob = bytes([0x63, 26, 0x30, 24]) + bytes(range(24))   # 28 B
    plain = conf + blob
    salt = b"EXAMPLE.COMsvc"
    key = string_to_key(pw, salt, 32)
    ke, ki = usage_keys(key, USAGE_TGS_REP_TICKET)
    edata = cts_encrypt(ke, plain)
    chk = hmac_mod.new(ki, plain, hashlib.sha1).digest()[:12]
    return f"$krb5tgs$18$svc$EXAMPLE.COM${chk.hex()}${edata.hex()}"


def test_mixed_floor_targets_stay_on_device():
    """One below-floor target must NOT demote the whole job: the
    device worker keeps CTS-safe targets on compiled steps and scans
    the short one with a host pseudo-step (per-target routing)."""
    dev = get_engine("krb5tgs-aes", device="jax")
    cpu = get_engine("krb5tgs-aes", device="cpu")
    gen = MaskGenerator("?d?d")
    s_short, s_long = gen.candidate(31), gen.candidate(77)
    targets = [dev.parse_target(_short_line(s_short)),
               dev.parse_target(_line(s_long, "krb5tgs", 18,
                                      USAGE_TGS_REP_TICKET, seed=8))]
    w = dev.make_mask_worker(gen, targets, batch=128, hit_capacity=8,
                             oracle=cpu)
    assert type(w).__name__ == "Krb5AesMaskWorker"
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert sorted((h.target_index, h.plaintext) for h in hits) == \
        [(0, s_short), (1, s_long)]


@pytest.mark.smoke
def test_pa_long_form_der_window():
    """Long-form DER length branches must expect the PA-ENC-TS-ENC [0]
    inner tag 0xA0 (not the SEQUENCE 0x30 of ticket payloads) -- the
    0x81 branch's byte 4 is the first content byte (ADVICE.md round-5
    low: a wrong expectation here is a silent missed-crack)."""
    from dprf_tpu.engines.device.krb5aes import (CONF,
                                                 der_filter_words_aes)

    # 0x81 long form: L - 2 >= 0x80, L - 3 <= 0xFF -> window byte 4 is
    # the inner tag
    L = 200
    exp, msk = der_filter_words_aes(CONF + L, USAGE_PA_TIMESTAMP)
    b = [(exp >> (8 * i)) & 0xFF for i in range(4)]
    assert b == [0x30, 0x81, L - 3, 0xA0]
    assert msk == 0xFFFFFFFF
    # ticket usages keep the inner SEQUENCE expectation
    exp_t, _ = der_filter_words_aes(CONF + L, USAGE_TGS_REP_TICKET)
    assert [(exp_t >> (8 * i)) & 0xFF for i in range(4)] == \
        [0x63, 0x81, L - 3, 0x30]
    # short form: 24-bit window (byte 4 masked out), PA inner tag 0xA0
    exp_s, msk_s = der_filter_words_aes(CONF + 40, USAGE_PA_TIMESTAMP)
    assert [(exp_s >> (8 * i)) & 0xFF for i in range(4)] == \
        [0x30, 38, 0xA0, 0x00]
    assert msk_s == 0x00FFFFFF
    # 0x82 windows carry tag + 3 length bytes only -- no content byte
    exp_w, msk_w = der_filter_words_aes(CONF + 0x1000, USAGE_PA_TIMESTAMP)
    C = 0x1000 - 4
    assert [(exp_w >> (8 * i)) & 0xFF for i in range(4)] == \
        [0x30, 0x82, (C >> 8) & 0xFF, C & 0xFF]


_LONG_REALM = "VERY-LONG-SUBDOMAIN.CORP.EXAMPLE-ENTERPRISES.COM"


def test_long_salt_targets_demote_to_oracle():
    """A salt (realm+user) above the one-block PBKDF2 budget must
    route to the CPU oracle instead of crashing the job with 'salt too
    long for one block' at the first step() (ADVICE.md round-5
    medium)."""
    from dprf_tpu.engines.device.krb5aes import (MAX_DEVICE_SALT,
                                                 _target_device_ok)

    dev = get_engine("krb5tgs-aes", device="jax")
    cpu = get_engine("krb5tgs-aes", device="cpu")
    gen = MaskGenerator("?d?d")
    secret = gen.candidate(42)
    line = _line(secret, "krb5tgs", 18, USAGE_TGS_REP_TICKET, seed=5,
                 user="svc-backup", realm=_LONG_REALM)
    t = dev.parse_target(line)
    assert len(t.params["salt"]) > MAX_DEVICE_SALT
    assert not _target_device_ok(t)

    # single long-salt target: the whole job demotes (mask worker)
    w = dev.make_mask_worker(gen, [t], batch=128, hit_capacity=8,
                             oracle=cpu)
    assert type(w).__name__ == "CpuWorker"
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, secret)]

    # wordlist scaffold demotes too (it has no per-target host steps)
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    wgen = WordlistRulesGenerator([secret, b"nope"], max_len=16)
    ww = dev.make_wordlist_worker(wgen, [t], batch=16, hit_capacity=8,
                                  oracle=cpu)
    assert type(ww).__name__ == "CpuWorker"


def test_mixed_long_salt_target_gets_host_step():
    """Mixed hashlist: the long-salt target rides a host pseudo-step
    while eligible targets keep compiled device steps (same per-target
    routing as the below-floor edata case).  The device step is only
    CONSTRUCTED here (jit is lazy) -- the host step is driven directly
    so the test stays off the multi-minute XLA PBKDF2 compile."""
    dev = get_engine("krb5tgs-aes", device="jax")
    cpu = get_engine("krb5tgs-aes", device="cpu")
    gen = MaskGenerator("?d?d")
    s_long = gen.candidate(31)
    t_long = dev.parse_target(_line(s_long, "krb5tgs", 18,
                                    USAGE_TGS_REP_TICKET, seed=5,
                                    user="svc-backup",
                                    realm=_LONG_REALM))
    t_ok = dev.parse_target(_line(gen.candidate(77), "krb5tgs", 18,
                                  USAGE_TGS_REP_TICKET, seed=8))
    w = dev.make_mask_worker(gen, [t_long, t_ok], batch=128,
                             hit_capacity=8, oracle=cpu)
    assert type(w).__name__ == "Krb5AesMaskWorker"
    # index 0 (long salt) is a plain-python host pseudo-step; index 1
    # is a jitted device step
    assert not hasattr(w._steps[0], "lower")
    assert hasattr(w._steps[1], "lower")
    import numpy as np
    count, lanes, _ = w._steps[0](
        np.zeros(gen.length, np.int32), np.int32(gen.keyspace), None)
    assert int(count) == 1 and int(lanes[0]) == 31


def test_machine_account_principal_parses():
    """AD machine accounts end in '$'; the parser must split
    checksum/edata from the right, not count fields."""
    pw = b"W1"
    line = _line(pw, "krb5tgs", 18, USAGE_TGS_REP_TICKET,
                 user="WS01$", realm="CORP.LOCAL")
    cpu = get_engine("krb5tgs-aes", device="cpu")
    t = cpu.parse_target(line)
    assert t.params["salt"] == b"CORP.LOCALWS01$"
    assert cpu.verify(pw, t)


def test_pbkdf2_lanes_matches_hashlib():
    """The generic PBKDF2 kernel body (ops/pallas_pbkdf2.pbkdf2_lanes)
    reproduces hashlib's PBKDF2-HMAC-SHA1 bit-for-bit on an eager tiny
    batch, at both deployed key widths (T1-only and T1||T2[:3]).  The
    pallas wrapper follows the PMKID kernel's convention: interpret
    mode is NOT executed hermetically (known multi-minute jit-of-
    interpret cost); the wrapper is proven on hardware like the other
    KDF kernels.  The worker's kernel route shares the XLA verdict
    tail (make_krb5aes_check) with the XLA filter, which the e2e
    worker tests above already cover."""
    import jax.numpy as jnp
    import numpy as np

    from dprf_tpu.ops.pallas_pbkdf2 import pbkdf2_lanes

    salt, iters = b"EXAMPLE.COMsvc", 3
    shape = (1, 128)
    cands = [b"pw%02d" % i for i in range(100)] + \
        [b"x%03d" % i for i in range(28)]
    byts = [jnp.asarray(np.array([c[p] for c in cands], np.uint32)
                        .reshape(1, 128)) for p in range(4)]
    for n_words in (4, 8):
        out = pbkdf2_lanes(byts, list(salt), len(salt),
                           jnp.int32(iters), n_words, shape)
        got = np.stack([np.asarray(w).reshape(128) for w in out],
                       axis=1)
        for i, c in enumerate(cands):
            want = hashlib.pbkdf2_hmac("sha1", c, salt, iters,
                                       4 * n_words)
            want_w = np.frombuffer(want, ">u4")
            assert (got[i] == want_w).all(), (n_words, i)


#: slow: 385 s in the tier-1 run of PR 21 -- XLA:CPU compiling the
#: interpret-mode discharge of the PBKDF2 kernel for every kernel
#: target.  The kernel's Mosaic compile is in tests/test_chip_compile.py
#: (krb5aes-pbkdf2, ~20 s).
@pytest.mark.slow
def test_kernel_route_builds_and_marks(monkeypatch):
    """DPRF_PALLAS=1: the mask worker routes eligible targets onto the
    PBKDF2 kernel step (kernel_targets marker).  The kernel itself is
    stubbed to the XLA filter so the test checks ROUTING without the
    multi-minute interpret compile (see test_pbkdf2_lanes_matches_
    hashlib for the math proof)."""
    from dprf_tpu.engines.device import krb5aes as dev_mod

    monkeypatch.setenv("DPRF_PALLAS", "1")
    calls = {}

    def fake_kdf_step(gen, batch, params, hit_capacity, interpret,
                      iterations=4096, kdf=None):
        calls["built"] = (batch, params["key_len"], iterations)
        fb = dev_mod.make_krb5aes_filter(params, iterations)
        return dev_mod._make_step(gen, batch, fb, hit_capacity), None

    monkeypatch.setattr(dev_mod, "_make_kdf_kernel_step", fake_kdf_step)
    dev = get_engine("krb5tgs-aes", device="jax")
    cpu = get_engine("krb5tgs-aes", device="cpu")
    gen = MaskGenerator("?d?l")
    secret = gen.candidate(117)
    t = dev.parse_target(_line(secret, "krb5tgs", 18,
                               USAGE_TGS_REP_TICKET, seed=2))
    w = dev.make_mask_worker(gen, [t], batch=64, hit_capacity=8,
                             oracle=cpu)
    assert w.kernel_targets == {0}
    assert calls["built"][1] == 32
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == \
        [(0, secret)]


def test_extra_metadata_field_rejected():
    """A starred metadata field between realm and checksum must error
    at load time, not silently corrupt the salt."""
    cpu = get_engine("krb5tgs-aes", device="cpu")
    good = _line(b"W1", "krb5tgs", 18, USAGE_TGS_REP_TICKET)
    parts = good.split("$")
    bad = "$".join(parts[:5] + ["*spn*"] + parts[5:])
    with pytest.raises(ValueError, match="malformed"):
        cpu.parse_target(bad)
