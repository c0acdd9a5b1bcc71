"""CPU oracle engines against published RFC/FIPS/OpenBSD test vectors."""

import random

import pytest

pytestmark = pytest.mark.smoke

from dprf_tpu import get_engine
from dprf_tpu.engines.cpu import engines
from dprf_tpu.engines.cpu.md4 import md4
from dprf_tpu.engines.cpu import bcrypt as bc

# RFC 1320 appendix A.5
MD4_VECTORS = [
    (b"", "31d6cfe0d16ae931b73c59d7e0c089c0"),
    (b"a", "bde52cb31de33e46245e05fbdbd6fb24"),
    (b"abc", "a448017aaf21d8525fc10ae87aa6729d"),
    (b"message digest", "d9130a8164549fe818874806e1c7014b"),
    (b"abcdefghijklmnopqrstuvwxyz", "d79e1c308aa5bbcdeea8ed63df412da9"),
    (b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789",
     "043f8582f241db351ce627e153e7f0e4"),
    (b"1234567890123456789012345678901234567890123456789012345678901234"
     b"5678901234567890", "e33b4ddc9c38f2199c3e7b164fcc0536"),
]

# RFC 1321 appendix A.5
MD5_VECTORS = [
    (b"", "d41d8cd98f00b204e9800998ecf8427e"),
    (b"abc", "900150983cd24fb0d6963f7d28e17f72"),
    (b"message digest", "f96b697d7cb7938d525a2f31aaf161d0"),
]

SHA1_VECTORS = [
    (b"", "da39a3ee5e6b4b0d3255bfef95601890afd80709"),
    (b"abc", "a9993e364706816aba3e25717850c26c9cd0d89d"),
]

SHA256_VECTORS = [
    (b"abc",
     "ba7816bf8f01cfea414140de5dae2223b00361a396177a9cb410ff61f20015ad"),
    (b"",
     "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"),
]

# Widely-published NTLM digests
NTLM_VECTORS = [
    (b"password", "8846f7eaee8fb117ad06bdd830b7586c"),
    (b"", "31d6cfe0d16ae931b73c59d7e0c089c0"),
]

# Classic OpenBSD/John-the-Ripper bcrypt vectors
BCRYPT_VECTORS = [
    (b"U*U", "$2a$05$CCCCCCCCCCCCCCCCCCCCC.E5YPO9kmyuRGyh0XouQYb4YMJKvyOeW"),
    (b"U*U*", "$2a$05$CCCCCCCCCCCCCCCCCCCCC.VGOzA784oUp/Z0DY336zx7pLYAy0lwK"),
    (b"U*U*U", "$2a$05$XXXXXXXXXXXXXXXXXXXXXOAcXxm9kjPGEMsLznoKqmqw7tc8WCx4a"),
]


@pytest.mark.parametrize("msg,hexdigest", MD4_VECTORS)
def test_md4_rfc1320(msg, hexdigest):
    assert md4(msg).hex() == hexdigest


@pytest.mark.parametrize("engine,vectors", [
    ("md5", MD5_VECTORS), ("sha1", SHA1_VECTORS), ("sha256", SHA256_VECTORS),
    ("ntlm", NTLM_VECTORS),
])
def test_fast_hash_vectors(engine, vectors):
    eng = get_engine(engine)
    msgs = [m for m, _ in vectors]
    digests = eng.hash_batch(msgs)
    for (msg, expect), got in zip(vectors, digests):
        assert got.hex() == expect, f"{engine}({msg!r})"
        assert len(got) == eng.digest_size


def _ntlm_scalar(c: bytes) -> bytes:
    return md4(c.decode("latin-1").encode("utf-16-le"))


def _seeded_candidates(n: int, lo: int = 0, hi: int = 27,
                       seed: int = 4000) -> list:
    rng = random.Random(seed)
    return [bytes(rng.randrange(256) for _ in range(rng.randint(lo, hi)))
            for _ in range(n)]


#: NtlmEngine.hash_batch's batches: each case is (candidates, known
#: digests or None); every digest must be md4() over the UTF-16LE form
NTLM_BATCHES = {
    "mixed_lengths_2000": lambda: (_seeded_candidates(2000), None),
    "every_byte_value": lambda: (
        [bytes([b]) * (1 + b % 27) for b in range(256)]
        + [bytes(range(i, i + 27)) for i in range(0, 256 - 27, 27)]
        + [bytes(range(256 - 27, 256))], None),
    "empty": lambda: ([], None),
    "one": lambda: (_seeded_candidates(1), None),
    "under_crossover": lambda: (
        _seeded_candidates(engines.NTLM_ARRAY_MIN - 1), None),
    "at_crossover": lambda: (
        _seeded_candidates(engines.NTLM_ARRAY_MIN), None),
    "over_crossover": lambda: (
        _seeded_candidates(engines.NTLM_ARRAY_MIN + 1), None),
    "longer_than_one_block": lambda: (
        _seeded_candidates(40, 20, 40), None),
    # the RFC 1320 messages (one of 80 bytes, two blocks) and NTLM's
    # published digests in one batch past the crossover
    "rfc1320_and_known_digests": lambda: (
        [m for m, _ in MD4_VECTORS + NTLM_VECTORS] * 2,
        [bytes.fromhex(h) for _, h in NTLM_VECTORS]),
}


@pytest.mark.parametrize("case", sorted(NTLM_BATCHES))
def test_ntlm_batch_equals_scalar_md4(case):
    cands, known = NTLM_BATCHES[case]()
    got = get_engine("ntlm").hash_batch(cands)
    assert got == [_ntlm_scalar(c) for c in cands]
    assert all(type(d) is bytes and len(d) == 16 for d in got)
    if known:
        n = len(MD4_VECTORS)
        assert got[n:n + len(known)] == known


def test_parse_target_roundtrip():
    eng = get_engine("md5")
    t = eng.parse_target("900150983cd24fb0d6963f7d28e17f72")
    assert eng.verify(b"abc", t)
    assert not eng.verify(b"abd", t)


@pytest.mark.parametrize("password,expected", BCRYPT_VECTORS)
def test_bcrypt_vectors(password, expected):
    variant, cost, salt, digest = bc.parse_hash(expected)
    assert bc.bcrypt_hash(password, salt, cost, variant) == expected


def test_bcrypt_engine_verify():
    eng = get_engine("bcrypt")
    t = eng.parse_target(BCRYPT_VECTORS[0][1])
    assert t.params["cost"] == 5
    assert eng.verify(b"U*U", t)
    assert not eng.verify(b"U*V", t)


def test_bcrypt_b64_roundtrip():
    raw = bytes(range(16))
    assert bc.b64_decode(bc.b64_encode(raw)[:22], 16) == raw


def test_pmkid_engine():
    import hashlib, hmac
    essid, mac_ap, mac_sta = b"TestNet", bytes(6), bytes(range(6))
    pw = b"hunter2hunter2"
    pmk = hashlib.pbkdf2_hmac("sha1", pw, essid, 4096, 32)
    pmkid = hmac.new(pmk, b"PMK Name" + mac_ap + mac_sta,
                     hashlib.sha1).digest()[:16]
    line = f"{pmkid.hex()}*{mac_ap.hex()}*{mac_sta.hex()}*{essid.hex()}"
    eng = get_engine("wpa2-pmkid")
    t = eng.parse_target(line)
    assert eng.verify(pw, t)
    assert not eng.verify(b"wrong-pass", t)


def test_registry():
    from dprf_tpu import engine_names
    names = engine_names("cpu")
    for n in ["md5", "sha1", "sha256", "ntlm", "bcrypt", "wpa2-pmkid"]:
        assert n in names


def test_engine_alias_sets_device_symmetric():
    """Every name resolvable on one device resolves on the other
    (a job written with a jax-side alias must not fail under
    --device=cpu, and vice versa)."""
    from dprf_tpu.engines import engine_names

    cpu = set(engine_names("cpu"))
    jax = set(engine_names("jax"))
    assert cpu == jax, (sorted(cpu - jax), sorted(jax - cpu))
