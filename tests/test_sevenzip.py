"""7-Zip AES-256 (hashcat 11600): KDF construction, encrypt-forward
round trips, parsing, device-vs-oracle, workers."""

import hashlib
import random
import struct
import zlib

import pytest

from dprf_tpu.engines import get_engine
from dprf_tpu.engines.cpu.sevenzip import (parse_7z, sevenzip_decrypt,
                                           sevenzip_key)
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.ops.aes import aes_encrypt_block
from dprf_tpu.runtime.workunit import WorkUnit

#: tiny KDF for tests -- the real default is 19 (0.5M units); the
#: stream walker's group math is identical at any power.
CYCLES = 8


def test_kdf_matches_streaming_construction():
    pw, salt = b"pass7", b"NaCl"
    h = hashlib.sha256()
    for i in range(1 << CYCLES):
        h.update(salt + pw.decode("latin-1").encode("utf-16-le")
                 + struct.pack("<Q", i))
    assert sevenzip_key(pw, salt, CYCLES) == h.digest()


def _line(password: bytes, content: bytes, salt: bytes = b"",
          cycles: int = CYCLES, seed: int = 9) -> str:
    """Encrypt `content` forward with the true password's key."""
    rng = random.Random(seed)
    iv = bytes(rng.randrange(256) for _ in range(16))
    key = sevenzip_key(password, salt, cycles)
    padded = content + bytes(-len(content) % 16 or 0)
    ct, prev = b"", iv
    for off in range(0, len(padded), 16):
        block = aes_encrypt_block(
            key, bytes(p ^ v for p, v in
                       zip(padded[off:off + 16], prev)))
        ct += block
        prev = block
    crc = zlib.crc32(content) & 0xFFFFFFFF
    return (f"$7z$0${cycles}${len(salt)}${salt.hex()}$16${iv.hex()}$"
            f"{crc}${len(ct)}${len(content)}${ct.hex()}")


def test_oracle_roundtrip_and_parse():
    pw, content = b"s3vn", b"The quick brown fox jumps over it."
    cpu = get_engine("7z", "cpu")
    t = cpu.parse_target(_line(pw, content, salt=b"sa"))
    assert cpu.verify(pw, t) and not cpu.verify(b"nope", t)
    # aliases resolve on both devices
    assert type(get_engine("sevenzip", "cpu")) is type(cpu)


def test_parse_errors():
    with pytest.raises(ValueError):          # compressed coder
        parse_7z("$7z$1$19$0$$16$" + "00" * 16 + "$1$16$10$" + "00" * 16)
    with pytest.raises(ValueError):
        parse_7z("$zip$not-7z")
    with pytest.raises(ValueError):          # data not block-aligned
        parse_7z("$7z$0$19$0$$16$" + "00" * 16 + "$1$15$10$" + "00" * 15)


def test_decrypt_roundtrip():
    key = bytes(range(32))
    iv = bytes(range(16, 32))
    content = b"sixteen byte blk" * 3
    ct, prev = b"", iv
    for off in range(0, len(content), 16):
        block = aes_encrypt_block(
            key, bytes(p ^ v for p, v in
                       zip(content[off:off + 16], prev)))
        ct += block
        prev = block
    assert sevenzip_decrypt(key, iv, ct) == content


@pytest.mark.smoke
@pytest.mark.compileheavy    # iterated SHA-256 KDF step compile
def test_mask_worker_end_to_end():
    dev = get_engine("7z", "jax")
    cpu = get_engine("7z", "cpu")
    gen = MaskGenerator("?l?d")
    secret = gen.candidate(155)
    t = dev.parse_target(_line(secret, b"archive payload bytes!",
                               salt=b"Qz"))
    w = dev.make_mask_worker(gen, [t], batch=64, hit_capacity=8,
                             oracle=cpu)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.cand_index, h.plaintext)
            for h in hits] == [(0, 155, secret)]


def test_mask_worker_unaligned_group():
    """A mask length whose stream unit does NOT divide 64 exercises
    the multi-block group walker (unit = 2*3+8 = 14 -> 7-block,
    32-unit groups)."""
    dev = get_engine("7z", "jax")
    cpu = get_engine("7z", "cpu")
    gen = MaskGenerator("?d?d?d")
    secret = gen.candidate(421)
    t = dev.parse_target(_line(secret, b"x" * 20))
    w = dev.make_mask_worker(gen, [t], batch=128, hit_capacity=8,
                             oracle=cpu)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.cand_index) for h in hits] == [(0, 421)]


def test_short_iv_field_accepted():
    """Real 7z2hashcat lines zero-pad the IV field to 16 bytes while
    iv_len records the true (often 8-byte) length."""
    pw = b"v8"
    cpu = get_engine("7z", "cpu")
    line = _line(pw, b"iv padding check")
    f = line.split("$")
    # rewrite: iv_len 8, field still 32 hex chars (true iv + zeros)
    true_iv = bytes.fromhex(f[7])[:8]
    key = sevenzip_key(pw, b"", CYCLES)
    content = b"iv padding check"
    ct, prev = b"", (true_iv + bytes(8))
    for off in range(0, len(content), 16):
        block = aes_encrypt_block(
            key, bytes(p ^ v for p, v in
                       zip(content[off:off + 16], prev)))
        ct += block
        prev = block
    crc = zlib.crc32(content) & 0xFFFFFFFF
    line8 = (f"$7z$0${CYCLES}$0$$8${(true_iv + bytes(8)).hex()}$"
             f"{crc}${len(ct)}${len(content)}${ct.hex()}")
    t = cpu.parse_target(line8)
    assert t.params["iv"] == true_iv
    assert cpu.verify(pw, t) and not cpu.verify(b"xx", t)


def test_device_payload_cap_falls_back_to_cpu():
    from dprf_tpu.runtime.worker import CpuWorker

    dev = get_engine("7z", "jax")
    cpu = get_engine("7z", "cpu")
    gen = MaskGenerator("?d?d")
    secret = gen.candidate(77)
    big = bytes(range(256)) * 8          # 2048 B > the 1024 B cap
    t = dev.parse_target(_line(secret, big))
    w = dev.make_mask_worker(gen, [t], batch=64, hit_capacity=8,
                             oracle=cpu)
    assert isinstance(w, CpuWorker)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, secret)]


#: slow: the interpret-mode discharge of the 7-Zip KDF kernel is 3.7 MB
#: of StableHLO, and XLA:CPU (jaxlib 0.9.0) does not finish compiling
#: it in five minutes (algebraic-simplifier loop, then codegen) -- this
#: is what the tier-1 run hung on.  The kernel's Mosaic compile is in
#: tests/test_chip_compile.py (7z-kdf, ~11 s).
@pytest.mark.slow
def test_kdf_pallas_kernel_matches_oracle():
    """Interpret-mode KDF kernel vs the streaming oracle, lane for
    lane (the kernel emits raw key states; AES+CRC stay in XLA)."""
    import numpy as np
    import jax.numpy as jnp

    from dprf_tpu.ops.pallas_7z import make_7z_kdf_pallas_fn

    gen = MaskGenerator("?l?d")
    salt = b"Na"
    fn = make_7z_kdf_pallas_fn(gen, batch=1024, salt=salt, cycles=CYCLES,
                               sub=8, interpret=True)
    keys = np.asarray(fn(jnp.asarray(gen.digits(0), jnp.int32)))
    for idx in (0, 7, 259):
        want = sevenzip_key(gen.candidate(idx), salt, CYCLES)
        got = b"".join(int(w).to_bytes(4, "big") for w in keys[idx])
        assert got == want, idx


@pytest.mark.slow     # same interpret-mode kernel compile as above
def test_kernel_worker_planted(monkeypatch):
    """DPRF_PALLAS=1 routes the per-target step onto the KDF kernel
    (interpret off-TPU); planted crack through the production sweep."""
    monkeypatch.setenv("DPRF_PALLAS", "1")
    dev = get_engine("7z", "jax")
    cpu = get_engine("7z", "cpu")
    gen = MaskGenerator("?l?d")
    secret = gen.candidate(201)
    t = dev.parse_target(_line(secret, b"kernel path payload!"))
    w = dev.make_mask_worker(gen, [t], batch=64, hit_capacity=8,
                             oracle=cpu)
    assert w.batch >= 64        # rounded up to the kernel tile
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.cand_index, h.plaintext)
            for h in hits] == [(0, 201, secret)]


def test_sharded_worker():
    import jax

    from dprf_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) >= 8
    dev = get_engine("7z", "jax")
    cpu = get_engine("7z", "cpu")
    gen = MaskGenerator("?d?l")
    secret = gen.candidate(101)
    t = dev.parse_target(_line(secret, b"sharded 7z check"))
    w = dev.make_sharded_mask_worker(gen, [t], make_mesh(8),
                                     batch_per_device=16, hit_capacity=8,
                                     oracle=cpu)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, secret)]
