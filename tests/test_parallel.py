"""Multi-chip sharding tests on the 8-virtual-device CPU mesh.

Validates that the shard_map crack step produces exactly the hits the
single-device fused step (and the CPU oracle) produce, that the psum'd
total matches per-shard counts, and that the sharded worker cracks an
end-to-end planted-password job.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# device-pipeline compiles: full suite / tier-1, excluded from the <5-min
# smoke tier (tools/check_markers.py enforces an explicit tier decision)
pytestmark = pytest.mark.compileheavy

from dprf_tpu.engines import get_engine
from dprf_tpu.engines.base import Target
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops.pipeline import make_mask_crack_step, target_words
from dprf_tpu.parallel import (ShardedMaskWorker, make_mesh,
                               make_sharded_mask_step)
from dprf_tpu.runtime.workunit import WorkUnit


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest should fake 8 CPU devices"
    return make_mesh(8)


def _ntlm(pw: bytes) -> bytes:
    from dprf_tpu.engines.cpu.md4 import md4
    return md4(bytes(b for ch in pw for b in (ch, 0)))


def test_mesh_shape(mesh):
    assert mesh.devices.shape == (8,)
    assert mesh.axis_names == ("candidates",)


def test_sharded_md5_finds_planted_password(mesh):
    gen = MaskGenerator("?l?l?l?l")
    pw = b"crab"
    idx = gen.index_of(pw)
    tgt = target_words(hashlib.md5(pw).digest(), little_endian=True)
    engine = get_engine("md5", device="jax")
    step = make_sharded_mask_step(engine, gen, tgt, mesh,
                                        batch_per_device=1024)
    super_batch = 8 * 1024
    bstart = (idx // super_batch) * super_batch
    base = jnp.asarray(gen.digits(bstart), dtype=jnp.int32)
    total, counts, lanes, tpos = step(base, jnp.int32(super_batch))
    assert int(total) == 1
    assert int(counts.sum()) == 1
    lanes_np = np.asarray(lanes)
    hit_lanes = lanes_np[lanes_np >= 0]
    assert list(hit_lanes) == [idx - bstart]


def test_sharded_matches_single_device_step(mesh):
    """Same super-batch through the 8-shard step and the 1-device step."""
    gen = MaskGenerator("?l?l?l?l")
    engine = get_engine("md5", device="jax")
    # plant several targets inside one super-batch
    super_batch = 8 * 512
    bstart = 3 * super_batch
    plant_idx = [bstart + 7, bstart + 600, bstart + 2048, bstart + 4095]
    digests = [hashlib.md5(gen.candidate(i)).digest() for i in plant_idx]
    table = cmp_ops.make_target_table(digests, little_endian=True)

    sh_step = make_sharded_mask_step(engine, gen, table, mesh,
                                           batch_per_device=512)
    single = make_mask_crack_step(engine, gen, table, batch=super_batch)

    base = jnp.asarray(gen.digits(bstart), dtype=jnp.int32)
    total, counts, lanes, tpos = sh_step(base, jnp.int32(super_batch))
    s_count, s_lanes, s_tpos = single(base, jnp.int32(super_batch))

    assert int(total) == int(s_count) == len(plant_idx)
    sh_pairs = sorted((int(l), int(t))
                      for l, t in zip(np.asarray(lanes).ravel(),
                                      np.asarray(tpos).ravel()) if l >= 0)
    s_pairs = sorted((int(l), int(t))
                     for l, t in zip(np.asarray(s_lanes),
                                     np.asarray(s_tpos)) if l >= 0)
    assert sh_pairs == s_pairs
    assert [p[0] + bstart for p in sh_pairs] == plant_idx


def test_sharded_respects_n_valid(mesh):
    """Lanes past n_valid must not report hits even if they match."""
    gen = MaskGenerator("?d?d?d")
    engine = get_engine("md5", device="jax")
    idx = gen.index_of(b"777")
    tgt = target_words(hashlib.md5(b"777").digest(), little_endian=True)
    step = make_sharded_mask_step(engine, gen, tgt, mesh,
                                        batch_per_device=128)
    base = jnp.asarray(gen.digits(0), dtype=jnp.int32)
    total, *_ = step(base, jnp.int32(idx))       # 777 is lane idx: excluded
    assert int(total) == 0
    total, *_ = step(base, jnp.int32(idx + 1))   # included
    assert int(total) == 1


def test_sharded_ntlm_multi_target_worker(mesh):
    """End-to-end: sharded NTLM worker over a unit spanning super-batches."""
    gen = MaskGenerator("?l?l?l")
    pws = [b"abc", b"xyz", b"qqq"]
    targets = [Target(p.decode(), _ntlm(p)) for p in pws]
    engine = get_engine("ntlm", device="jax")
    w = ShardedMaskWorker(engine, gen, targets, mesh, batch_per_device=256)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert len(hits) == 3
    got = {h.plaintext: h.target_index for h in hits}
    assert got == {b"abc": 0, b"xyz": 1, b"qqq": 2}
    for h in hits:
        assert gen.candidate(h.cand_index) == h.plaintext


def test_sharded_overflow_rescan_no_duplicates(mesh):
    """An overflowing shard triggers a full super-batch rescan; hits from
    non-overflowed shards must not be double-reported."""
    gen = MaskGenerator("?d?d?d")
    # hit_capacity=2: make shard 1 overflow (3 hits in its lane range)
    # while shard 0 has a normal hit.
    batch = 32
    pws = [b"005",                        # shard 0 (lanes 0..31)
           b"033", b"040", b"050",        # shard 1 (lanes 32..63): overflow
           ]
    targets = [Target(p.decode(), hashlib.md5(p).digest()) for p in pws]
    w = ShardedMaskWorker(get_engine("md5", device="jax"), gen, targets,
                          mesh, batch_per_device=batch, hit_capacity=2,
                          oracle=get_engine("md5", device="cpu"))
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert sorted(h.plaintext for h in hits) == sorted(pws)
    assert len(hits) == len(set(h.cand_index for h in hits)) == 4


def test_sharded_worker_matches_cpu_worker(mesh):
    from dprf_tpu.runtime.worker import CpuWorker
    gen = MaskGenerator("?d?d?d?d")
    pws = [b"0042", b"9999", b"1234"]
    targets = [Target(p.decode(), hashlib.sha256(p).digest()) for p in pws]
    dev = ShardedMaskWorker(get_engine("sha256", device="jax"), gen, targets,
                            mesh, batch_per_device=128)
    cpu = CpuWorker(get_engine("sha256", device="cpu"), gen, targets)
    unit = WorkUnit(0, 0, gen.keyspace)
    dev_hits = sorted((h.target_index, h.cand_index, h.plaintext)
                      for h in dev.process(unit))
    cpu_hits = sorted((h.target_index, h.cand_index, h.plaintext)
                      for h in cpu.process(unit))
    assert dev_hits == cpu_hits == [
        (0, gen.index_of(b"0042"), b"0042"),
        (1, gen.index_of(b"9999"), b"9999"),
        (2, gen.index_of(b"1234"), b"1234"),
    ]


# ------------------------------------------------- salted engines (r3)

def test_sharded_bcrypt_mask_worker(mesh):
    """Config 4's engine on the 8-chip mesh: planted password found,
    hits identical to the single-chip worker."""
    from dprf_tpu.engines.cpu.bcrypt import bcrypt_hash
    from dprf_tpu.engines.device.bcrypt import (BcryptMaskWorker,
                                                ShardedBcryptMaskWorker)

    eng = get_engine("bcrypt", device="jax")
    cpu = get_engine("bcrypt", device="cpu")
    gen = MaskGenerator("?d?d?l")
    pw = b"42x"
    line = bcrypt_hash(pw, bytes(range(16)), cost=4)
    targets = [cpu.parse_target(line)]
    sharded = ShardedBcryptMaskWorker(eng, gen, targets, mesh,
                                      batch_per_device=32)
    hits = sharded.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, pw)]
    single = BcryptMaskWorker(eng, gen, targets, batch=256)
    assert ([(h.target_index, h.cand_index, h.plaintext)
             for h in single.process(WorkUnit(0, 0, gen.keyspace))]
            == [(h.target_index, h.cand_index, h.plaintext) for h in hits])


def test_sharded_bcrypt_wordlist_worker(mesh):
    from dprf_tpu.engines.cpu.bcrypt import bcrypt_hash
    from dprf_tpu.engines.device.bcrypt import ShardedBcryptWordlistWorker
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.rules.parser import parse_rule

    eng = get_engine("bcrypt", device="jax")
    cpu = get_engine("bcrypt", device="cpu")
    words = [b"alpha", b"beta", b"gamma", b"delta", b"omega"]
    rules = [parse_rule(":"), parse_rule("u"), parse_rule("$1")]
    gen = WordlistRulesGenerator(words, rules)
    pw = b"GAMMA"        # gamma + 'u' rule
    line = bcrypt_hash(pw, bytes(range(16)), cost=4)
    targets = [cpu.parse_target(line)]
    w = ShardedBcryptWordlistWorker(eng, gen, targets, mesh,
                                    word_batch_per_device=2)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.plaintext) for h in hits] == [(0, pw)]
    assert gen.candidate(hits[0].cand_index) == pw


def test_sharded_pmkid_worker(mesh):
    """Config 5's pod-scale path on the fake mesh, including the
    multi-match lane (same passphrase cracking two captures)."""
    import hashlib as _hl
    import hmac as _hmac
    from dprf_tpu.engines.device.pmkid import ShardedPmkidWorker

    eng = get_engine("wpa2-pmkid", device="jax")
    cpu = get_engine("wpa2-pmkid", device="cpu")
    eng.iterations = cpu.iterations = 64
    try:
        gen = MaskGenerator("pw?d?d")
        ap = bytes.fromhex("aabbccddeeff")
        sta = bytes.fromhex("112233445566")

        def line(pw, essid):
            pmk = _hl.pbkdf2_hmac("sha1", pw, essid, 64, 32)
            pmkid = _hmac.new(pmk, b"PMK Name" + ap + sta,
                              _hl.sha1).digest()[:16]
            return f"{pmkid.hex()}*{ap.hex()}*{sta.hex()}*{essid.hex()}"

        targets = [cpu.parse_target(line(b"pw37", b"NetA")),
                   cpu.parse_target(line(b"pw55", b"NetB")),
                   cpu.parse_target(line(b"pw55", b"NetA"))]
        w = ShardedPmkidWorker(eng, gen, targets, mesh,
                               batch_per_device=8, oracle=cpu)
        hits = w.process(WorkUnit(0, 0, gen.keyspace))
        got = sorted((h.target_index, h.plaintext) for h in hits)
        assert got == [(0, b"pw37"), (1, b"pw55"), (2, b"pw55")]
    finally:
        del eng.iterations, cpu.iterations     # restore class attrs


def test_multihost_init_and_crack_subprocess():
    """init_multihost (jax.distributed) with an explicit 1-process
    coordinator, then a sharded crack over the virtual mesh -- run in a
    subprocess so the distributed global state can't leak into other
    tests.  Exercises the same code path a real pod slice uses."""
    import os
    import subprocess
    import sys

    code = r"""
import hashlib
import jax
jax.config.update("jax_platforms", "cpu")
from dprf_tpu.parallel.mesh import init_multihost
assert init_multihost("localhost:12757", 1, 0) is True
assert init_multihost() is False          # idempotent second call
assert jax.process_index() == 0 and jax.process_count() == 1
import jax.numpy as jnp
import numpy as np
from dprf_tpu.engines import get_engine
from dprf_tpu.generators.mask import MaskGenerator
from dprf_tpu.ops.pipeline import target_words
from dprf_tpu.parallel import make_mesh, make_sharded_mask_step
gen = MaskGenerator("?l?l?l")
pw = b"fox"
idx = gen.index_of(pw)
tgt = target_words(hashlib.md5(pw).digest(), little_endian=True)
step = make_sharded_mask_step(get_engine("md5", device="jax"),
                                    gen, tgt, make_mesh(8), 64)
base = jnp.asarray(gen.digits(0), dtype=jnp.int32)
for bstart in range(0, gen.keyspace, 512):
    base = jnp.asarray(gen.digits(bstart), dtype=jnp.int32)
    total, counts, lanes, tpos = step(base, jnp.int32(
        min(512, gen.keyspace - bstart)))
    if int(total):
        lanes_np = np.asarray(lanes)
        assert bstart + int(lanes_np[lanes_np >= 0][0]) == idx
        print("MULTIHOST_OK")
        break
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MULTIHOST_OK" in proc.stdout


def test_multihost_two_process_crack(tmp_path):
    """The REAL multi-process DCN path: two
    separate OS processes, each with 4 local virtual CPU devices, form
    one 8-device mesh via `jax.distributed` (Gloo collectives) and run
    the SAME `dprf crack --multihost` command SPMD.  Process 0 owns the
    potfile; both observe the planted hit through the replicated
    buffers and exit 0.  This is the only in-environment proof that the
    cross-host mesh actually forms and the sharded step's collectives
    run over a process boundary."""
    import os
    import socket
    import subprocess
    import sys

    pw = b"fox"
    digest = hashlib.md5(pw).hexdigest()
    hashfile = tmp_path / "hashes.txt"
    hashfile.write_text(digest + "\n")
    pot = tmp_path / "mh.pot"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))

    def free_port() -> int:
        with socket.socket() as s:      # free TCP port for the
            s.bind(("127.0.0.1", 0))    # jax.distributed coordinator
            return s.getsockname()[1]

    def spawn(rank: int, port: int):
        argv = [sys.executable, "-m", "dprf_tpu", "crack",
                "?l?l?l", str(hashfile), "--engine", "md5",
                "--device", "tpu", "--devices", "8", "--multihost",
                "--coordinator-address", f"127.0.0.1:{port}",
                "--num-processes", "2", "--process-id", str(rank),
                "--potfile", str(pot), "--unit-size", "4096",
                "--batch", "512", "-q"]
        return subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)

    def attempt():
        port = free_port()
        procs = [spawn(0, port), spawn(1, port)]
        results = []
        try:
            for p in procs:
                results.append(p.communicate(timeout=600) +
                               (p.returncode,))
        finally:
            for q in procs:   # on any failure, don't orphan the peer
                if q.poll() is None:
                    q.kill()
                    q.communicate()
        return results

    results = attempt()
    if any(rc != 0 and "bind" in err.lower() for _, err, rc in results):
        results = attempt()   # free_port TOCTOU: retry on a new port
    for rank, (_, err, rc) in enumerate(results):
        assert rc == 0, f"rank {rank}: {err[-2000:]}"
    # process 0 owns the potfile and prints the crack
    assert f"{digest}:fox" in results[0][0]
    from dprf_tpu.runtime.potfile import Potfile
    assert Potfile(str(pot)).get(digest) == pw


def test_sharded_keccak_worker(mesh):
    """Round 4b: the sha3/keccak family rides the generic sharded
    worker via the digest_candidates hook (previously --devices N on
    this family had no path)."""
    gen = MaskGenerator("?l?l?l?l")
    pw = b"toad"
    idx = gen.index_of(pw)
    dev = get_engine("sha3-256", device="jax")
    t = dev.parse_target(hashlib.sha3_256(pw).hexdigest())
    w = dev.make_sharded_mask_worker(gen, [t], mesh,
                                     batch_per_device=1024,
                                     hit_capacity=8,
                                     oracle=get_engine("sha3-256"))
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.cand_index, h.plaintext)
            for h in hits] == [(0, idx, pw)]


def test_sharded_keccak_wordlist_worker(mesh):
    from dprf_tpu.generators.wordlist import WordlistRulesGenerator
    from dprf_tpu.rules.parser import parse_rule

    words = [b"alpha", b"bravo", b"charlie"] + \
        [b"w%03d" % i for i in range(200)]
    rules = [parse_rule(":"), parse_rule("u")]
    gen = WordlistRulesGenerator(words, rules, max_len=12)
    dev = get_engine("keccak-256", device="jax")
    cpu = get_engine("keccak-256")
    plant = b"BRAVO"                     # rule 'u' on word 1
    t = dev.parse_target(cpu.hash_batch([plant])[0].hex())
    w = dev.make_sharded_wordlist_worker(gen, [t], mesh,
                                         word_batch_per_device=16,
                                         hit_capacity=8, oracle=cpu)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert [(h.target_index, h.cand_index, h.plaintext)
            for h in hits] == [(0, 1 * gen.n_rules + 1, plant)]
