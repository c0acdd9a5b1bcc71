"""Rules-kernel (ops/pallas_rules.py) vs the CPU rule engine.

Strategy: the interpreter's SEMANTICS are tested by running
_interp_step eagerly (plain jnp on CPU, no pallas machinery) over a
lane-packed word batch and comparing bytes/lengths/validity against
rules/cpu.py for EVERY word x EVERY supported opcode -- stronger than
digest-level checks and fast.  The pallas plumbing (grid, SMEM
bytecode, varlen pack, digest, lane mapping, bucketing) is covered by
one small interpret-mode end-to-end test plus the worker tests; the
full best64 job runs on the chip in chip_smoke.py's wordlist-rules
phase and compiles for a described v5e in tests/test_chip_compile.py.
"""

import hashlib

import numpy as np
import jax.numpy as jnp
import pytest

# device-pipeline compiles: full suite / tier-1, excluded from the <5-min
# smoke tier (tools/check_markers.py enforces an explicit tier decision)
pytestmark = pytest.mark.compileheavy

from dprf_tpu.engines import get_engine
from dprf_tpu.generators.wordlist import WordlistRulesGenerator
from dprf_tpu.ops import pallas_rules as pr
from dprf_tpu.rules.cpu import apply_rule as apply_rule_cpu
from dprf_tpu.rules.parser import parse_rule
from dprf_tpu.runtime.workunit import WorkUnit

WORDS = ([b"alpha", b"bravo", b"s3cret", b"Delta", b"echo99",
          b"FOXtrot", b"g0lf!", b"hotellll", b"in", b"j", b"",
          b"aAzZ09!~", b"xxxxxxxxxxxxxxxx"]
         + [b"w%02d" % i for i in range(19)])     # 32 words = 1 row

#: one rule per supported opcode family (p1/p2 chosen so some of
#: WORDS survive and some fail the guards), plus multi-op chains
RULES = [":", "l", "u", "c", "C", "t", "T2", "r", "d", "p2", "f",
         "{", "}", "[", "]", "D2", "x12", "O12", "i2X", "o2Y", "'3",
         "se3", "z2", "Z2", "q", "k", "K", "*03", "L2", "R2", "+2",
         "-2", ".2", ",2", "y2", "Y2", "$!", "^#", "<5", ">3", "_6",
         "!x", "/e", "(a", ")o", "=1e", "%2e", "c $1 $2 $3", "u r ]"]

L = 16


def _lane_pack(words):
    """words -> (w tuple of L int32[(8,128)], lens, valid) with word i
    at sublane i//128, lane i%128 (only the first len(words) lanes are
    meaningful)."""
    shape = (8, 128)
    wb = np.zeros((8 * 128, L), np.int32)
    lens = np.zeros((8 * 128,), np.int32)
    for i, wd in enumerate(words):
        wb[i, :len(wd)] = np.frombuffer(wd, np.uint8)
        lens[i] = len(wd)
    w = tuple(jnp.asarray(wb[:, q].reshape(shape)) for q in range(L))
    return w, jnp.asarray(lens.reshape(shape)), \
        jnp.ones(shape, jnp.int32)


@pytest.mark.parametrize("rule", RULES)
def test_interp_step_matches_cpu(rule):
    """Every opcode family: _interp_step (eager) == rules/cpu.py on
    every word, byte for byte, including lengths and rejections."""
    ops = parse_rule(rule)
    w, lens, valid = _lane_pack(WORDS)
    for op in ops:
        w, lens, valid = pr._interp_step(
            w, lens, valid, jnp.int32(int(op.opcode)),
            jnp.int32(op.p1), jnp.int32(op.p2), L, (8, 128))
    wb = np.stack([np.asarray(x).reshape(-1) for x in w], axis=1)
    lens = np.asarray(lens).reshape(-1)
    valid = np.asarray(valid).reshape(-1)
    for i, word in enumerate(WORDS):
        want = apply_rule_cpu(word, ops, L)
        if want is None:
            assert valid[i] == 0, (rule, word)
        else:
            assert valid[i] == 1, (rule, word)
            got = bytes(wb[i, :lens[i]].astype(np.uint8))
            assert got == want, (rule, word, got, want)
            # zero-tail invariant
            assert not wb[i, lens[i]:].any(), (rule, word)


def test_small_end_to_end_interpret():
    """One small interpret-mode job through the full pallas chain:
    bucketed kernels, SMEM bytecode, varlen pack, digest, runtime
    target, flat-lane mapping."""
    words = [b"alpha", b"bravo", b"s3cret"] + [b"w%03d" % i
                                              for i in range(300)]
    rules = [parse_rule(":"), parse_rule("d"), parse_rule("c $!")]
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    step = pr.make_rules_crack_step(
        "md5", gen, np.full((4,), 0xFFFFFFFF, np.uint32),
        word_batch=1024, interpret=True)
    B = step.word_batch
    for (wi, ri) in ((2, 1), (1, 2)):
        plain = apply_rule_cpu(words[wi], rules[ri], 16)
        tgt = jnp.asarray(np.frombuffer(hashlib.md5(plain).digest(),
                                        "<u4").astype(np.uint32)
                          .view(np.int32))
        c, lanes, _ = step(jnp.int32(0), jnp.int32(gen.n_words),
                           target=tgt)
        got = np.asarray(lanes)
        assert int(c) == 1 and list(got[got >= 0]) == [ri * B + wi]


def test_all_best64_opcodes_supported():
    from dprf_tpu.rules.parser import load_rules
    assert pr.rules_supported(load_rules("best64"))


def test_rules_supported_rejects_purge_title():
    assert not pr.rules_supported([parse_rule("@x")])
    assert not pr.rules_supported([parse_rule("E")])
    assert not pr.rules_supported([parse_rule(":" * (pr.MAX_STEPS + 1))])


def test_step_buckets():
    rules = [parse_rule(r) for r in (":", "u r", "c $1 $2 $3", "$a")]
    assert pr.step_buckets(rules) == {1: [0, 3], 2: [1], 4: [2]}
    assert pr.ceil_pow2(1) == 1 and pr.ceil_pow2(3) == 4 \
        and pr.ceil_pow2(8) == 8


def test_worker_selected_and_cracks(monkeypatch):
    """DPRF_PALLAS=1 routes an eligible single-target wordlist job to
    the kernel worker; hits carry correct keyspace indices."""
    from dprf_tpu.runtime.worker import PallasWordlistWorker

    monkeypatch.setenv("DPRF_PALLAS", "1")
    words = [b"alpha", b"bravo", b"s3cret"] + [b"w%03d" % i
                                              for i in range(300)]
    rules = [parse_rule(":"), parse_rule("d")]
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    cpu = get_engine("md5", device="cpu")
    dev = get_engine("md5", device="jax")
    plain = apply_rule_cpu(words[2], rules[1], 16)
    t = cpu.parse_target(hashlib.md5(plain).hexdigest())
    w = dev.make_wordlist_worker(gen, [t], batch=1 << 16,
                                 hit_capacity=8, oracle=cpu)
    assert isinstance(w, PallasWordlistWorker)
    hits = w.process(WorkUnit(0, 0, gen.keyspace))
    assert {(h.target_index, h.cand_index) for h in hits} == \
        {(0, gen.index_of(2, 1))}
    for h in hits:
        assert cpu.hash_batch([h.plaintext])[0] == t.digest


def test_worker_falls_back_multi_target(monkeypatch):
    from dprf_tpu.runtime.worker import (DeviceWordlistWorker,
                                         PallasWordlistWorker)

    monkeypatch.setenv("DPRF_PALLAS", "1")
    gen = WordlistRulesGenerator(WORDS, [parse_rule(":")], max_len=16)
    cpu = get_engine("md5", device="cpu")
    dev = get_engine("md5", device="jax")
    ts = [cpu.parse_target(hashlib.md5(b"x%d" % i).hexdigest())
          for i in range(3)]
    w = dev.make_wordlist_worker(gen, ts, batch=1 << 16,
                                 hit_capacity=8, oracle=cpu)
    assert isinstance(w, DeviceWordlistWorker)
    assert not isinstance(w, PallasWordlistWorker)


def test_worker_falls_back_unsupported_rule(monkeypatch):
    from dprf_tpu.runtime.worker import (DeviceWordlistWorker,
                                         PallasWordlistWorker)

    monkeypatch.setenv("DPRF_PALLAS", "1")
    gen = WordlistRulesGenerator(WORDS, [parse_rule(":"),
                                         parse_rule("@x")], max_len=16)
    cpu = get_engine("md5", device="cpu")
    dev = get_engine("md5", device="jax")
    t = cpu.parse_target(hashlib.md5(b"nothing").hexdigest())
    w = dev.make_wordlist_worker(gen, [t], batch=1 << 16,
                                 hit_capacity=8, oracle=cpu)
    assert isinstance(w, DeviceWordlistWorker)
    assert not isinstance(w, PallasWordlistWorker)


def test_worker_non_aligned_units(monkeypatch):
    """WorkUnits whose word start is NOT TILE_W-aligned must decode
    hits at the correct keyspace indices (regression: the first kernel
    floored w0 to the tile boundary, hashing the wrong words)."""
    from dprf_tpu.runtime.worker import PallasWordlistWorker

    monkeypatch.setenv("DPRF_PALLAS", "1")
    words = [b"w%04d" % i for i in range(2000)]
    plant_word = 1500
    words[plant_word] = b"s3cret"
    rules = [parse_rule(":"), parse_rule("d"), parse_rule("$!")]
    gen = WordlistRulesGenerator(words, rules, max_len=16)
    cpu = get_engine("md5", device="cpu")
    dev = get_engine("md5", device="jax")
    plain = apply_rule_cpu(b"s3cret", rules[1], 16)
    t = cpu.parse_target(hashlib.md5(plain).hexdigest())
    w = dev.make_wordlist_worker(gen, [t], batch=1 << 12,
                                 hit_capacity=8, oracle=cpu)
    assert isinstance(w, PallasWordlistWorker)
    # a unit starting mid-tile: word start = 300 (not a multiple of
    # TILE_W=1024), covering the planted word
    unit = WorkUnit(0, 300 * gen.n_rules, (1990 - 300) * gen.n_rules)
    hits = w.process(unit)
    assert {(h.target_index, h.cand_index) for h in hits} == \
        {(0, gen.index_of(plant_word, 1))}
    for h in hits:
        assert cpu.hash_batch([h.plaintext])[0] == t.digest
