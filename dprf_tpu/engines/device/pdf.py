"""Device PDF RC4 engines (hashcat 10400 / 10500).

TPU mapping of the user-password check (cpu/pdf.py for the spec):

- The Algorithm-2 MD5 runs over pad32(password) || O || P || ID
  [|| -1]: only the first 32 bytes depend on the candidate, and O
  fills the rest of block 1 — so block 2 (P, ID, metadata flag, MD
  padding) is a TARGET-CONSTANT 16-word block precomputed on host,
  and block 1 is built on device from the candidate with the spec
  PAD string gathered in per length.
- R2: key = digest[:5]; the stored U is RC4(key, PAD), so the filter
  compares ONE keystream word against U[0:4] ^ PAD[0:4] (the
  coordinator oracle confirms the full 32 bytes).
- R3+: 50 chained MD5s (fori_loop), then the 20-pass RC4 cascade over
  MD5(PAD || ID) via ops/rc4.rc4_apply16; all 16 result bytes are
  compared (4 words), so device hits are already exact.

The RC4 passes ride the XLA rc4 ops (per-lane serial gathers — the
bcrypt/krb5 slow shape), so absolute rates are low; the pallas RC4
layout (ops/pallas_krb5.py) is the recorded upgrade path.  Workers
are per-target sweeps; mixed R2/R3 hashlists get per-target steps.
"""

from __future__ import annotations

from typing import Sequence

import hashlib
import struct

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from dprf_tpu.engines import register
from dprf_tpu.engines.base import Target
from dprf_tpu.engines.cpu.pdf import PAD, PdfEngine
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops.md5 import INIT as MD5_INIT, md5_compress
from dprf_tpu.ops.rc4 import (rc4_apply16, rc4_keystream_bytes,
                              words_to_bytes)

_PAD_ARR = np.frombuffer(PAD, np.uint8).astype(np.int32)
_PAD_W0 = int.from_bytes(PAD[:4], "little")


def _le_words(data: bytes) -> np.ndarray:
    return np.frombuffer(data, "<u4").astype(np.uint32)


def _block2_words(p: dict) -> np.ndarray:
    """The target-constant second MD5 block of Algorithm 2."""
    tail = struct.pack("<i", p["p"]) + p["id"]
    if p["rev"] >= 4 and not p["enc_metadata"]:
        tail += b"\xff\xff\xff\xff"
    total = 64 + len(tail)
    padded = tail + b"\x80" + bytes(55 - len(tail)) + \
        struct.pack("<Q", total * 8)
    assert len(padded) == 64, "block-2 tail exceeds one block"
    return _le_words(padded)


def _padded_pw_words(cand, lens):
    """words 0..7 of block 1: candidate bytes then the spec PAD."""
    B, maxlen = cand.shape
    pad_dev = jnp.asarray(_PAD_ARR)
    words = []
    for w in range(8):
        acc = jnp.zeros((B,), jnp.uint32)
        for q in range(4):
            pos = 4 * w + q
            if pos < maxlen:
                from_pw = cand[:, pos].astype(jnp.uint32)
            else:
                from_pw = jnp.zeros((B,), jnp.uint32)
            pad_idx = jnp.clip(pos - lens, 0, 31)
            from_pad = jnp.take(pad_dev, pad_idx).astype(jnp.uint32)
            byte = jnp.where(pos < lens, from_pw, from_pad)
            acc = acc | (byte << jnp.uint32(8 * q))
        words.append(acc)
    return words


def pdf_key_words(cand, lens, o_words, b2_words, rev: int,
                  key_len: int):
    """Candidates -> Algorithm-2 digest words uint32[B, 4] (the
    50-fold R3+ stretch runs over digest[:key_len] — 5 for 40-bit
    keys, 16 for 128-bit)."""
    B = cand.shape[0]
    pw = _padded_pw_words(cand, lens)
    b1 = jnp.stack(pw + [jnp.broadcast_to(o_words[w], (B,))
                         for w in range(8)], axis=1)
    init = jnp.broadcast_to(jnp.asarray(MD5_INIT), (B, 4))
    state = md5_compress(init, b1)
    b2 = jnp.broadcast_to(b2_words[None, :], (B, 16))
    digest = md5_compress(state, b2)
    if rev >= 3:
        iter_pad = jnp.zeros((B, 16), jnp.uint32)
        iter_pad = iter_pad.at[:, key_len // 4].set(
            jnp.uint32(0x80 << (8 * (key_len % 4))))
        iter_pad = iter_pad.at[:, 14].set(jnp.uint32(key_len * 8))
        keep = jnp.uint32((1 << (8 * (key_len % 4))) - 1
                          if key_len % 4 else 0xFFFFFFFF)

        def body(_, d):
            block = iter_pad
            for w in range(key_len // 4):
                block = block.at[:, w].set(d[:, w])
            if key_len % 4:
                w = key_len // 4
                block = block.at[:, w].set(block[:, w]
                                           | (d[:, w] & keep))
            return md5_compress(init, block)

        digest = lax.fori_loop(0, 50, body, digest)
    return digest


def make_pdf2_filter(key_len: int):
    """R2: first keystream word of RC4(digest[:key_len], ...) as
    uint32[B, 1]; the step's target word is U[0:4] ^ PAD[0:4]."""
    def fb(cand, lens, o_words, b2_words):
        digest = pdf_key_words(cand, lens, o_words, b2_words, 2,
                               key_len)
        key = words_to_bytes(digest)[:, :key_len]
        return rc4_keystream_bytes(key, 1)
    return fb


def make_pdf3_u(key_len: int):
    """R3+: the full 16-byte recomputed U as uint32[B, 4]."""
    def fb(cand, lens, o_words, b2_words, x0_words):
        B = cand.shape[0]
        digest = pdf_key_words(cand, lens, o_words, b2_words, 3,
                               key_len)
        key = words_to_bytes(digest)[:, :key_len]
        u = jnp.broadcast_to(x0_words[None, :],
                             (B, 4)).astype(jnp.uint32)
        u = rc4_apply16(key, u)

        def body(i, u):
            return rc4_apply16(key ^ i, u)

        return lax.fori_loop(1, 20, body, u)
    return fb


def _target_args(t: Target):
    p = t.params
    o_words = jnp.asarray(_le_words(p["o"]))
    b2 = jnp.asarray(_block2_words(p))
    if p["rev"] == 2:
        tw = jnp.asarray(
            np.array([int.from_bytes(p["u"][:4], "little") ^ _PAD_W0],
                     np.uint32))
        return (o_words, b2), tw
    x0 = hashlib.md5(PAD + p["id"]).digest()
    return ((o_words, b2, jnp.asarray(_le_words(x0))),
            jnp.asarray(_le_words(p["u"][:16])))


def _filter_for(rev: int, key_len: int):
    return (make_pdf2_filter(key_len) if rev == 2
            else make_pdf3_u(key_len))


def _make_step(gen, batch: int, rev: int, key_len: int,
               hit_capacity: int):
    flat = gen.flat_charsets
    length = gen.length
    fb = _filter_for(rev, key_len)

    @jax.jit
    def step(base_digits, n_valid, *args):
        *params, target = args
        cand = gen.decode_batch(base_digits, flat, batch)
        lens = jnp.full((batch,), length, jnp.int32)
        word = fb(cand, lens, *params)
        found = cmp_ops.compare_single(word, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step


def _make_wordlist_step(gen, word_batch: int, rev: int,
                        key_len: int, hit_capacity: int):
    from dprf_tpu.ops.rules_pipeline import expand_rules

    B, Lw = word_batch, gen.max_len
    words_np, lens_np = gen.packed_words(pad_to=B,
                                         min_size=gen.n_words + B - 1)
    words_dev = jnp.asarray(words_np)
    lens_dev = jnp.asarray(lens_np)
    rules = gen.rules
    fb = _filter_for(rev, key_len)

    @jax.jit
    def step(w0, n_valid_words, *args):
        *params, target = args
        wslice = lax.dynamic_slice(words_dev, (w0, 0), (B, Lw))
        lslice = lax.dynamic_slice(lens_dev, (w0,), (B,))
        base_valid = jnp.arange(B, dtype=jnp.int32) < n_valid_words
        cw, cl, cv = expand_rules(rules, wslice, lslice, base_valid, Lw)
        word = fb(cw, cl, *params)
        found = cmp_ops.compare_single(word, target) & cv
        return cmp_ops.compact_hits(found, jnp.zeros_like(cl),
                                    hit_capacity)

    return step


from dprf_tpu.engines.device.phpass import (PhpassMaskWorker,  # noqa: E402
                                            PhpassWordlistWorker,
                                            ShardedPhpassMaskWorker)


class PdfMaskWorker(PhpassMaskWorker):
    """Per-target sweep with PER-REVISION compiled steps (a hashlist
    may mix R2 and R3 documents); the base sweep calls
    step(base, n, *targ), so _targs carries the target index and the
    dispatcher picks that target's step.

    On TPU, eligible kinds ride the fused Pallas kernel
    (ops/pallas_pdf.py — decode -> Algorithm-2 MD5 -> 50-fold stretch
    -> RC4 cascade in one program, the krb5 RC4 layout); others keep
    the XLA step."""

    def __init__(self, engine, gen, targets, batch: int = 1 << 16,
                 hit_capacity: int = 64, oracle=None):
        from dprf_tpu.ops import pallas_krb5, pallas_pdf
        from dprf_tpu.ops.pallas_mask import pallas_mode
        from dprf_tpu.ops.pallas_pdf import target_scalars

        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        mode = pallas_mode()
        tile = pallas_krb5.SUBC * pallas_pdf.CHUNKS
        if mode is not None:
            batch = max(tile, (batch // tile) * tile)
        self.batch = self.stride = batch
        by_kind = {}
        self._kargs = []
        self.kernel_kinds = set()      # (rev, key_len) on the kernel
        for t in self.targets:
            kind = (2 if t.params["rev"] == 2 else 3,
                    t.params["key_len"])
            if kind not in by_kind:
                step = None
                interp = (mode or {}).get("interpret", False)
                if mode is not None and pallas_pdf.pdf_kernel_eligible(
                        gen, *kind, on_hardware=not interp):
                    from dprf_tpu.engines.device._kernel_util import \
                        kind_kernel_step
                    from dprf_tpu.utils.sync import hard_sync
                    scalars = target_scalars(t)
                    step = kind_kernel_step(
                        lambda: pallas_pdf.make_pdf_crack_step(
                            gen, batch, *kind,
                            hit_capacity=hit_capacity,
                            interpret=interp),
                        lambda s: hard_sync(s(
                            jnp.zeros((gen.length,), jnp.int32),
                            jnp.int32(0), *scalars)))
                if step is None:
                    step = _make_step(gen, batch, *kind, hit_capacity)
                    kernel = False
                else:
                    kernel = True
                    self.kernel_kinds.add(kind)
                by_kind[kind] = (step, kernel)
            step, kernel = by_kind[kind]
            if kernel:
                o, b2, x0, u = target_scalars(t)
                self._kargs.append((step, (o, b2, x0), u))
            else:
                params, tw = _target_args(t)
                self._kargs.append((step, params, tw))
        self._targs = [(ti,) for ti in range(len(self.targets))]

    def step(self, base, n_valid, ti: int):
        s, params, tw = self._kargs[ti]
        return s(base, n_valid, *params, tw)


class PdfWordlistWorker(PhpassWordlistWorker):
    def __init__(self, engine, gen, targets, batch: int = 1 << 16,
                 hit_capacity: int = 64, oracle=None):
        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        self.batch = batch
        self.word_batch = max(1, batch // gen.n_rules)
        self.stride = self.word_batch * gen.n_rules
        by_kind = {}
        self._kargs = []
        for t in self.targets:
            kind = (2 if t.params["rev"] == 2 else 3,
                    t.params["key_len"])
            if kind not in by_kind:
                by_kind[kind] = _make_wordlist_step(
                    gen, self.word_batch, *kind, hit_capacity)
            params, tw = _target_args(t)
            self._kargs.append((by_kind[kind], params, tw))
        self._targs = [(ti,) for ti in range(len(self.targets))]

    def step(self, w0, n_valid, ti: int):
        s, params, tw = self._kargs[ti]
        return s(w0, n_valid, *params, tw)


class ShardedPdfMaskWorker(ShardedPhpassMaskWorker):
    """Multi-chip sweep on the generic per-target sharded step; built
    per revision (R2: 2 params + 1-word target, R3: 3 params +
    4-word target)."""

    def __init__(self, engine, gen, targets, mesh,
                 batch_per_device: int = 1 << 14, hit_capacity: int = 64,
                 oracle=None):
        from dprf_tpu.parallel.sharded import \
            make_sharded_pertarget_step
        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        self.mesh = mesh
        self.batch = self.stride = mesh.devices.size * batch_per_device
        by_kind = {}
        self._kargs = []
        for t in self.targets:
            rev = 2 if t.params["rev"] == 2 else 3
            kind = (rev, t.params["key_len"])
            if kind not in by_kind:
                by_kind[kind] = make_sharded_pertarget_step(
                    gen, mesh, batch_per_device, _filter_for(*kind),
                    2 if rev == 2 else 3, hit_capacity)
            params, tw = _target_args(t)
            self._kargs.append((by_kind[kind], params, tw))
        self._targs = [(ti,) for ti in range(len(self.targets))]

    def step(self, base, n_valid, ti: int):
        s, params, tw = self._kargs[ti]
        return s(base, n_valid, *params, tw)


@register("pdf", device="jax")
class JaxPdfEngine(PdfEngine):
    def make_mask_worker(self, gen, targets, batch: int,
                         hit_capacity: int, oracle=None):
        return PdfMaskWorker(self, gen, targets, batch=batch,
                             hit_capacity=hit_capacity, oracle=oracle)

    def make_wordlist_worker(self, gen, targets, batch: int,
                             hit_capacity: int, oracle=None):
        return PdfWordlistWorker(self, gen, targets, batch=batch,
                                 hit_capacity=hit_capacity,
                                 oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        return ShardedPdfMaskWorker(
            self, gen, targets, mesh, batch_per_device=batch_per_device,
            hit_capacity=hit_capacity, oracle=oracle)
