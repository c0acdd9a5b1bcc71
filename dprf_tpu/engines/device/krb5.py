"""Device Kerberos etype-23 engines (krb5tgs 13100 / krb5asrep 18200).

Full RFC 4757 verification needs RC4 over the WHOLE multi-KB ticket
plus HMAC-MD5 over the plaintext — per candidate.  The device path
avoids all of it: the plaintext is confounder(8 random bytes) || DER
ticket, and the DER header at offset 8 ([APPLICATION n] + length +
SEQUENCE + length) is DETERMINISTIC given len(edata2) - 8, so the
filter is

    NTLM -> K1 -> K3 (two constant-message HMAC-MD5s, shared with
    netntlmv2) -> RC4 KSA + 12 keystream bytes (ops/rc4.py) ->
    (keystream[8:12] ^ edata2[8:12]) & mask == expected

an exact masked 32-bit compare.  False-positive odds are ~2^-32 per
candidate per target (~2^-30 for AS-REP's relaxed tag byte); the
coordinator's CPU-oracle verification (runtime/coordinator.py) is the
authoritative RFC check on every reported hit, exactly the Bloom
prefilter contract of the 1000-target path.

A non-DER (BER long-form) encoder would defeat the header prediction —
MIT krb5 and Windows KDCs emit DER; the CPU engine remains the
fallback for exotic encoders (`--device=cpu`).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from dprf_tpu.engines import register
from dprf_tpu.engines.base import Target
from dprf_tpu.engines.cpu.krb5 import Krb5AsRepEngine, Krb5TgsEngine
from dprf_tpu.engines.device.netntlmv2 import (_hmac_md5_const_msg,
                                               hmac_msg_blocks)
from dprf_tpu.engines.device.phpass import (PhpassMaskWorker,
                                            PhpassWordlistWorker,
                                            ShardedPhpassMaskWorker)
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops import pack as pack_ops
from dprf_tpu.ops.md4 import md4_digest_words
from dprf_tpu.ops.rc4 import rc4_keystream_words

#: RFC 4757 prepends 8 random confounder bytes before the DER ticket;
#: the predictable header lives at plaintext offset CONF.
CONF = 8


def der_filter_words(edata_len: int, msg_type: int) -> tuple[int, int]:
    """(expected, mask) little-endian uint32 over plaintext bytes
    [8, 12) — the DER header right after the confounder.

    DER framing of the decrypted ticket: [APPLICATION n] tag, outer
    length of C = (edata_len - 8) - header, then SEQUENCE (0x30) and
    its length.  DER's definite minimal-length rule fixes the outer
    form from C alone, and the inner SEQUENCE fills the window:

      C < 0x80:        [tag,   C, 0x30, C-2]   (inner short form too)
      C <= 0xFF:       [tag, 0x81,   C, 0x30]
      C <= 0xFFFF:     [tag, 0x82, C>>8, C&0xFF]
      C <= 0xFFFFFF:   [tag, 0x83, C>>16, (C>>8)&0xFF]  (PAC-bloated)

    TGS plaintext is EncTicketPart [APPLICATION 3] = 0x63 (exact);
    AS-REP is EncASRepPart [APPLICATION 25] = 0x79, but some KDCs tag
    it EncTGSRepPart 0x7A, so its tag byte matches 0x78-0x7B
    (mask 0xFC)."""
    from dprf_tpu.engines.cpu.krb5 import TGS_MSG_TYPE
    if msg_type == TGS_MSG_TYPE:
        tag_exp, tag_mask = 0x63, 0xFF
    else:
        tag_exp, tag_mask = 0x78, 0xFC
    L = edata_len - CONF            # DER blob length
    if L - 2 < 0x80:
        exp = [tag_exp, L - 2, 0x30, L - 4]
        msk = [tag_mask, 0xFF, 0xFF, 0xFF]
    elif L - 3 <= 0xFF:
        exp = [tag_exp, 0x81, L - 3, 0x30]
        msk = [tag_mask, 0xFF, 0xFF, 0xFF]
    elif L - 4 <= 0xFFFF:
        C = L - 4
        exp = [tag_exp, 0x82, (C >> 8) & 0xFF, C & 0xFF]
        msk = [tag_mask, 0xFF, 0xFF, 0xFF]
    elif L - 5 <= 0xFFFFFF:
        C = L - 5
        exp = [tag_exp, 0x83, (C >> 16) & 0xFF, (C >> 8) & 0xFF]
        msk = [tag_mask, 0xFF, 0xFF, 0xFF]
    else:
        # a >16 MB ticket is not a ticket; a silent filter miss would
        # be a false NEGATIVE, so refuse loudly (--device=cpu works)
        raise ValueError(f"DER blob of {L} bytes (edata2 minus "
                         "confounder) exceeds the header forms the "
                         "device filter predicts")
    pack = lambda bs: sum(b << (8 * t) for t, b in enumerate(bs))
    return pack(exp) & pack(msk), pack(msk)


def krb5_filter_batch(cand: jnp.ndarray, lens: jnp.ndarray,
                      type_blocks, type_n, chk_blocks, chk_n,
                      cipher4, mask) -> jnp.ndarray:
    """Candidates -> masked plaintext-bytes-[8,12) word uint32[B, 1].

    cipher4: uint32[1] — edata2 bytes [8, 12) (LE), past the
    confounder; mask: uint32[1].  The step's target word is the DER
    expectation from `der_filter_words`, already masked."""
    wide = pack_ops.utf16le_widen(cand)
    nt = md4_digest_words(pack_ops.pack_varlen(wide, lens * 2,
                                               big_endian=False))
    k1 = _hmac_md5_const_msg(nt, type_blocks, type_n)
    k3 = _hmac_md5_const_msg(k1, chk_blocks, chk_n)
    ks = rc4_keystream_words(k3, (CONF + 4) // 4)
    plain4 = ks[:, CONF // 4] ^ cipher4[0]
    return (plain4 & mask[0])[:, None]


#: krb5_filter_batch's per-target argument count (everything between
#: `lens` and the target word) — the sharded pertarget step needs it.
N_PARAMS = 6


def make_krb5_mask_step(gen, batch: int, hit_capacity: int = 64):
    """step(base_digits, n_valid, *target_params, expected) ->
    (count, lanes, _)."""
    flat = gen.flat_charsets
    length = gen.length
    if length > 27:
        raise ValueError("krb5 etype-23 passwords cap at 27 chars "
                         "(single-block UTF-16LE NTLM)")

    @jax.jit
    def step(base_digits, n_valid, type_blocks, type_n, chk_blocks,
             chk_n, cipher4, mask, expected):
        cand = gen.decode_batch(base_digits, flat, batch)
        lens = jnp.full((batch,), length, jnp.int32)
        word = krb5_filter_batch(cand, lens, type_blocks, type_n,
                                 chk_blocks, chk_n, cipher4, mask)
        found = cmp_ops.compare_single(word, expected)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step


def make_krb5_wordlist_step(gen, word_batch: int, hit_capacity: int = 64):
    from jax import lax

    from dprf_tpu.ops.rules_pipeline import expand_rules

    B, Lw = word_batch, gen.max_len
    if Lw > 27:
        raise ValueError("krb5 etype-23 passwords cap at 27 chars")
    words_np, lens_np = gen.packed_words(pad_to=B,
                                         min_size=gen.n_words + B - 1)
    words_dev = jnp.asarray(words_np)
    lens_dev = jnp.asarray(lens_np)
    rules = gen.rules

    @jax.jit
    def step(w0, n_valid_words, type_blocks, type_n, chk_blocks,
             chk_n, cipher4, mask, expected):
        wslice = lax.dynamic_slice(words_dev, (w0, 0), (B, Lw))
        lslice = lax.dynamic_slice(lens_dev, (w0,), (B,))
        base_valid = jnp.arange(B, dtype=jnp.int32) < n_valid_words
        cw, cl, cv = expand_rules(rules, wslice, lslice, base_valid, Lw)
        word = krb5_filter_batch(cw, cl, type_blocks, type_n,
                                 chk_blocks, chk_n, cipher4, mask)
        found = cmp_ops.compare_single(word, expected) & cv
        return cmp_ops.compact_hits(found, jnp.zeros_like(cl),
                                    hit_capacity)

    return step


def _targs(targets: Sequence[Target]):
    out = []
    for t in targets:
        p = t.params
        tw, tn = hmac_msg_blocks(
            p["msg_type"].to_bytes(4, "little"), 1, what="msg_type")
        cw, cn = hmac_msg_blocks(p["checksum"], 1, what="checksum")
        expected, mask = der_filter_words(len(p["edata"]),
                                          p["msg_type"])
        cipher4 = int.from_bytes(p["edata"][CONF:CONF + 4], "little")
        out.append((jnp.asarray(tw), jnp.int32(tn),
                    jnp.asarray(cw), jnp.int32(cn),
                    jnp.asarray([cipher4], jnp.uint32),
                    jnp.asarray([mask], jnp.uint32),
                    jnp.asarray([expected], jnp.uint32)))
    return out


class Krb5MaskWorker(PhpassMaskWorker):
    def __init__(self, engine, gen, targets, batch: int = 1 << 18,
                 hit_capacity: int = 64, oracle=None):
        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        self.batch = self.stride = batch
        self._targs = _targs(self.targets)
        self.step = make_krb5_mask_step(gen, batch, hit_capacity)


class PallasKrb5MaskWorker(PhpassMaskWorker):
    """Mask sweep over the RC4 prefilter KERNEL (ops/pallas_krb5.py):
    the XLA step's RC4 swaps are per-lane serial gathers; the
    kernel's sublane layout makes them vector ops.  Target scalars are runtime, so one compiled kernel
    serves the whole hashlist (both msg types).  Sweep loop, rescan,
    and the hit contract come from PhpassMaskWorker."""

    def __init__(self, engine, gen, targets, batch: int = 1 << 18,
                 hit_capacity: int = 64, oracle=None,
                 interpret: bool = False):
        from dprf_tpu.ops import pallas_krb5

        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        tile = pallas_krb5.SUBC * pallas_krb5.CHUNKS
        batch = max(tile, (batch // tile) * tile)
        self.batch = self.stride = batch
        self._targs = [pallas_krb5.target_scalars(t) for t in targets]
        self.step = pallas_krb5.make_krb5_crack_step(
            gen, batch, hit_capacity, interpret=interpret)

    def warmup(self) -> None:
        """One launch so a Mosaic compile failure raises in the
        factory, not mid-job."""
        import jax.numpy as jnp

        from dprf_tpu.utils.sync import hard_sync
        base = jnp.asarray(self.gen.digits(0), dtype=jnp.int32)
        hard_sync(self.step(base, jnp.int32(0), *self._targs[0]))


def maybe_pallas_krb5_worker(engine, gen, targets, batch: int,
                             hit_capacity: int, oracle):
    """PallasKrb5MaskWorker when the job is kernel-eligible (warmed so
    a compile failure raises here, at construction), else None ->
    XLA-step worker."""
    from dprf_tpu.ops import pallas_krb5
    from dprf_tpu.ops.pallas_mask import pallas_mode

    mode = pallas_mode()
    if mode is None or not pallas_krb5.krb5_kernel_eligible(gen):
        return None
    worker = PallasKrb5MaskWorker(
        engine, gen, targets, batch=batch,
        hit_capacity=hit_capacity, oracle=oracle,
        interpret=mode.get("interpret", False))
    worker.warmup()
    return worker


class Krb5WordlistWorker(PhpassWordlistWorker):
    def __init__(self, engine, gen, targets, batch: int = 1 << 18,
                 hit_capacity: int = 64, oracle=None):
        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        self.batch = batch
        self.word_batch = max(1, batch // gen.n_rules)
        self.stride = self.word_batch * gen.n_rules
        self._targs = _targs(self.targets)
        self.step = make_krb5_wordlist_step(gen, self.word_batch,
                                            hit_capacity)


class ShardedKrb5MaskWorker(ShardedPhpassMaskWorker):
    def __init__(self, engine, gen, targets, mesh,
                 batch_per_device: int = 1 << 16, hit_capacity: int = 64,
                 oracle=None):
        from dprf_tpu.parallel.sharded import \
            make_sharded_pertarget_step
        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        self.mesh = mesh
        self.batch = self.stride = mesh.devices.size * batch_per_device
        self._targs = _targs(self.targets)
        if gen.length > 27:
            raise ValueError("krb5 etype-23 passwords cap at 27 chars")
        self.step = make_sharded_pertarget_step(
            gen, mesh, batch_per_device, krb5_filter_batch, N_PARAMS,
            hit_capacity)


class _JaxKrb5Mixin:
    def make_mask_worker(self, gen, targets, batch: int,
                         hit_capacity: int, oracle=None):
        worker = maybe_pallas_krb5_worker(self, gen, targets, batch,
                                          hit_capacity, oracle)
        if worker is not None:
            return worker
        return Krb5MaskWorker(self, gen, targets, batch=batch,
                              hit_capacity=hit_capacity, oracle=oracle)

    def make_wordlist_worker(self, gen, targets, batch: int,
                             hit_capacity: int, oracle=None):
        return Krb5WordlistWorker(self, gen, targets, batch=batch,
                                  hit_capacity=hit_capacity,
                                  oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        return ShardedKrb5MaskWorker(
            self, gen, targets, mesh, batch_per_device=batch_per_device,
            hit_capacity=hit_capacity, oracle=oracle)


@register("krb5tgs", device="jax")
class JaxKrb5TgsEngine(_JaxKrb5Mixin, Krb5TgsEngine):
    pass


@register("krb5asrep", device="jax")
class JaxKrb5AsRepEngine(_JaxKrb5Mixin, Krb5AsRepEngine):
    pass
