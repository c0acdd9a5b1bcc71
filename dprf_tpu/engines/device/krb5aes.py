"""Device Kerberos AES etype-17/18 engines (hashcat 19600/19700,
19800/19900, 32100): fused PBKDF2 -> DK -> CBC-prefilter check.

TPU mapping of the RFC 3962 check (cpu/krb5aes.py for the spec and
the full oracle):

- **PBKDF2-HMAC-SHA1** (4096 iterations, 1 block for AES-128 / 2 for
  AES-256) dominates the cost — the same fused XLA chain config 5's
  PMKID engine rides (`ops/hmac_sha1.pbkdf2_sha1_block`).
- **DK derivations** (string-to-key's "kerberos" fold, then the
  usage||0xAA encryption subkey) are 1-2 batched AES encryptions each
  with per-candidate keys (`ops/aes.aes_encrypt_block_batch`); the
  n-fold constants are host bytes.
- **Prefilter**: decrypt ONE ciphertext block with Ke and check the
  DER header right after the 16-byte confounder — plaintext bytes
  [16, 20) are deterministic given len(edata2) exactly like the
  etype-23 filter (engines/device/krb5.der_filter_words, CONF=8
  there / 16 here).  Block 2 is plain CBC as long as it is not in
  the CTS stolen pair, so the device path requires edata2 >= 64
  bytes (always true for real TGS/AS-REP tickets; short Pre-Auth
  timestamps fall back to the CPU oracle).
- Device hits are *maybes*: the masked DER window is 32 bits for
  long-form tickets but only 24 bits for short-form ones (the
  short-form branch masks byte 4 out, so expect a 2^-24 false-maybe
  rate there, 2^-32 otherwise); the coordinator oracle-verifies each
  with the full CTS + HMAC-SHA1-96 chain, mirroring the etype-23
  design.

Mask, wordlist+rules, and sharded mask all run on device (variable
candidate lengths flow through pack_raw_varlen into the HMAC key
block); jobs fall back to the CPU oracle only when a target's edata2
sits below the CTS-safe floor, its salt (realm+user) exceeds the
one-block PBKDF2 salt budget (51 bytes), or a wordlist exceeds the
one-block HMAC key budget (55 bytes).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from dprf_tpu.engines import register
from dprf_tpu.engines.cpu.krb5aes import (Krb5AsRepAesEngine,
                                          Krb5PaAesEngine,
                                          Krb5TgsAesEngine,
                                          USAGE_AS_REP,
                                          USAGE_PA_TIMESTAMP,
                                          USAGE_TGS_REP_TICKET, nfold)
from dprf_tpu.ops import compare as cmp_ops
from dprf_tpu.ops.aes import aes_decrypt_blocks, aes_encrypt_block_batch
from dprf_tpu.ops.hmac_sha1 import hmac_key_states, pbkdf2_sha1_block

#: confounder prefix of the decrypted plaintext (one AES block).
CONF = 16

#: smallest edata2 the device prefilter covers: the DER window block
#: (index 1) must sit outside the CTS stolen pair in every layout.
MIN_DEVICE_EDATA = 64

#: largest salt (realm+user) the fused PBKDF2 path packs: salt + the
#: 4-byte block index + 0x80 marker + 8-byte length must fit one
#: 64-byte SHA-1 block (ops/hmac_sha1.salt_block).  Long AD realms or
#: service-account principals above this run on the CPU oracle --
#: demoted at routing time, NOT discovered as a ValueError at the
#: first step() (ADVICE.md round-5 medium).
MAX_DEVICE_SALT = 51


def der_filter_words_aes(edata_len: int, usage: int) -> tuple[int, int]:
    """(expected, mask) little-endian uint32 over plaintext bytes
    [16, 20) — the DER header right after the confounder.  Same
    definite-minimal-length reasoning as the etype-23 filter
    (engines/device/krb5.der_filter_words), with the AES confounder
    width and per-usage application tags:

    TGS-REP ticket enc-part is EncTicketPart [APPLICATION 3] = 0x63
    (exact); AS-REP is EncASRepPart 0x79 with 0x7A KDC variance
    (match 0x78-0x7B, mask 0xFC); the Pre-Auth timestamp is a bare
    SEQUENCE 0x30."""
    if usage == USAGE_TGS_REP_TICKET:
        tag_exp, tag_mask = 0x63, 0xFF
    elif usage == USAGE_AS_REP:
        tag_exp, tag_mask = 0x78, 0xFC
    else:
        tag_exp, tag_mask = 0x30, 0xFF
    L = edata_len - CONF            # DER blob length (CTS: no padding)
    # first content byte after the length: inner SEQUENCE 0x30, or the
    # [0] context tag 0xA0 of a PA-ENC-TS-ENC (same for BOTH length
    # forms -- the long-form branches below must not assume 0x30, or a
    # large Pre-Auth blob's true password would be prefilter-rejected:
    # a silent missed-crack, ADVICE.md round-5 low)
    inner = 0xA0 if usage == USAGE_PA_TIMESTAMP else 0x30
    if L - 2 < 0x80:
        # short-form length; the third window byte is the first
        # content byte; byte 4 varies, so the window is 24 bits here
        exp = [tag_exp, L - 2, inner, 0x00]
        msk = [tag_mask, 0xFF, 0xFF, 0x00]
    elif L - 3 <= 0xFF:
        exp = [tag_exp, 0x81, L - 3, inner]
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
        raise ValueError("edata2 above 16 MB is not a ticket; use "
                         "--device=cpu")
    exp_w = sum(e << (8 * i) for i, e in enumerate(exp))
    msk_w = sum(m << (8 * i) for i, m in enumerate(msk))
    return exp_w & msk_w, msk_w


def _words_to_bytes_be(words: jnp.ndarray) -> jnp.ndarray:
    """uint32[B, W] big-endian words -> uint8[B, 4W] (SHA-1/PBKDF2
    output serialization)."""
    B, W = words.shape
    shifts = jnp.asarray([24, 16, 8, 0], jnp.uint32)
    return ((words[:, :, None] >> shifts[None, None, :])
            & jnp.uint32(0xFF)).reshape(B, 4 * W).astype(jnp.uint8)


def _dk_batch(base: jnp.ndarray, constant: bytes) -> jnp.ndarray:
    """RFC 3961 DK with per-candidate base keys uint8[B, 16|32]:
    chain ECB encryptions of the n-folded constant until key-length
    bytes exist (1 block for AES-128, 2 for AES-256)."""
    B, kl = base.shape
    nf = nfold(constant, 16) if len(constant) != 16 else constant
    block = jnp.broadcast_to(
        jnp.asarray(np.frombuffer(nf, np.uint8)), (B, 16))
    out = aes_encrypt_block_batch(base, block)
    if kl == 16:
        return out
    out2 = aes_encrypt_block_batch(base, out)
    return jnp.concatenate([out, out2], axis=1)


def make_krb5aes_check(params: dict):
    """check(base uint8[B, key_len] PBKDF2 output) -> uint32[B, 1]
    MASKED DER window: the cheap tail (DK derivations + one-block CBC
    decrypt) shared by the XLA filter and the Pallas KDF-kernel step
    (the 7z pattern: heavy KDF on the kernel, verdict in XLA)."""
    usage, edata = params["usage"], params["edata"]
    _, mask_w = der_filter_words_aes(len(edata), usage)
    c1 = np.frombuffer(edata[:16], np.uint8)
    c2 = np.frombuffer(edata[16:32], np.uint8).reshape(1, 16)
    usage_const = usage.to_bytes(4, "big") + b"\xaa"

    def check(base):
        kkey = _dk_batch(base, b"kerberos")
        ke = _dk_batch(kkey, usage_const)
        p2 = aes_decrypt_blocks(ke, c2)[:, 0] ^ jnp.asarray(c1)
        word = (p2[:, 0].astype(jnp.uint32)
                | (p2[:, 1].astype(jnp.uint32) << 8)
                | (p2[:, 2].astype(jnp.uint32) << 16)
                | (p2[:, 3].astype(jnp.uint32) << 24))
        return (word & jnp.uint32(mask_w))[:, None]

    return check


def make_krb5aes_filter(params: dict, iterations: int = 4096):
    """fb(cand, lens) -> uint32[B, 1] MASKED DER window (compare
    against the masked expectation from der_filter_words_aes);
    candidate lengths arrive at trace time via `lens` (varlen HMAC
    keys), so the filter serves mask, wordlist, and sharded steps
    alike."""
    salt, key_len = params["salt"], params["key_len"]
    check = make_krb5aes_check(params)

    def fb(cand, lens):
        from dprf_tpu.ops.hmac import pack_raw_varlen
        key_words = pack_raw_varlen(cand, lens, big_endian=True)
        istate, ostate = hmac_key_states(key_words)
        t1 = pbkdf2_sha1_block(istate, ostate, salt, 1, iterations)
        if key_len == 16:
            base = _words_to_bytes_be(t1)[:, :16]
        else:
            t2 = pbkdf2_sha1_block(istate, ostate, salt, 2, iterations)
            base = _words_to_bytes_be(
                jnp.concatenate([t1, t2[:, :3]], axis=1))
        return check(base)

    return fb


def _expected_word(t) -> jnp.ndarray:
    exp_w, _ = der_filter_words_aes(len(t.params["edata"]),
                                    t.params["usage"])
    return jnp.asarray(np.array([exp_w], np.uint32))


from dprf_tpu.engines.device.phpass import (PhpassMaskWorker,  # noqa: E402
                                            PhpassWordlistWorker,
                                            ShardedPhpassMaskWorker)


def kdf_kernel_enabled(interpret: bool) -> bool:
    """The PBKDF2 kernel route is DEFAULT-OFF on real hardware until a
    recorded planted-crack run exists (DPRF_KRB5AES_KERNEL=1 enables
    it for the measuring session): the shape matches the PMKID
    kernel, but a new kernel variant is not trusted before its first
    compile and run on a chip are on record.  Interpret mode (tests)
    is ungated."""
    from dprf_tpu.utils import env as envreg
    return interpret or envreg.get_bool("DPRF_KRB5AES_KERNEL")


def _make_kdf_kernel_step(gen, batch: int, params: dict,
                          hit_capacity: int, interpret: bool,
                          iterations: int = 4096, kdf=None):
    """Mask step with PBKDF2 on the Pallas kernel
    (ops/pallas_pbkdf2.make_pbkdf2_kdf_pallas_fn) and the DK + CBC
    verdict in XLA — the KDF is ~99% of the work at 4096 iterations.
    The salt bytes and iteration count are runtime SMEM scalars, so
    callers share one compiled `kdf` per (mask, salt_len, key_len)
    across targets (the worker passes its cache entry)."""
    from dprf_tpu.ops.pallas_pbkdf2 import make_pbkdf2_kdf_pallas_fn

    salt, key_len = params["salt"], params["key_len"]
    check = make_krb5aes_check(params)
    if kdf is None:
        kdf = make_pbkdf2_kdf_pallas_fn(gen, batch, len(salt),
                                        key_len // 4,
                                        interpret=interpret)
    salt_dev = jnp.asarray(np.frombuffer(salt, np.uint8)
                           .astype(np.int32))

    @jax.jit
    def step(base_digits, n_valid, target):
        words = kdf(base_digits, jnp.int32(iterations), salt_dev)
        word = check(_words_to_bytes_be(words))
        found = cmp_ops.compare_single(word, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step, kdf


class Krb5AesMaskWorker(PhpassMaskWorker):
    """Per-target sweep (salt/etype/edata are per-target constants,
    so each target owns a compiled step).  A target outside the device
    envelope (edata2 below the CTS-safe floor, or salt above the
    one-block PBKDF2 budget) gets a HOST pseudo-step (full oracle over
    the unit) instead of demoting the whole job: mixed hashlists keep
    every eligible target on the device path.  On TPU the PBKDF2 runs
    on the fused Pallas kernel where DPRF_KRB5AES_KERNEL enables it
    (compiled at construction; a compile failure raises)."""

    def __init__(self, engine, gen, targets, batch: int = 1 << 13,
                 hit_capacity: int = 64, oracle=None):
        from dprf_tpu.engines.device._kernel_util import kind_kernel_step
        from dprf_tpu.ops.pallas_mask import TILE, pallas_mode
        from dprf_tpu.utils.sync import hard_sync

        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        mode = pallas_mode()
        if mode is not None:
            batch = max(TILE, (batch // TILE) * TILE)
        self.batch = self.stride = batch
        self._steps = []
        self.kernel_targets = set()    # target indices on the kernel
        kdf_cache = {}    # one compiled KDF per (salt_len, key_len)
        for ti, t in enumerate(self.targets):
            # below-floor edata2 OR over-budget salt: host pseudo-step
            # for THIS target only (the rest of the hashlist keeps its
            # compiled device steps)
            if not _target_device_ok(t):
                self._steps.append(self._host_step(ti))
                continue
            step = None
            interp = (mode or {}).get("interpret", False)
            if mode is not None and kdf_kernel_enabled(interp):
                tw = _expected_word(t)
                kind = (len(t.params["salt"]), t.params["key_len"])
                built = {}

                def build(t=t, kind=kind):
                    s, kdf = _make_kdf_kernel_step(
                        gen, batch, t.params, hit_capacity,
                        interpret=interp,
                        iterations=getattr(engine, "iterations", 4096),
                        kdf=kdf_cache.get(kind))
                    built["kdf"] = kdf
                    return s

                step = kind_kernel_step(
                    build,
                    lambda s, tw=tw: hard_sync(s(
                        jnp.zeros((gen.length,), jnp.int32),
                        jnp.int32(0), tw)))
                kdf_cache[kind] = built["kdf"]
            if step is None:
                fb = make_krb5aes_filter(
                    t.params, getattr(engine, "iterations", 4096))
                step = _make_step(gen, batch, fb, hit_capacity)
            else:
                self.kernel_targets.add(ti)
            self._steps.append(step)
        self._targs = [(ti, _expected_word(t))
                       for ti, t in enumerate(self.targets)]

    def _rescan(self, start, end, ti):
        # the device engine IS a full CPU-capable oracle (subclass of
        # the cpu engine), so an overflow without an explicit oracle
        # still rescans exactly instead of raising
        if self.oracle is None:
            from dprf_tpu.runtime.worker import CpuWorker, Hit
            from dprf_tpu.runtime.workunit import WorkUnit
            sub = WorkUnit(-1, start, end - start)
            hits = CpuWorker(self.engine, self.gen,
                             [self.targets[ti]]).process(sub)
            return [Hit(ti, h.cand_index, h.plaintext) for h in hits]
        return super()._rescan(start, end, ti)

    def _host_step(self, ti: int):
        """Oracle scan with the jitted-step output contract; the base
        sweep's int()/np.asarray() reads work on plain numpy."""
        t = self.targets[ti]
        oracle = self.oracle or self.engine

        def step(base_digits, n_valid, target):
            digits = [int(d) for d in np.asarray(base_digits)]
            start = 0
            for d, r in zip(digits, self.gen.radices):
                start = start * r + d
            n = int(n_valid)
            lanes = [i for i in range(n)
                     if oracle.verify(self.gen.candidate(start + i), t)]
            buf = np.full((self.hit_capacity,), -1, np.int32)
            buf[:len(lanes)] = lanes[:self.hit_capacity]
            return (np.int32(len(lanes)), buf,
                    np.zeros_like(buf))

        return step

    def step(self, base, n_valid, ti: int, target):
        return self._steps[ti](base, n_valid, target)


def _make_step(gen, batch: int, fb, hit_capacity: int):
    flat = gen.flat_charsets
    length = gen.length

    @jax.jit
    def step(base_digits, n_valid, target):
        cand = gen.decode_batch(base_digits, flat, batch)
        lens = jnp.full((batch,), length, jnp.int32)
        word = fb(cand, lens)
        found = cmp_ops.compare_single(word, target)
        found = found & (jnp.arange(batch, dtype=jnp.int32) < n_valid)
        return cmp_ops.compact_hits(found, jnp.zeros((batch,), jnp.int32),
                                    hit_capacity)

    return step


class Krb5AesWordlistWorker(PhpassWordlistWorker):
    """Wordlist+rules on device — the realistic Kerberoasting attack
    shape; per-target compiled steps (the shared scaffold of
    phpass.make_pertarget_wordlist_step with this engine's filter;
    variable candidate lengths flow into pack_raw_varlen)."""

    def __init__(self, engine, gen, targets, batch: int = 1 << 13,
                 hit_capacity: int = 64, oracle=None):
        from dprf_tpu.engines.device.phpass import \
            make_pertarget_wordlist_step
        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        self.batch = batch
        self.word_batch = max(1, batch // gen.n_rules)
        self.stride = self.word_batch * gen.n_rules
        self._steps = [
            make_pertarget_wordlist_step(
                gen, self.word_batch,
                make_krb5aes_filter(t.params,
                                    getattr(engine, "iterations", 4096)),
                hit_capacity)
            for t in self.targets]
        self._targs = [(ti, _expected_word(t))
                       for ti, t in enumerate(self.targets)]

    def step(self, w0, n_valid, ti: int, target):
        return self._steps[ti](w0, n_valid, target)


class ShardedKrb5AesMaskWorker(ShardedPhpassMaskWorker):
    def __init__(self, engine, gen, targets, mesh,
                 batch_per_device: int = 1 << 11, hit_capacity: int = 64,
                 oracle=None):
        from dprf_tpu.parallel.sharded import \
            make_sharded_pertarget_step
        self._setup_sweep(engine, gen, targets, hit_capacity, oracle)
        self.mesh = mesh
        self.batch = self.stride = mesh.devices.size * batch_per_device
        self._steps = [make_sharded_pertarget_step(
            gen, mesh, batch_per_device,
            make_krb5aes_filter(t.params,
                                getattr(engine, "iterations", 4096)),
            0, hit_capacity)
            for t in self.targets]
        self._targs = [(ti, _expected_word(t))
                       for ti, t in enumerate(self.targets)]

    def step(self, base, n_valid, ti: int, target):
        return self._steps[ti](base, n_valid, target)


def _target_device_ok(t) -> bool:
    """One target's eligibility for the fused device path: edata2 at
    or above the CTS-safe floor AND a salt that fits the one-block
    PBKDF2 layout.  The salt check matters: without it a long AD
    realm/principal crashes the job at the first step() with
    'salt too long for one block' instead of demoting to the oracle."""
    return (len(t.params["edata"]) >= MIN_DEVICE_EDATA
            and len(t.params["salt"]) <= MAX_DEVICE_SALT)


def _device_ok(targets, any_ok: bool = False) -> bool:
    """False when the job must demote to the CPU oracle.  With
    any_ok (the mask sweep, which routes ineligible targets to host
    pseudo-steps per target), one device-eligible target keeps the
    device worker; the wordlist/sharded scaffolds demote on any
    ineligible target (below-floor edata2 or over-budget salt)."""
    eligible = [_target_device_ok(t) for t in targets]
    ok = any(eligible) if any_ok else all(eligible)
    if not ok:
        from dprf_tpu.utils.logging import DEFAULT as log
        log.warn("krb5 AES target outside the device envelope (edata2 "
                 "below the CTS-safe floor, or salt above the "
                 "one-block budget); running on the CPU oracle",
                 edata_bytes=min(len(t.params["edata"]) for t in targets),
                 floor=MIN_DEVICE_EDATA,
                 salt_bytes=max(len(t.params["salt"]) for t in targets),
                 salt_cap=MAX_DEVICE_SALT)
    return ok


class _JaxKrb5AesMixin:
    def make_mask_worker(self, gen, targets, batch: int,
                         hit_capacity: int, oracle=None):
        if not _device_ok(targets, any_ok=True):
            from dprf_tpu.runtime.worker import CpuWorker
            return CpuWorker(oracle or self, gen, targets)
        return Krb5AesMaskWorker(self, gen, targets, batch=batch,
                                 hit_capacity=hit_capacity,
                                 oracle=oracle)

    def make_wordlist_worker(self, gen, targets, batch: int,
                             hit_capacity: int, oracle=None):
        if not _device_ok(targets) or gen.max_len > 55:
            from dprf_tpu.runtime.worker import CpuWorker
            return CpuWorker(oracle or self, gen, targets)
        return Krb5AesWordlistWorker(self, gen, targets, batch=batch,
                                     hit_capacity=hit_capacity,
                                     oracle=oracle)

    def make_sharded_mask_worker(self, gen, targets, mesh,
                                 batch_per_device: int, hit_capacity: int,
                                 oracle=None):
        if not _device_ok(targets):
            from dprf_tpu.runtime.worker import CpuWorker
            return CpuWorker(oracle or self, gen, targets)
        return ShardedKrb5AesMaskWorker(
            self, gen, targets, mesh, batch_per_device=batch_per_device,
            hit_capacity=hit_capacity, oracle=oracle)


@register("krb5tgs17", device="jax")
@register("krb5tgs18", device="jax")
@register("krb5tgs-aes", device="jax")
class JaxKrb5TgsAesEngine(_JaxKrb5AesMixin, Krb5TgsAesEngine):
    pass


@register("krb5pa17", device="jax")
@register("krb5pa18", device="jax")
@register("krb5pa", device="jax")
class JaxKrb5PaAesEngine(_JaxKrb5AesMixin, Krb5PaAesEngine):
    pass


@register("krb5asrep17", device="jax")
@register("krb5asrep18", device="jax")
@register("krb5asrep-aes", device="jax")
class JaxKrb5AsRepAesEngine(_JaxKrb5AesMixin, Krb5AsRepAesEngine):
    pass
