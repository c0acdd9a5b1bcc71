"""CPU reference HashEngines -- the bit-exact oracles.

These fill the role BASELINE.json config 1 calls the "CPU reference
HashEngine": every device engine must match them exactly, and they are
the `--device=cpu` execution path of the CLI.
"""

from __future__ import annotations

import hashlib
import hmac
import struct
from typing import Optional, Sequence

import numpy as np

from dprf_tpu.engines import register
from dprf_tpu.engines.base import HashEngine, Target
from dprf_tpu.engines.cpu.md4 import md4, md4_blocks
from dprf_tpu.engines.cpu import bcrypt as _bcrypt


class _HashlibEngine(HashEngine):
    _algo: str

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        algo = self._algo
        return [hashlib.new(algo, c).digest() for c in candidates]


@register("md5")
class Md5Engine(_HashlibEngine):
    name = "md5"
    digest_size = 16
    _algo = "md5"


@register("sha1")
class Sha1Engine(_HashlibEngine):
    name = "sha1"
    digest_size = 20
    _algo = "sha1"


@register("sha256")
class Sha256Engine(_HashlibEngine):
    name = "sha256"
    digest_size = 32
    _algo = "sha256"


@register("sha512")
@register("sha-512")      # alias tables are device-symmetric
class Sha512Engine(_HashlibEngine):
    name = "sha512"
    digest_size = 64
    max_candidate_len = 111    # single-block limit of the device engine
    _algo = "sha512"


@register("sha384")
@register("sha-384")
class Sha384Engine(_HashlibEngine):
    name = "sha384"
    digest_size = 48
    max_candidate_len = 111
    _algo = "sha384"


@register("sha224")
class Sha224Engine(_HashlibEngine):
    name = "sha224"
    digest_size = 28
    _algo = "sha224"


#: fixed device salt buffer width; also bounds parseable salt length
SALT_MAX = 32

_SALT_HEX_RE = None


def parse_salted_line(text: str, digest_size: int):
    """hashcat-convention 'hexdigest:salt' -> (digest, salt bytes);
    '$HEX[..]' decodes hex salts.  Shared by CPU and device engines."""
    import re
    global _SALT_HEX_RE
    if _SALT_HEX_RE is None:
        _SALT_HEX_RE = re.compile(r"^\$HEX\[([0-9a-fA-F]*)\]$")
    digest_hex, sep, salt_text = text.strip().partition(":")
    if not sep:
        raise ValueError(f"expected 'digest:salt', got {text!r}")
    digest = bytes.fromhex(digest_hex)
    if len(digest) != digest_size:
        raise ValueError(f"expected {digest_size}-byte digest in {text!r}")
    m = _SALT_HEX_RE.match(salt_text)
    salt = bytes.fromhex(m.group(1)) if m else salt_text.encode("latin-1")
    if len(salt) > SALT_MAX:
        raise ValueError(f"salt longer than {SALT_MAX} bytes in {text!r}")
    return digest, salt


class _SaltedCpuMixin(HashEngine):
    """CPU oracle for the salted fast modes: md5/sha1/sha256 over
    $pass.$salt ('ps', hashcat 10/110/1410) and $salt.$pass ('sp',
    hashcat 20/120/1420)."""

    salted = True
    _order: str

    def parse_target(self, text: str) -> Target:
        digest, salt = parse_salted_line(text, self.digest_size)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError(f"{self.name} needs target params (salt)")
        salt = params["salt"]
        if self._order == "ps":
            return [hashlib.new(self._algo, c + salt).digest()
                    for c in candidates]
        return [hashlib.new(self._algo, salt + c).digest()
                for c in candidates]


def _register_salted_cpu(algo: str, digest_size: int,
                         block_limit: int = 55):
    for order in ("ps", "sp"):
        name = f"{algo}-{order}"
        cls = type(f"{algo.title()}{order.title()}Engine",
                   (_SaltedCpuMixin,),
                   {"name": name, "digest_size": digest_size,
                    "_algo": algo, "_order": order,
                    "__doc__": (f"Salted {algo}: "
                                + ("$pass.$salt" if order == "ps"
                                   else "$salt.$pass")
                                + " ('hexdigest:salt' lines)."),
                    # leave headroom for any parseable salt in the
                    # single block
                    "max_candidate_len": block_limit - SALT_MAX})
        register(name, device="cpu")(cls)


_register_salted_cpu("md5", 16)
_register_salted_cpu("sha1", 20)
_register_salted_cpu("sha256", 32)
_register_salted_cpu("sha512", 64, block_limit=111)


def parse_ldap_line(text: str, scheme: str, digest_size: int):
    """LDAP userPassword line '{SCHEME}base64(digest + salt)' ->
    (digest, salt).  The salt is whatever follows the digest in the
    decoded blob (typically 4-8 bytes; empty for the unsalted {SHA}/
    {MD5} schemes)."""
    import base64

    t = text.strip()
    tag = "{" + scheme + "}"
    if not t[:len(tag)].upper() == tag:
        raise ValueError(f"not an LDAP {tag} line: {text!r}")
    try:
        blob = base64.b64decode(t[len(tag):], validate=True)
    except Exception as e:
        raise ValueError(f"bad base64 in LDAP line {text!r}: {e}")
    if len(blob) < digest_size:
        raise ValueError(f"LDAP {tag} blob shorter than the "
                         f"{digest_size}-byte digest: {text!r}")
    digest, salt = blob[:digest_size], blob[digest_size:]
    if len(salt) > SALT_MAX:
        raise ValueError(f"salt longer than {SALT_MAX} bytes in {text!r}")
    return digest, salt


class _LdapSaltedEngine(_SaltedCpuMixin):
    """LDAP {SSHA}-style schemes: digest(pass + salt), digest and salt
    packed together in one base64 blob -- the salted 'ps' computation
    with LDAP's line format."""

    _order = "ps"
    _scheme: str

    def parse_target(self, text: str) -> Target:
        digest, salt = parse_ldap_line(text, self._scheme,
                                       self.digest_size)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt})


@register("ldap-ssha")
@register("ssha")
class LdapSshaEngine(_LdapSaltedEngine):
    """LDAP {SSHA} (hashcat 111): sha1($pass.$salt), base64 blob."""

    name = "ldap-ssha"
    digest_size = 20
    _algo = "sha1"
    _scheme = "SSHA"
    max_candidate_len = 55 - SALT_MAX


@register("ldap-ssha512")
@register("ssha512")
class LdapSsha512Engine(_LdapSaltedEngine):
    """LDAP {SSHA512} (hashcat 1711): sha512($pass.$salt)."""

    name = "ldap-ssha512"
    digest_size = 64
    _algo = "sha512"
    _scheme = "SSHA512"
    max_candidate_len = 111 - SALT_MAX


@register("ldap-smd5")
class LdapSmd5Engine(_LdapSaltedEngine):
    """LDAP {SMD5}: md5($pass.$salt), base64 blob."""

    name = "ldap-smd5"
    digest_size = 16
    _algo = "md5"
    _scheme = "SMD5"
    max_candidate_len = 55 - SALT_MAX


class _LdapPlainMixin(HashEngine):
    """Unsalted LDAP schemes ({SHA}, {MD5}): the plain fast hash with
    the base64 line format, so the multi-target fast path applies."""

    _scheme: str

    def parse_target(self, text: str) -> Target:
        digest, salt = parse_ldap_line(text, self._scheme,
                                       self.digest_size)
        if salt:
            raise ValueError(f"unexpected salt bytes after the digest "
                             f"in unsalted {{{self._scheme}}} line: "
                             f"{text!r}")
        return Target(raw=text.strip(), digest=digest)


@register("ldap-sha")
class LdapShaEngine(_LdapPlainMixin, Sha1Engine):
    """LDAP {SHA} (hashcat 101): raw sha1, base64 line format."""

    name = "ldap-sha"
    _scheme = "SHA"


@register("ldap-md5")
class LdapMd5Engine(_LdapPlainMixin, Md5Engine):
    """LDAP {MD5}: raw md5, base64 line format."""

    name = "ldap-md5"
    _scheme = "MD5"


@register("oracle11")
@register("oracle-11g")
class Oracle11Engine(_SaltedCpuMixin):
    """Oracle 11g (hashcat 112): sha1($pass.$salt) with a 10-byte
    salt.  Accepts Oracle's native 'S:<40-hex digest><20-hex salt>'
    and hashcat's 'hexdigest:salt' lines."""

    name = "oracle11"
    digest_size = 20
    _algo = "sha1"
    _order = "ps"
    #: the 11g salt is fixed at 10 raw bytes, so candidates get the
    #: rest of the single block (cf. the generic 55 - SALT_MAX cap)
    max_candidate_len = 55 - 10

    def parse_target(self, text: str) -> Target:
        t = text.strip()
        if t[:2].upper() == "S:" and len(t) == 62:
            try:
                digest = bytes.fromhex(t[2:42])
                salt = bytes.fromhex(t[42:])
            except ValueError:
                raise ValueError(f"bad hex in oracle11 line: {text!r}")
            return Target(raw=t, digest=digest, params={"salt": salt})
        tgt = super().parse_target(text)
        salt = tgt.params["salt"]
        # hashcat -m 112 lines carry the salt HEX-ENCODED (ST_HEX):
        # a 20-hex-char field is the 10-byte salt, not literal bytes
        if len(salt) == 20:
            try:
                salt = bytes.fromhex(salt.decode("ascii"))
            except (ValueError, UnicodeDecodeError):
                pass
        if len(salt) != 10:
            raise ValueError(
                f"oracle11 salts are exactly 10 bytes (20 hex chars); "
                f"got {len(salt)} in {text!r}")
        return Target(raw=tgt.raw, digest=tgt.digest,
                      params={"salt": salt})


def mysql323_words(password: bytes) -> tuple:
    """MySQL pre-4.1 OLD_PASSWORD(): two 31-bit words from an
    add/xor/shift scan over the password bytes (space and tab are
    skipped, as the server does).  All arithmetic is u32."""
    M = 0xFFFFFFFF
    nr, nr2, add = 1345345333, 0x12345671, 7
    for c in password:
        if c in (0x20, 0x09):
            continue
        nr ^= ((((nr & 63) + add) * c) + ((nr << 8) & M)) & M
        nr2 = (nr2 + (((nr2 << 8) & M) ^ nr)) & M
        add = (add + c) & M
    return nr & 0x7FFFFFFF, nr2 & 0x7FFFFFFF


@register("mysql323")
@register("mysql-old")
class Mysql323Engine(HashEngine):
    """MySQL pre-4.1 OLD_PASSWORD (hashcat 200): 16 hex chars = two
    big-endian 31-bit words."""

    name = "mysql323"
    digest_size = 8
    max_candidate_len = 55

    def parse_target(self, text: str) -> Target:
        t = text.strip()
        digest = bytes.fromhex(t)
        if len(digest) != 8:
            raise ValueError(f"mysql323 wants 16 hex chars: {text!r}")
        return Target(raw=t, digest=digest)

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        out = []
        for c in candidates:
            a, b = mysql323_words(c)
            out.append(a.to_bytes(4, "big") + b.to_bytes(4, "big"))
        return out


def parse_mssql_line(text: str, version_tag: str, digest_hex: int):
    """MSSQL '0x<ver><8-hex salt><hex digest[s]>' -> (salt, digests).
    2000 lines carry TWO 40-hex sha1 digests (case-sensitive then
    upper-cased); 2005 carry one 40-hex; 2012/2014 one 128-hex."""
    t = text.strip()
    if not t.lower().startswith("0x" + version_tag):
        raise ValueError(f"not an MSSQL 0x{version_tag} line: {text!r}")
    body = t[2 + len(version_tag):]
    if len(body) < 8 + digest_hex or (len(body) - 8) % digest_hex:
        raise ValueError(f"malformed MSSQL line (want 8-hex salt + "
                         f"k x {digest_hex}-hex digest): {text!r}")
    try:
        salt = bytes.fromhex(body[:8])
        digests = [bytes.fromhex(body[8 + i * digest_hex:
                                      8 + (i + 1) * digest_hex])
                   for i in range((len(body) - 8) // digest_hex)]
    except ValueError:
        raise ValueError(f"bad hex in MSSQL line: {text!r}")
    return salt, digests


class _MssqlCpuBase(HashEngine):
    """sha-family over utf16le($pass) . $salt (4-byte salt)."""

    salted = True
    _algo: str
    _tag: str
    _upper = False
    #: digests per line: 2000 stores [case-sensitive, upper-cased],
    #: 2005/2012 exactly one.  Enforced so a 2000-format line fed to
    #: the 2005 engine (or vice versa) is rejected instead of silently
    #: cracking against the wrong digest.
    _ndigests = 1

    def parse_target(self, text: str) -> Target:
        salt, digests = parse_mssql_line(text, self._tag,
                                         2 * self.digest_size)
        if len(digests) != self._ndigests:
            raise ValueError(
                f"{self.name} wants {self._ndigests} digest(s) per "
                f"line, got {len(digests)} -- wrong MSSQL version? "
                f"{text!r}")
        # 2000 lines: [case-sensitive, upper]; crack the LAST digest
        # (the case-insensitive one).
        return Target(raw=text.strip(), digest=digests[-1],
                      params={"salt": salt})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError(f"{self.name} needs target params (salt)")
        salt = params["salt"]
        out = []
        for c in candidates:
            if self._upper:
                c = c.upper()          # ASCII-only, like the device path
            wide = bytes(b for ch in c for b in (ch, 0))
            out.append(hashlib.new(self._algo, wide + salt).digest())
        return out


@register("mssql2000")
class Mssql2000Engine(_MssqlCpuBase):
    """MSSQL 2000 (hashcat 131): sha1(utf16le(upper($pass)) . $salt) --
    the case-insensitive second digest of the 0x0100 line."""

    name = "mssql2000"
    digest_size = 20
    _algo = "sha1"
    _tag = "0100"
    _upper = True
    _ndigests = 2
    max_candidate_len = (55 - 4) // 2


@register("mssql2005")
class Mssql2005Engine(_MssqlCpuBase):
    """MSSQL 2005 (hashcat 132): sha1(utf16le($pass) . $salt)."""

    name = "mssql2005"
    digest_size = 20
    _algo = "sha1"
    _tag = "0100"
    max_candidate_len = (55 - 4) // 2


def descrypt_encode(digest8: bytes) -> str:
    """8-byte descrypt ciphertext -> the 11 itoa64 chars of a crypt(3)
    line: the 64 bits MSB-first in 6-bit groups (NOT phpass's
    little-endian packing), 2 zero bits appended."""
    from dprf_tpu.engines.cpu.phpass import ITOA64
    bits = [(digest8[i // 8] >> (7 - i % 8)) & 1 for i in range(64)]
    bits += [0, 0]
    out = []
    for g in range(11):
        v = 0
        for b in bits[6 * g:6 * g + 6]:
            v = (v << 1) | b
        out.append(ITOA64[v])
    return "".join(out)


def descrypt_decode(text11: str) -> bytes:
    """11 itoa64 chars -> the 8-byte ciphertext (inverse of
    descrypt_encode)."""
    from dprf_tpu.engines.cpu.phpass import ITOA64
    bits = []
    for ch in text11:
        v = ITOA64.index(ch)
        bits += [(v >> k) & 1 for k in range(5, -1, -1)]
    if bits[64] or bits[65]:
        raise ValueError("descrypt digest has nonzero trailing bits")
    return bytes(sum(bits[8 * k + j] << (7 - j) for j in range(8))
                 for k in range(8))


@register("descrypt")
@register("des-crypt")
@register("unix-crypt")
class DescryptEngine(HashEngine):
    """Traditional DES crypt(3) (hashcat 1500): 25 chained DES
    encryptions of the zero block, E expansion perturbed by the 12-bit
    salt, key = low 7 bits of the first 8 password bytes.  Validated
    against the system crypt()."""

    name = "descrypt"
    digest_size = 8
    salted = True
    #: crypt(3) silently truncates at 8; the workers cap candidates so
    #: every reported plaintext hashes to the target as-is
    max_candidate_len = 8

    def parse_target(self, text: str) -> Target:
        from dprf_tpu.engines.cpu.phpass import ITOA64
        t = text.strip()
        if len(t) != 13:
            raise ValueError(f"descrypt wants 13-char salt+digest "
                             f"lines, got {len(t)}: {text!r}")
        try:
            salt = ITOA64.index(t[0]) | (ITOA64.index(t[1]) << 6)
            digest = descrypt_decode(t[2:])
        except ValueError as e:
            raise ValueError(f"bad descrypt line {text!r}: {e}")
        return Target(raw=t, digest=digest,
                      params={"salt": salt, "salt_text": t[:2]})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        from dprf_tpu.ops.des import des_crypt25, descrypt_key8
        if params is None or "salt" not in params:
            raise ValueError("descrypt needs target params (salt)")
        salt = params["salt"]
        return [des_crypt25(descrypt_key8(c), salt) for c in candidates]


@register("mssql2012")
@register("mssql2014")
class Mssql2012Engine(_MssqlCpuBase):
    """MSSQL 2012/2014 (hashcat 1731): sha512(utf16le($pass) . $salt),
    0x0200 lines."""

    name = "mssql2012"
    digest_size = 64
    _algo = "sha512"
    _tag = "0200"
    max_candidate_len = (111 - 4) // 2


#: nested double-hash combinations (outer, inner) with their hashcat
#: modes -- the ONE list device/nested.py and the oracles share (this
#: module stays jax-free, so it is the importable-everywhere home)
NESTED_COMBOS = [
    ("md5", "md5"),        # 2600
    ("sha1", "sha1"),      # 4500
    ("md5", "sha1"),       # 4400
    ("sha1", "md5"),       # 4700
    ("sha256", "md5"),     # 20800
    ("sha256", "sha1"),    # 20700
]
NESTED_DIGEST_SIZE = {"md5": 16, "sha1": 20, "sha256": 32}


class _NestedCpuMixin(HashEngine):
    """CPU oracle for nested modes: outer(hex(inner(password)))."""

    _outer: str
    _inner: str

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        return [hashlib.new(
            self._outer,
            hashlib.new(self._inner, c).hexdigest().encode()).digest()
            for c in candidates]


def _register_nested_cpu():
    for outer, inner in NESTED_COMBOS:
        name = f"{outer}({inner})"
        cls = type(f"{outer.title()}Of{inner.title()}Engine",
                   (_NestedCpuMixin,),
                   {"name": name,
                    "digest_size": NESTED_DIGEST_SIZE[outer],
                    "__doc__": f"Nested {outer}(hex({inner}(password))).",
                    "_outer": outer, "_inner": inner})
        register(name, device="cpu")(cls)


_register_nested_cpu()


def parse_mysql41(text: str) -> Target:
    """MySQL 4.1+ hash line: '*' + 40 uppercase hex chars (the '*' is
    part of the stored format; bare hex is accepted too)."""
    t = text.strip()
    hexpart = t[1:] if t.startswith("*") else t
    digest = bytes.fromhex(hexpart)
    if len(digest) != 20:
        raise ValueError(f"mysql41 wants 20 digest bytes, got {text!r}")
    return Target(raw=t, digest=digest)


@register("mysql41")
class Mysql41Engine(HashEngine):
    """MySQL 4.1+ PASSWORD() = sha1(sha1(password)), raw inner digest."""

    name = "mysql41"
    digest_size = 20

    def parse_target(self, text: str) -> Target:
        return parse_mysql41(text)

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        return [hashlib.sha1(hashlib.sha1(c).digest()).digest()
                for c in candidates]


def _md4_utf16(password: bytes) -> bytes:
    return md4(password.decode("latin-1").encode("utf-16-le"))


def netntlmv2_proof(password: bytes, user: str, domain: str,
                    challenge: bytes, blob: bytes) -> bytes:
    """NetNTLMv2 reference: nt = MD4(UTF16LE(pw)); key2 = HMAC-MD5(nt,
    UTF16LE(upper(user)+domain)); proof = HMAC-MD5(key2, chal+blob)."""
    nt = _md4_utf16(password)
    ident = (user.upper() + domain).encode("utf-16-le")
    key2 = hmac.new(nt, ident, "md5").digest()
    return hmac.new(key2, challenge + blob, "md5").digest()


def parse_netntlmv2(text: str):
    """'USER::DOMAIN:chal:proof:blob' (hex fields) ->
    (user, domain, challenge, proof, blob)."""
    t = text.strip()
    user, sep, rest = t.partition("::")
    if not sep:
        raise ValueError(f"not a NetNTLMv2 line (no '::'): {text!r}")
    parts = rest.split(":")
    if len(parts) != 4:
        raise ValueError(f"malformed NetNTLMv2 line: {text!r}")
    domain, chal_hex, proof_hex, blob_hex = parts
    challenge = bytes.fromhex(chal_hex)
    proof = bytes.fromhex(proof_hex)
    blob = bytes.fromhex(blob_hex)
    if len(challenge) != 8 or len(proof) != 16:
        raise ValueError(f"bad challenge/proof length in {text!r}")
    return user, domain, challenge, proof, blob


@register("netntlmv2")
class NetNtlmV2Engine(HashEngine):
    """NetNTLMv2 challenge-response (hashcat 5600)."""

    name = "netntlmv2"
    digest_size = 16
    salted = True
    max_candidate_len = 27     # NTLM single-block UTF-16LE limit

    def parse_target(self, text: str) -> Target:
        user, domain, challenge, proof, blob = parse_netntlmv2(text)
        return Target(raw=text.strip(), digest=proof,
                      params={"user": user, "domain": domain,
                              "challenge": challenge, "blob": blob})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("netntlmv2 needs target params")
        return [netntlmv2_proof(c, params["user"], params["domain"],
                                params["challenge"], params["blob"])
                for c in candidates]


@register("ntlm")
class NtlmEngine(HashEngine):
    """NTLM: MD4 over the UTF-16LE encoding of the password."""

    name = "ntlm"
    digest_size = 16
    # 27 chars -> 54 UTF-16LE bytes, still a single MD4 block after padding.
    max_candidate_len = 27

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        # Candidates are raw bytes; treat them as latin-1 text so the
        # UTF-16LE widening is the byte-interleave NTLM expects for the
        # ASCII masks (?l/?u/?d/?s/?a) used by the benchmarks.
        if (len(candidates) < NTLM_ARRAY_MIN
                or max(map(len, candidates)) > self.max_candidate_len):
            return [md4(c.decode("latin-1").encode("utf-16-le"))
                    for c in candidates]
        return _ntlm_array(candidates)


#: batch length from which NtlmEngine.hash_batch hashes as arrays.  On
#: an x86 host the NumPy MD4 costs 0.31-0.33 ms a call up to 64
#: candidates (0.48 ms at 261), the scalar md4 28 us a candidate (7.1 ms
#: at 261): the two forms cost the same at about 11 candidates
NTLM_ARRAY_MIN = 12


def _ntlm_array(candidates: Sequence[bytes]) -> list[bytes]:
    """NTLM of a batch of candidates of at most max_candidate_len
    bytes (one MD4 block each), byte for byte `md4()` over each one's
    UTF-16LE form, through md4_blocks.  Every row carries its own
    length, so a batch of mixed lengths is exact."""
    width, n = NtlmEngine.max_candidate_len, len(candidates)
    lens = np.fromiter(map(len, candidates), dtype=np.int64, count=n)
    chars = np.frombuffer(b"".join(c.ljust(width, b"\0")
                                   for c in candidates),
                          dtype=np.uint8).reshape(n, width)
    block = np.zeros((n, 64), dtype=np.uint8)
    block[:, 0:2 * width:2] = chars     # latin-1 -> UTF-16LE
    block[np.arange(n), 2 * lens] = 0x80
    words = block.view("<u4").astype(np.uint32)
    words[:, 14] = lens * 16            # message bits (< 2^32)
    digests = md4_blocks(words).astype("<u4").tobytes()
    return [digests[i:i + 16] for i in range(0, 16 * n, 16)]


@register("bcrypt")
class BcryptEngine(HashEngine):
    """bcrypt (EksBlowfish).  Salted: digests are per-(candidate, target)."""

    name = "bcrypt"
    digest_size = 23
    salted = True
    max_candidate_len = 72

    def parse_target(self, text: str) -> Target:
        variant, cost, salt, digest = _bcrypt.parse_hash(text)
        if not 4 <= cost <= 31:
            raise ValueError(f"bcrypt cost out of range 4..31: {cost}")
        return Target(raw=text.strip(), digest=digest,
                      params={"variant": variant, "cost": cost, "salt": salt})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("bcrypt needs target params (salt, cost)")
        salt, cost = params["salt"], params["cost"]
        return [_bcrypt.bcrypt_raw(c, salt, cost) for c in candidates]


@register("md5crypt")
class Md5cryptEngine(HashEngine):
    """$1$ modular crypt (FreeBSD md5crypt; hashcat 500)."""

    name = "md5crypt"
    digest_size = 16
    salted = True
    max_candidate_len = 15    # device single-block budget: 16+2L+8 <= 55

    def parse_target(self, text: str) -> Target:
        from dprf_tpu.engines.cpu.md5crypt import parse_md5crypt
        salt, digest = parse_md5crypt(text)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        from dprf_tpu.engines.cpu.md5crypt import md5crypt_raw
        if not params:
            raise ValueError("md5crypt needs target params (salt)")
        return [md5crypt_raw(c, params["salt"], self.magic)
                for c in candidates]

    #: scheme tag in the initial md5 context; subclasses override.
    magic = b"$1$"


@register("apr1")
@register("apache-md5")
class Apr1Engine(Md5cryptEngine):
    """Apache $apr1$ (htpasswd MD5; hashcat 1600): md5crypt with a
    6-byte magic -- same 1000-round scheme otherwise."""

    name = "apr1"
    magic = b"$apr1$"

    def parse_target(self, text: str) -> Target:
        from dprf_tpu.engines.cpu.md5crypt import parse_md5crypt
        salt, digest = parse_md5crypt(text, prefix="$apr1$")
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt})


@register("sha512crypt")
class Sha512cryptEngine(HashEngine):
    """$6$ modular crypt (Linux shadow default; hashcat 1800)."""

    name = "sha512crypt"
    digest_size = 64
    salted = True
    max_candidate_len = 15    # device budget: 64 + 2L + 16 <= 111

    def parse_target(self, text: str) -> Target:
        from dprf_tpu.engines.cpu.sha512crypt import parse_sha512crypt
        rounds, salt, digest = parse_sha512crypt(text)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt, "rounds": rounds})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        from dprf_tpu.engines.cpu.sha512crypt import sha512crypt_raw
        if not params:
            raise ValueError("sha512crypt needs target params "
                             "(salt, rounds)")
        return [sha512crypt_raw(c, params["salt"], params["rounds"])
                for c in candidates]


@register("sha256crypt")
class Sha256cryptEngine(HashEngine):
    """$5$ modular crypt (hashcat 7400)."""

    name = "sha256crypt"
    digest_size = 32
    salted = True
    max_candidate_len = 15

    def parse_target(self, text: str) -> Target:
        from dprf_tpu.engines.cpu.sha256crypt import parse_sha256crypt
        rounds, salt, digest = parse_sha256crypt(text)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt, "rounds": rounds})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        from dprf_tpu.engines.cpu.sha256crypt import sha256crypt_raw
        if not params:
            raise ValueError("sha256crypt needs target params "
                             "(salt, rounds)")
        return [sha256crypt_raw(c, params["salt"], params["rounds"])
                for c in candidates]


#: pbkdf2 salt + INT(4) + 0x80 + length must fit the U1 block
PBKDF2_SALT_MAX = 51


def parse_pbkdf2_sha256(text: str):
    """-> (iterations, salt bytes, dk bytes).  Accepts Django's
    'pbkdf2_sha256$iter$salt$b64' and hashcat 10900's
    'sha256:iter:b64salt:b64dk'."""
    import base64
    t = text.strip()
    if t.startswith("pbkdf2_sha256$"):
        parts = t.split("$")
        if len(parts) != 4:
            raise ValueError(f"malformed Django pbkdf2 line: {text!r}")
        iters = int(parts[1])
        salt = parts[2].encode("latin-1")
        dk = base64.b64decode(parts[3])
    elif t.startswith("sha256:"):
        parts = t.split(":")
        if len(parts) != 4:
            raise ValueError(f"malformed pbkdf2 line: {text!r}")
        iters = int(parts[1])
        salt = base64.b64decode(parts[2])
        dk = base64.b64decode(parts[3])
    else:
        raise ValueError(f"not a pbkdf2-sha256 line: {text!r}")
    if not 1 <= iters <= (1 << 31) - 1:
        raise ValueError(f"iterations out of range in {text!r}")
    if len(salt) > PBKDF2_SALT_MAX:
        raise ValueError(f"salt longer than {PBKDF2_SALT_MAX} bytes: "
                         f"{text!r}")
    if len(dk) != 32:
        raise ValueError(f"expected a 32-byte derived key: {text!r}")
    return iters, salt, dk


@register("pbkdf2-sha256")
class Pbkdf2Sha256Engine(HashEngine):
    """PBKDF2-HMAC-SHA256 (Django default hasher; hashcat 10900)."""

    name = "pbkdf2-sha256"
    digest_size = 32
    salted = True
    max_candidate_len = 64    # single-block HMAC key

    def parse_target(self, text: str) -> Target:
        iters, salt, dk = parse_pbkdf2_sha256(text)
        return Target(raw=text.strip(), digest=dk,
                      params={"salt": salt, "iterations": iters})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("pbkdf2-sha256 needs target params")
        return [hashlib.pbkdf2_hmac("sha256", c, params["salt"],
                                    params["iterations"], 32)
                for c in candidates]


_CISCO_ITOA64 = ("./0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
                 "abcdefghijklmnopqrstuvwxyz")
_STD_B64 = ("ABCDEFGHIJKLMNOPQRSTUVWXYZ"
            "abcdefghijklmnopqrstuvwxyz0123456789+/")
_TO_STD = str.maketrans(_CISCO_ITOA64, _STD_B64)
_FROM_STD = str.maketrans(_STD_B64, _CISCO_ITOA64)


def cisco8_encode(dk: bytes) -> str:
    """Cisco type 8 digest text: standard base64 bit order, itoa64
    alphabet, no padding (verified against the published mode-9200
    example hash)."""
    import base64
    return base64.b64encode(dk).decode().rstrip("=").translate(_FROM_STD)


def cisco8_decode(text: str) -> bytes:
    import base64
    std = text.translate(_TO_STD)
    # validate=True: a char outside the itoa64 alphabet must raise, not
    # silently decode into a wrong digest
    return base64.b64decode(std + "=" * (-len(std) % 4), validate=True)


@register("cisco8")
@register("cisco-ios-8")
class Cisco8Engine(HashEngine):
    """Cisco IOS type 8 ($8$salt$hash): PBKDF2-HMAC-SHA256, 20000
    iterations, 32-byte dk (hashcat 9200).  Execution is the
    pbkdf2-sha256 path; only the line format differs."""

    name = "cisco8"
    digest_size = 32
    salted = True
    max_candidate_len = 64

    def parse_target(self, text: str) -> Target:
        t = text.strip()
        parts = t.split("$")
        if len(parts) != 4 or parts[0] != "" or parts[1] != "8":
            raise ValueError(f"not a Cisco type 8 hash: {text!r}")
        salt = parts[2].encode("latin-1")
        if not salt or len(salt) > PBKDF2_SALT_MAX:
            raise ValueError(f"bad Cisco type 8 salt in {text!r}")
        dk = cisco8_decode(parts[3])
        if len(dk) != 32:
            raise ValueError(f"Cisco type 8 wants a 32-byte dk: {text!r}")
        return Target(raw=t, digest=dk,
                      params={"salt": salt, "iterations": 20000})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("cisco8 needs target params")
        return [hashlib.pbkdf2_hmac("sha256", c, params["salt"],
                                    params["iterations"], 32)
                for c in candidates]


@register("pbkdf2-sha1")
class Pbkdf2Sha1Engine(HashEngine):
    """Generic PBKDF2-HMAC-SHA1 (hashcat 12000:
    'sha1:iter:b64salt:b64dk', dk 4..40 bytes in 4-byte steps)."""

    name = "pbkdf2-sha1"
    digest_size = 20           # nominal; per-target dk width may differ
    salted = True
    max_candidate_len = 64

    def parse_target(self, text: str) -> Target:
        import base64
        t = text.strip()
        parts = t.split(":")
        if len(parts) != 4 or parts[0] != "sha1":
            raise ValueError(f"not a pbkdf2-sha1 line: {text!r}")
        iters = int(parts[1])
        salt = base64.b64decode(parts[2])
        dk = base64.b64decode(parts[3])
        if not 1 <= iters <= (1 << 31) - 1:
            raise ValueError(f"iterations out of range in {text!r}")
        if len(salt) > PBKDF2_SALT_MAX:
            raise ValueError(f"salt longer than {PBKDF2_SALT_MAX}: "
                             f"{text!r}")
        if not 4 <= len(dk) <= 40 or len(dk) % 4:
            raise ValueError("derived key must be 4..40 bytes in 4-byte "
                             f"steps: {text!r}")
        return Target(raw=t, digest=dk,
                      params={"salt": salt, "iterations": iters,
                              "dklen": len(dk)})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("pbkdf2-sha1 needs target params")
        return [hashlib.pbkdf2_hmac("sha1", c, params["salt"],
                                    params["iterations"],
                                    params.get("dklen", 20))
                for c in candidates]


@register("atlassian")
@register("pkcs5s2")
class AtlassianEngine(Pbkdf2Sha1Engine):
    """Atlassian/Crowd {PKCS5S2} (hashcat 12001): PBKDF2-HMAC-SHA1,
    10000 iterations, base64(16-byte salt + 32-byte dk)."""

    name = "atlassian"

    def parse_target(self, text: str) -> Target:
        import base64
        t = text.strip()
        tag = "{PKCS5S2}"
        if not t.startswith(tag):
            raise ValueError(f"not a {tag} line: {text!r}")
        try:
            blob = base64.b64decode(t[len(tag):], validate=True)
        except Exception as e:
            raise ValueError(f"bad base64 in {text!r}: {e}")
        if len(blob) != 48:
            raise ValueError(f"{tag} blob must be 48 bytes "
                             f"(16 salt + 32 dk): {text!r}")
        return Target(raw=t, digest=blob[16:],
                      params={"salt": blob[:16], "iterations": 10000,
                              "dklen": 32})


@register("phpass")
class PhpassEngine(HashEngine):
    """phpass portable hashes ($P$/$H$, WordPress/phpBB; hashcat 400):
    h = md5(salt+pass), then count x h = md5(h+pass)."""

    name = "phpass"
    digest_size = 16
    salted = True

    from dprf_tpu.engines.cpu.phpass import MAX_PASS_LEN as \
        max_candidate_len  # noqa: F401  (39: digest+pass in one block)

    def parse_target(self, text: str) -> Target:
        from dprf_tpu.engines.cpu.phpass import parse_phpass
        count, salt, digest = parse_phpass(text)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt, "count": count})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        from dprf_tpu.engines.cpu.phpass import phpass_raw
        if not params:
            raise ValueError("phpass needs target params (salt, count)")
        return [phpass_raw(c, params["salt"], params["count"])
                for c in candidates]


@register("wpa2-eapol")
@register("wpa2")
class Wpa2EapolEngine(HashEngine):
    """WPA2 4-way-handshake MIC (hc22000 WPA*02 lines; hashcat 22000).
    Same PBKDF2 cost as PMKID plus PRF-512 and the EAPOL HMAC."""

    name = "wpa2-eapol"
    digest_size = 16
    salted = True
    max_candidate_len = 63    # WPA passphrase limit
    iterations = 4096         # PBKDF2 rounds; tests lower it

    def parse_target(self, text: str) -> Target:
        from dprf_tpu.engines.cpu.wpa2 import parse_wpa02
        f = parse_wpa02(text)
        return Target(raw=text.strip(), digest=f.pop("mic"), params=f)

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        from dprf_tpu.engines.cpu.wpa2 import wpa2_mic
        if not params:
            raise ValueError("wpa2-eapol needs target params")
        return [wpa2_mic(c, params["essid"], params["mac_ap"],
                         params["mac_sta"], params["anonce"],
                         params["eapol"], params["keyver"],
                         self.iterations)
                for c in candidates]


@register("wpa2-pmkid")
class Pmkid2Engine(HashEngine):
    """WPA2-PMKID: PMK = PBKDF2-HMAC-SHA1(pass, essid, 4096, 32);
    PMKID = HMAC-SHA1(PMK, "PMK Name" | MAC_AP | MAC_STA)[:16].

    Target lines use the hashcat 16800 format:
    ``pmkid*mac_ap*mac_sta*essid_hex`` (macs as 12 hex chars, no colons).
    """

    name = "wpa2-pmkid"
    digest_size = 16
    salted = True
    max_candidate_len = 63    # WPA passphrase limit
    iterations = 4096         # PBKDF2 rounds; tests lower it for speed

    def parse_target(self, text: str) -> Target:
        parts = text.strip().split("*")
        if len(parts) != 4:
            raise ValueError(f"expected pmkid*mac_ap*mac_sta*essid, got {text!r}")
        pmkid, mac_ap, mac_sta, essid_hex = parts
        digest = bytes.fromhex(pmkid)
        ap, sta = bytes.fromhex(mac_ap), bytes.fromhex(mac_sta)
        if len(digest) != self.digest_size:
            raise ValueError(f"PMKID must be {self.digest_size} bytes, "
                             f"got {len(digest)} from {text!r}")
        if len(ap) != 6 or len(sta) != 6:
            raise ValueError(f"MACs must be 6 bytes each in {text!r}")
        return Target(
            raw=text.strip(),
            digest=digest,
            params={"essid": bytes.fromhex(essid_hex),
                    "mac_ap": ap, "mac_sta": sta})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("wpa2-pmkid needs target params (essid, macs)")
        message = b"PMK Name" + params["mac_ap"] + params["mac_sta"]
        out = []
        for c in candidates:
            pmk = hashlib.pbkdf2_hmac("sha1", c, params["essid"],
                                      self.iterations, 32)
            out.append(hmac.new(pmk, message, hashlib.sha1).digest()[:16])
        return out


# Convenience aliases matching common reference spellings.
register("pmkid")(Pmkid2Engine)
register("sha-1")(Sha1Engine)
register("sha-256")(Sha256Engine)


class _HmacCpuMixin(HashEngine):
    """CPU oracle for the HMAC fast modes over ``hexdigest:salt`` lines:
    key = $pass, message = $salt (hashcat 50/150/1450) or key = $salt,
    message = $pass (60/160/1460)."""

    salted = True
    _algo: str
    _key_is_pass: bool

    def parse_target(self, text: str) -> Target:
        digest, salt = parse_salted_line(text, self.digest_size)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError(f"{self.name} needs target params (salt)")
        salt = params["salt"]
        if self._key_is_pass:
            return [hmac.new(c, salt, self._algo).digest()
                    for c in candidates]
        return [hmac.new(salt, c, self._algo).digest()
                for c in candidates]


def _register_hmac_cpu(algo: str, digest_size: int):
    for key_is_pass in (True, False):
        name = f"hmac-{algo}" + ("" if key_is_pass else "-salt")
        key, msg = (("$pass", "$salt") if key_is_pass
                    else ("$salt", "$pass"))
        cls = type(f"Hmac{algo.title()}{'Pass' if key_is_pass else 'Salt'}"
                   "Engine", (_HmacCpuMixin,),
                   {"name": name, "digest_size": digest_size,
                    "_algo": algo, "_key_is_pass": key_is_pass,
                    "__doc__": (f"HMAC-{algo.upper()} (key = {key}, "
                                f"message = {msg}); 'hexdigest:salt' "
                                "lines."),
                    # key = $pass: candidate must fit one key block;
                    # key = $salt: candidate is a one-block message.
                    "max_candidate_len": 64 if key_is_pass else 55})
        register(name, device="cpu")(cls)


_register_hmac_cpu("md5", 16)
_register_hmac_cpu("sha1", 20)
_register_hmac_cpu("sha256", 32)


@register("jwt-hs256")
@register("jwt")
class JwtHs256Engine(HashEngine):
    """JWT HS256 (hashcat 16500): HMAC-SHA256(secret, signing input)
    where a target line is the full ``header.payload.signature`` token
    (base64url) and the signing input ``header.payload`` is a per-target
    message constant."""

    name = "jwt-hs256"
    digest_size = 32
    salted = True
    max_candidate_len = 64

    @staticmethod
    def _b64url(text: str) -> bytes:
        import base64
        pad = "=" * (-len(text) % 4)
        return base64.urlsafe_b64decode(text + pad)

    def parse_target(self, text: str) -> Target:
        parts = text.strip().split(".")
        if len(parts) != 3:
            raise ValueError(f"expected header.payload.signature JWT, "
                             f"got {text!r}")
        sig = self._b64url(parts[2])
        if len(sig) != self.digest_size:
            raise ValueError(
                f"JWT signature must be {self.digest_size} bytes "
                f"(HS256), got {len(sig)} from {text!r}")
        msg = (parts[0] + "." + parts[1]).encode("ascii")
        return Target(raw=text.strip(), digest=sig, params={"msg": msg})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("jwt-hs256 needs target params (msg)")
        return [hmac.new(c, params["msg"], hashlib.sha256).digest()
                for c in candidates]


@register("scrypt")
class ScryptEngine(HashEngine):
    """scrypt (RFC 7914; hashcat 8900): memory-hard KDF with
    ``SCRYPT:N:r:p:<b64 salt>:<b64 dk>`` target lines.  N, r, p are
    per-target parameters; the derived key is 32 bytes."""

    name = "scrypt"
    digest_size = 32
    salted = True
    max_candidate_len = 64     # one HMAC-SHA256 key block

    def parse_target(self, text: str) -> Target:
        import base64
        parts = text.strip().split(":")
        if len(parts) != 6 or parts[0].upper() != "SCRYPT":
            raise ValueError(
                f"expected SCRYPT:N:r:p:salt:dk, got {text!r}")
        n, r, p = (int(x) for x in parts[1:4])
        if n < 2 or n & (n - 1):
            raise ValueError(f"scrypt N must be a power of two: {n}")
        if n > 1 << 24:
            # V alone would be 128*r*N bytes per candidate; an absurd N
            # in one hostile line must not OOM the process
            raise ValueError(f"scrypt N={n} over the 2^24 limit")
        if not (1 <= r <= 32 and 1 <= p <= 16) or p * 4 * r > 255:
            raise ValueError(f"unsupported scrypt r={r} p={p}")
        salt = base64.b64decode(parts[4])
        digest = base64.b64decode(parts[5])
        if len(digest) != self.digest_size:
            raise ValueError(
                f"scrypt dk must be {self.digest_size} bytes, got "
                f"{len(digest)}")
        if len(salt) > PBKDF2_SALT_MAX:
            raise ValueError(
                f"salt longer than {PBKDF2_SALT_MAX} bytes")
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt, "n": n, "r": r, "p": p})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("scrypt needs target params (salt, n, r, p)")
        n, r, p = params["n"], params["r"], params["p"]
        # maxmem: V alone is 128*r*N bytes; give the libcrypto check
        # ample headroom.
        mem = 128 * r * n * max(1, p) * 2 + (1 << 20)
        return [hashlib.scrypt(c, salt=params["salt"], n=n, r=r, p=p,
                               dklen=self.digest_size, maxmem=mem)
                for c in candidates]


@register("zip2")
@register("winzip")
class Zip2Engine(HashEngine):
    """WinZip AES (hashcat 13600): ``$zip2$*0*M*0*salt*verify*dlen*
    data*auth*$/zip2$`` where M selects AES-128/192/256 (keylen
    16/24/32, salt 8/12/16).  DK = PBKDF2-HMAC-SHA1(pass, salt, 1000,
    2*keylen+2); the last 2 DK bytes are the password verification
    value (a 1/2^16 prefilter) and the stored auth code is
    HMAC-SHA1(DK[keylen:2*keylen], data)[:10] -- the digest this
    engine compares."""

    name = "zip2"
    digest_size = 10
    salted = True
    max_candidate_len = 64
    iterations = 1000

    _KEYLEN = {1: 16, 2: 24, 3: 32}

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        if not (body.startswith("$zip2$*") and body.endswith("*$/zip2$")):
            raise ValueError(f"expected $zip2$*...*$/zip2$ line, "
                             f"got {text[:40]!r}")
        parts = body[len("$zip2$*"):-len("*$/zip2$")].split("*")
        if len(parts) != 8:
            raise ValueError(f"expected 8 '*' fields in {text[:40]!r}")
        type_, mode, magic, salt_hex, verify_hex, dlen_hex, data_hex, \
            auth_hex = parts
        if type_ != "0" or magic != "0":
            # hashcat 13600 fixes both fields to 0 (AE-2); anything
            # else is a format we would crack under wrong semantics
            raise ValueError(
                f"unsupported zip2 version/magic {type_}/{magic}")
        mode = int(mode)
        if mode not in self._KEYLEN:
            raise ValueError(f"zip2 mode must be 1/2/3, got {mode}")
        salt = bytes.fromhex(salt_hex)
        if len(salt) != 4 + 4 * mode:
            raise ValueError(f"zip2 mode {mode} needs a "
                             f"{4 + 4 * mode}-byte salt")
        verify = bytes.fromhex(verify_hex)
        if len(verify) != 2:
            raise ValueError("zip2 verify value must be 2 bytes")
        data = bytes.fromhex(data_hex)
        if int(dlen_hex, 16) != len(data):
            raise ValueError("zip2 data length field disagrees with data")
        auth = bytes.fromhex(auth_hex)
        if len(auth) != self.digest_size:
            raise ValueError("zip2 auth code must be 10 bytes")
        return Target(raw=body, digest=auth,
                      params={"salt": salt, "mode": mode,
                              "verify": verify, "data": data})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("zip2 needs target params (salt, mode, data)")
        kl = self._KEYLEN[params["mode"]]
        out = []
        for c in candidates:
            dk = hashlib.pbkdf2_hmac("sha1", c, params["salt"],
                                     self.iterations, 2 * kl + 2)
            out.append(hmac.new(dk[kl:2 * kl], params["data"],
                                hashlib.sha1).digest()[:self.digest_size])
        return out


def _utf16_lower_user(user: str) -> bytes:
    return user.lower().encode("utf-16-le")


#: DCC outer-block budget: 16 digest bytes + salt + 0x80 + 8-byte
#: length must fit one 64-byte MD4 block -> salt <= 39 bytes; an even
#: byte count (UTF-16LE) makes that 38 bytes = 19 characters (Windows
#: caps sAMAccountName at 20, so 19 covers all but the edge).
DCC_USER_MAX = 19


def _parse_user_digest(text_digest_hex: str, user: str,
                       digest_size: int):
    """Shared mscache/mscache2 field validation -> (digest, salt)."""
    digest = bytes.fromhex(text_digest_hex)
    if len(digest) != digest_size:
        raise ValueError(f"expected {digest_size}-byte digest, "
                         f"got {len(digest)}")
    if not user:
        raise ValueError("empty username")
    if len(user) > DCC_USER_MAX:
        raise ValueError(f"username longer than {DCC_USER_MAX} chars")
    return digest, _utf16_lower_user(user)


def _dcc1(password: bytes, user_salt: bytes) -> bytes:
    """MS Cache v1: MD4(MD4(UTF16LE(pw)) || UTF16LE(lower(user)))."""
    inner = md4(password.decode("latin-1").encode("utf-16-le"))
    return md4(inner + user_salt)


@register("mscache")
@register("dcc")
class MsCacheEngine(HashEngine):
    """MS Cache v1 / Domain Cached Credentials (hashcat 1100):
    ``hexdigest:username`` lines; digest = MD4(MD4(UTF16LE(pw)) ||
    UTF16LE(lower(user)))."""

    name = "mscache"
    digest_size = 16
    salted = True
    max_candidate_len = 27     # UTF-16LE widening: one MD4 block

    def parse_target(self, text: str) -> Target:
        digest_hex, sep, user = text.strip().partition(":")
        if not sep or not user:
            raise ValueError(f"expected 'digest:username', got {text!r}")
        digest, salt = _parse_user_digest(digest_hex, user,
                                          self.digest_size)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt, "user": user})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("mscache needs target params (user)")
        return [_dcc1(c, params["salt"]) for c in candidates]


@register("mscache2")
@register("dcc2")
class MsCache2Engine(HashEngine):
    """MS Cache v2 / DCC2 (hashcat 2100): ``$DCC2$<iter>#<user>#<hex>``
    lines; digest = PBKDF2-HMAC-SHA1(DCC1, UTF16LE(lower(user)),
    iterations, 16)."""

    name = "mscache2"
    digest_size = 16
    salted = True
    max_candidate_len = 27

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        if not body.startswith("$DCC2$"):
            raise ValueError(f"expected $DCC2$iter#user#hash, got {text!r}")
        parts = body[len("$DCC2$"):].split("#")
        if len(parts) != 3:
            raise ValueError(f"expected 3 '#' fields in {text!r}")
        iterations = int(parts[0])
        if not 1 <= iterations <= (1 << 24):
            raise ValueError(f"unreasonable DCC2 iterations {iterations}")
        user = parts[1]
        digest, salt = _parse_user_digest(parts[2], user,
                                          self.digest_size)
        return Target(raw=body, digest=digest,
                      params={"salt": salt, "user": user,
                              "iterations": iterations})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("mscache2 needs target params (user, iters)")
        return [hashlib.pbkdf2_hmac("sha1", _dcc1(c, params["salt"]),
                                    params["salt"],
                                    params["iterations"], 16)
                for c in candidates]


@register("lm")
class LmEngine(HashEngine):
    """LM hash, one half (hashcat 3000): DES_{str_to_key(upper(pw))}
    ("KGS!@#$%") over a <= 7-char half.  A full 16-byte LM hash is two
    independent halves -- split it into two lines.  Candidates are
    uppercased here (LM is case-insensitive), so lowercase masks and
    wordlists work unchanged."""

    name = "lm"
    digest_size = 8
    max_candidate_len = 7

    def parse_target(self, text: str) -> Target:
        t = text.strip()
        digest = bytes.fromhex(t)
        if len(digest) == 16:
            raise ValueError(
                "full 16-byte LM hash: split it into its two 8-byte "
                "halves (one line each); each half cracks independently")
        if len(digest) != self.digest_size:
            raise ValueError(f"lm wants 8 digest bytes, got {text!r}")
        return Target(raw=t, digest=digest)

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        from dprf_tpu.ops.des import lm_half
        # a candidate longer than 7 bytes can never BE an LM half:
        # an empty digest compares unequal to every 8-byte target
        # (rule expansions may legitimately overshoot; truncating
        # instead would report plaintexts that don't hash to the
        # target)
        return [lm_half(c) if len(c) <= 7 else b"" for c in candidates]


def netntlmv1_response(password: bytes, challenge: bytes) -> bytes:
    """NetNTLMv1 NT response: the 16-byte NTLM hash zero-padded to 21
    bytes makes three DES keys; each encrypts the 8-byte challenge."""
    from dprf_tpu.ops.des import des_encrypt, str_to_key
    key21 = _md4_utf16(password) + bytes(5)
    return b"".join(des_encrypt(str_to_key(key21[7 * i:7 * i + 7]),
                                challenge) for i in range(3))


@register("netntlmv1")
class NetNtlmV1Engine(HashEngine):
    """NetNTLMv1 challenge-response (hashcat 5500):
    ``user::domain:lmresp(48 hex):ntresp(48 hex):challenge(16 hex)``
    lines; the NT response (24 bytes) is the digest."""

    name = "netntlmv1"
    digest_size = 24
    salted = True
    max_candidate_len = 27

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        parts = body.split(":")
        if len(parts) != 6 or parts[1]:
            raise ValueError(
                f"expected user::domain:lm:nt:challenge, got {text[:40]!r}")
        lmresp = bytes.fromhex(parts[3])
        ntresp = bytes.fromhex(parts[4])
        challenge = bytes.fromhex(parts[5])
        if len(ntresp) != self.digest_size:
            raise ValueError("NT response must be 24 bytes")
        if len(challenge) != 8:
            raise ValueError("server challenge must be 8 bytes")
        if len(lmresp) == 24 and lmresp[8:] == bytes(16) \
                and lmresp[:8] != bytes(8):
            # NTLMv1-ESS / SSP: the LM field carries the CLIENT
            # challenge and the DES input is MD5(server||client)[:8];
            # checking against the raw server challenge would silently
            # never match such captures
            challenge = hashlib.md5(challenge + lmresp[:8]).digest()[:8]
        return Target(raw=body, digest=ntresp,
                      params={"challenge": challenge, "user": parts[0],
                              "domain": parts[2]})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("netntlmv1 needs target params (challenge)")
        return [netntlmv1_response(c, params["challenge"])
                for c in candidates]


@register("office2007")
@register("office")
class Office2007Engine(HashEngine):
    """MS Office 2007 standard encryption (hashcat 9400):
    ``$office$*2007*20*128*16*<salt>*<encVerifier>*<encVerifierHash>``.
    Key = 50,002-round SHA-1 spin of (salt, UTF-16LE password) through
    the MS-OFFCRYPTO derivation; a candidate matches when
    SHA1(AES128dec(key, verifier)) equals the decrypted verifier hash.
    The comparable digest is a 1-byte match marker (the check is a
    decrypt-and-compare, not a digest equality)."""

    name = "office2007"
    digest_size = 1
    salted = True
    max_candidate_len = 19     # salt(16) + UTF-16LE pw in one SHA-1 block
    spin_count = 50000         # tests lower it for speed

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        parts = body.split("*")
        if len(parts) != 8 or parts[0] != "$office$" or \
                parts[1] != "2007":
            raise ValueError(
                f"expected $office$*2007*...*... line, got {text[:40]!r}")
        vsize, ksize, ssize = int(parts[2]), int(parts[3]), int(parts[4])
        if (vsize, ksize, ssize) != (20, 128, 16):
            raise ValueError(
                f"unsupported office2007 parameters {vsize}/{ksize}/"
                f"{ssize} (SHA-1 + AES-128 only)")
        salt = bytes.fromhex(parts[5])
        ev = bytes.fromhex(parts[6])
        evh = bytes.fromhex(parts[7])
        if len(salt) != 16 or len(ev) != 16 or len(evh) != 32:
            raise ValueError("bad office2007 field lengths")
        return Target(raw=body, digest=b"\x01",
                      params={"salt": salt, "verifier": ev,
                              "verifier_hash": evh})

    def _derive_key(self, password: bytes, salt: bytes) -> bytes:
        h = hashlib.sha1(
            salt + password.decode("latin-1").encode("utf-16-le")).digest()
        for i in range(self.spin_count):
            h = hashlib.sha1(i.to_bytes(4, "little") + h).digest()
        h = hashlib.sha1(h + (0).to_bytes(4, "little")).digest()
        buf = bytearray(b"\x36" * 64)
        for i, b in enumerate(h):
            buf[i] ^= b
        return hashlib.sha1(bytes(buf)).digest()[:16]

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("office2007 needs target params")
        from dprf_tpu.ops.aes import aes128_decrypt_block
        ev, evh = params["verifier"], params["verifier_hash"]
        out = []
        for c in candidates:
            key = self._derive_key(c, params["salt"])
            verifier = aes128_decrypt_block(key, ev)
            vhash = (aes128_decrypt_block(key, evh[:16])
                     + aes128_decrypt_block(key, evh[16:]))
            ok = hashlib.sha1(verifier).digest() == vhash[:20]
            out.append(b"\x01" if ok else b"\x00")
        return out


#: MS-OFFCRYPTO agile block keys (specification constants): the two
#: purposes of the password key encryptor's verifier.
OFFICE_BK_INPUT = bytes((0xFE, 0xA7, 0xD2, 0x76, 0x3B, 0x4B, 0x9E, 0x79))
OFFICE_BK_VALUE = bytes((0xD7, 0xAA, 0x0F, 0x6D, 0x30, 0x61, 0x34, 0x4E))


class _OfficeAgileEngine(HashEngine):
    """MS Office agile encryption (2010: SHA-1 + AES-128, hashcat
    9500; 2013: SHA-512 + AES-256, 9600):
    ``$office$*<ver>*<spin>*<keybits>*16*salt*encVerifier*encVerifierHash``.
    Match = H(CBCdec(key_input, verifier)) vs CBCdec(key_value,
    verifierHash) over the stored prefix."""

    digest_size = 1
    salted = True
    _version: str
    _hash: str
    _keybits: int

    @property
    def max_candidate_len(self):
        # salt(16) + UTF-16LE pw in one hash block
        return 19 if self._hash == "sha1" else 47

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        parts = body.split("*")
        if len(parts) != 8 or parts[0] != "$office$" or \
                parts[1] != self._version:
            raise ValueError(f"expected $office$*{self._version}*... "
                             f"line, got {text[:40]!r}")
        spin = int(parts[2])
        if not 1 <= spin <= (1 << 24):
            raise ValueError(f"unreasonable spin count {spin}")
        if int(parts[3]) != self._keybits or int(parts[4]) != 16:
            raise ValueError(
                f"office{self._version} expects {self._keybits}-bit "
                "keys and 16-byte salts")
        salt = bytes.fromhex(parts[5])
        ev = bytes.fromhex(parts[6])
        evh = bytes.fromhex(parts[7])
        if len(salt) != 16 or len(ev) != 16 or len(evh) != 32:
            raise ValueError("bad office agile field lengths")
        return Target(raw=body, digest=b"\x01",
                      params={"salt": salt, "verifier": ev,
                              "verifier_hash": evh, "spin": spin})

    def _agile_spin(self, password: bytes, salt: bytes,
                    spin: int) -> bytes:
        H = getattr(hashlib, self._hash)    # no name lookup per round
        h = H(salt
              + password.decode("latin-1").encode("utf-16-le")).digest()
        for i in range(spin):
            h = H(i.to_bytes(4, "little") + h).digest()
        return h

    def _agile_final(self, h: bytes, block_key: bytes) -> bytes:
        return hashlib.new(self._hash,
                           h + block_key).digest()[:self._keybits // 8]

    def _agile_key(self, password: bytes, salt: bytes, spin: int,
                   block_key: bytes) -> bytes:
        return self._agile_final(self._agile_spin(password, salt, spin),
                                 block_key)

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError(f"{self.name} needs target params")
        from dprf_tpu.ops.aes import aes_decrypt_block
        salt, spin = params["salt"], params["spin"]
        ev, evh = params["verifier"], params["verifier_hash"]
        out = []
        for c in candidates:
            # ONE spin per candidate; the two block-key finals share it
            h = self._agile_spin(c, salt, spin)
            ki = self._agile_final(h, OFFICE_BK_INPUT)
            kv = self._agile_final(h, OFFICE_BK_VALUE)
            inp = bytes(a ^ b for a, b in
                        zip(aes_decrypt_block(ki, ev), salt))
            v1 = bytes(a ^ b for a, b in
                       zip(aes_decrypt_block(kv, evh[:16]), salt))
            v2 = bytes(a ^ b for a, b in
                       zip(aes_decrypt_block(kv, evh[16:]), evh[:16]))
            want = hashlib.new(self._hash, inp).digest()
            # the stored value holds min(32, hash size) comparable
            # bytes (sha1's 20-byte digest is padded in the file; the
            # pad bytes are not part of the check)
            n = min(32, len(want))
            out.append(b"\x01" if (v1 + v2)[:n] == want[:n]
                       else b"\x00")
        return out


@register("office2010")
class Office2010Engine(_OfficeAgileEngine):
    name = "office2010"
    _version = "2010"
    _hash = "sha1"
    _keybits = 128


@register("office2013")
class Office2013Engine(_OfficeAgileEngine):
    name = "office2013"
    _version = "2013"
    _hash = "sha512"
    _keybits = 256


def rar5_pswcheck(dk32: bytes) -> bytes:
    """RAR5 password check value: XOR of the 8-byte quarters of the
    32-byte derived key computed at iterations + 32."""
    q = [dk32[8 * i:8 * i + 8] for i in range(4)]
    return bytes(a ^ b ^ c ^ d for a, b, c, d in zip(*q))


@register("rar5")
class Rar5Engine(HashEngine):
    """RAR5 (hashcat 13000): ``$rar5$16$<salt>$<log2 iter>$<iv>$8$
    <pswcheck>``.  Key = PBKDF2-HMAC-SHA256(pass, salt, 2^n + 32);
    the stored 8-byte check is the XOR of the dk's quarters."""

    name = "rar5"
    digest_size = 8
    salted = True
    max_candidate_len = 64

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        parts = body.split("$")
        if len(parts) != 8 or parts[0] or parts[1] != "rar5":
            raise ValueError(
                f"expected $rar5$16$salt$n$iv$8$check, got {text[:40]!r}")
        if int(parts[2]) != 16 or int(parts[6]) != 8:
            raise ValueError("rar5 expects 16-byte salts and 8-byte "
                             "check values")
        salt = bytes.fromhex(parts[3])
        n = int(parts[4])
        if not 1 <= n <= 24:
            raise ValueError(f"unreasonable rar5 iteration exponent {n}")
        check = bytes.fromhex(parts[7])
        if len(salt) != 16 or len(check) != 8:
            raise ValueError("bad rar5 field lengths")
        return Target(raw=body, digest=check,
                      params={"salt": salt, "iterations": (1 << n) + 32})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("rar5 needs target params (salt, iters)")
        return [rar5_pswcheck(hashlib.pbkdf2_hmac(
                    "sha256", c, params["salt"], params["iterations"], 32))
                for c in candidates]


class _EthereumEngineBase(HashEngine):
    """Ethereum keystore (v3) wallets: MAC = Keccak-256(dk[16:32] ||
    ciphertext) compared against the stored mac."""

    digest_size = 32
    salted = True
    max_candidate_len = 64

    def _mac(self, dk: bytes, params: dict) -> bytes:
        from dprf_tpu.ops.keccak import keccak256
        return keccak256(dk[16:32] + params["ct"])

    @staticmethod
    def _check_fields(salt: bytes, ct: bytes, mac: bytes) -> None:
        if len(mac) != 32:
            raise ValueError("ethereum mac must be 32 bytes")
        if len(salt) > PBKDF2_SALT_MAX:
            raise ValueError(f"salt longer than {PBKDF2_SALT_MAX} bytes")
        if len(ct) > 119:
            raise ValueError("ciphertext too long for the single-block "
                             "keccak MAC path (>119 bytes)")


@register("ethereum-pbkdf2")
class EthereumPbkdf2Engine(_EthereumEngineBase):
    """Ethereum keystore, PBKDF2 KDF (hashcat 15600):
    ``$ethereum$p*<iter>*<salt hex>*<ct hex>*<mac hex>``."""

    name = "ethereum-pbkdf2"

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        parts = body.split("*")
        if len(parts) != 5 or parts[0] != "$ethereum$p":
            raise ValueError(
                f"expected $ethereum$p*iter*salt*ct*mac, got {text[:40]!r}")
        iterations = int(parts[1])
        if not 1 <= iterations <= (1 << 24):
            raise ValueError(f"unreasonable iteration count {iterations}")
        salt = bytes.fromhex(parts[2])
        ct = bytes.fromhex(parts[3])
        mac = bytes.fromhex(parts[4])
        self._check_fields(salt, ct, mac)
        return Target(raw=body, digest=mac,
                      params={"salt": salt, "iterations": iterations,
                              "ct": ct})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("ethereum-pbkdf2 needs target params")
        return [self._mac(hashlib.pbkdf2_hmac(
                    "sha256", c, params["salt"], params["iterations"], 32),
                          params)
                for c in candidates]


@register("ethereum-scrypt")
class EthereumScryptEngine(_EthereumEngineBase):
    """Ethereum keystore, scrypt KDF (hashcat 15700):
    ``$ethereum$s*<N>*<r>*<p>*<salt hex>*<ct hex>*<mac hex>``."""

    name = "ethereum-scrypt"

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        parts = body.split("*")
        if len(parts) != 7 or parts[0] != "$ethereum$s":
            raise ValueError(
                f"expected $ethereum$s*N*r*p*salt*ct*mac, "
                f"got {text[:40]!r}")
        n, r, p = (int(x) for x in parts[1:4])
        if n < 2 or n & (n - 1) or n > (1 << 24):
            raise ValueError(f"scrypt N must be a power of two <= 2^24, "
                             f"got {n}")
        if not (1 <= r <= 32 and 1 <= p <= 16) or p * 4 * r > 255:
            raise ValueError(f"unsupported scrypt r={r} p={p}")
        salt = bytes.fromhex(parts[4])
        ct = bytes.fromhex(parts[5])
        mac = bytes.fromhex(parts[6])
        self._check_fields(salt, ct, mac)
        return Target(raw=body, digest=mac,
                      params={"salt": salt, "n": n, "r": r, "p": p,
                              "ct": ct})

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        if not params:
            raise ValueError("ethereum-scrypt needs target params")
        n, r, p = params["n"], params["r"], params["p"]
        mem = 128 * r * n * max(1, p) * 2 + (1 << 20)
        return [self._mac(hashlib.scrypt(c, salt=params["salt"], n=n,
                                         r=r, p=p, dklen=32, maxmem=mem),
                          params)
                for c in candidates]


@register("sha3-256")
@register("sha3")
class Sha3_256Engine(HashEngine):
    """SHA3-256 (hashcat 17400): bare 64-hex-digest lines."""

    name = "sha3-256"
    digest_size = 32
    max_candidate_len = 55

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        return [hashlib.sha3_256(c).digest() for c in candidates]


@register("keccak-256")
@register("keccak256")
class Keccak256Engine(HashEngine):
    """Original Keccak-256 (hashcat 17800; Ethereum's hash): bare
    64-hex-digest lines.  Differs from SHA3-256 only in the 0x01
    padding byte."""

    name = "keccak-256"
    digest_size = 32
    max_candidate_len = 55

    def hash_batch(self, candidates: Sequence[bytes],
                   params: Optional[dict] = None) -> list[bytes]:
        from dprf_tpu.ops.keccak import keccak256
        return [keccak256(c) for c in candidates]


#: (bits, sponge rate) for the SHA3/Keccak family; rate = 200 - bits/4
KECCAK_SIZES = [(224, 144), (384, 104), (512, 72)]


def _register_keccak_family():
    """sha3-224/384/512 (hashcat 17300/17500/17600; hashlib oracles)
    and keccak-224/384/512 (17700/17900/18000; scalar sponge oracle).
    256 variants are the explicit classes above."""
    from dprf_tpu.ops.keccak import keccak_digest

    for bits, rate in KECCAK_SIZES:
        def make_sha3_hash(bits):
            def hash_batch(self, candidates, params=None):
                return [hashlib.new(f"sha3_{bits}", c).digest()
                        for c in candidates]
            return hash_batch

        def make_keccak_hash(bits, rate):
            def hash_batch(self, candidates, params=None):
                return [keccak_digest(c, 0x01, rate, bits // 8)
                        for c in candidates]
            return hash_batch

        cls = type(f"Sha3_{bits}Engine", (HashEngine,),
                   {"name": f"sha3-{bits}", "digest_size": bits // 8,
                    "max_candidate_len": rate - 1,
                    "__doc__": f"SHA3-{bits}: bare hex-digest lines.",
                    "hash_batch": make_sha3_hash(bits)})
        register(f"sha3-{bits}", device="cpu")(cls)
        kcls = type(f"Keccak{bits}Engine", (HashEngine,),
                    {"name": f"keccak-{bits}", "digest_size": bits // 8,
                     "max_candidate_len": rate - 1,
                     "__doc__": (f"Original Keccak-{bits} (0x01 "
                                 "padding): bare hex-digest lines."),
                     "hash_batch": make_keccak_hash(bits, rate)})
        register(f"keccak-{bits}", device="cpu")(kcls)
        register(f"keccak{bits}", device="cpu")(kcls)


_register_keccak_family()


@register("postgres")
@register("postgres-md5")
class PostgresMd5Engine(_SaltedCpuMixin):
    """PostgreSQL MD5 auth hashes (hashcat 12): stored as
    ``md5<hex(md5(password || username))>``; target lines are
    ``md5<hex>:username`` or ``<hex>:username`` (``$HEX[..]`` decodes
    non-latin-1 usernames, the shared salted-line convention).  The
    hash itself is the salted-md5 'ps' oracle with the username as
    the salt."""

    name = "postgres"
    digest_size = 16
    _algo = "md5"
    _order = "ps"
    max_candidate_len = 55 - SALT_MAX

    def parse_target(self, text: str) -> Target:
        body = text.strip()
        if body.startswith("md5"):
            body = body[3:]
        digest, salt = parse_salted_line(body, self.digest_size)
        return Target(raw=text.strip(), digest=digest,
                      params={"salt": salt,
                              "user": salt.decode("latin-1")})
