"""Engine `ntlm` (hashcat `-m 1000`): MD4 over the password as
UTF-16LE, unsalted, the hash line its 32 hex digits.  MD4 is written
out here from RFC 1320 (OpenSSL 3 no longer ships it).

The operation count follows `work.py`'s rules on RFC 1320's rounds: a
step is `a = rol(a + f(b,c,d) + M[k] + const, s)`, so f, add, rotate,
one more add for a round constant or a constant non-zero word (folded
together; round 1 has no constant, and only the length word, 14, is a
non-zero constant there), and one more where `M[k]` varies; F at 3
operations, G as `(b & (c | d)) | (c & d)` at 4, H at 2; one block of
two bytes a character; a single target met in the middle, a list not.
"""

import struct

import work

_M32 = 0xFFFFFFFF

#: message word each step reads: RFC 1320's rounds 1, 2 and 3
_K = ([i for i in range(16)]
      + [(i % 4) * 4 + i // 4 for i in range(16)]
      + [0, 8, 4, 12, 2, 10, 6, 14, 1, 9, 5, 13, 3, 11, 7, 15])
_F = [3] * 16 + [4] * 16 + [2] * 16


def _rol(x, s):
    return ((x << s) | (x >> (32 - s))) & _M32


def md4(data):
    """RFC 1320 MD4 of a byte string -> 16 digest bytes."""
    msg = data + b"\x80" + b"\x00" * ((55 - len(data)) % 64) \
        + struct.pack("<Q", 8 * len(data))
    a, b, c, d = 0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476
    for off in range(0, len(msg), 64):
        x = struct.unpack("<16I", msg[off:off + 64])
        aa, bb, cc, dd = a, b, c, d
        for i in range(16):                       # round 1: F, k = i
            s = (3, 7, 11, 19)[i % 4]
            f = (b & c) | (~b & d)
            a, b, c, d = d, _rol((a + f + x[i]) & _M32, s), b, c
        for i in range(16):                       # round 2: G
            s = (3, 5, 9, 13)[i % 4]
            g = (b & c) | (b & d) | (c & d)
            a, b, c, d = d, _rol((a + g + x[_K[16 + i]] + 0x5A827999)
                                 & _M32, s), b, c
        for i in range(16):                       # round 3: H
            s = (3, 9, 11, 15)[i % 4]
            h = b ^ c ^ d
            a, b, c, d = d, _rol((a + h + x[_K[32 + i]] + 0x6ED9EBA1)
                                 & _M32, s), b, c
        a, b, c, d = ((a + aa) & _M32, (b + bb) & _M32,
                      (c + cc) & _M32, (d + dd) & _M32)
    return struct.pack("<4I", a, b, c, d)


def ntlm(password):
    """NTLM: MD4 over the password as UTF-16LE (bytes are latin-1)."""
    return md4(password.decode("latin-1").encode("utf-16-le"))


def target_line(plain, rng, cfg):
    return ntlm(plain).hex()


def filler_line(rng, cfg):
    return "%032x" % rng.getrandbits(128)


def matches(line, plain):
    return ntlm(plain).hex() == line


def ops_per_candidate(length, cfg):
    """MD4 of `length` characters, two bytes each (one block)."""
    varying = work.varying_words(2 * length)
    steps = []
    for i in range(48):
        k = _K[i]
        const = i >= 16 or (k == 14 and k not in varying)
        steps.append(_F[i] + 2 + bool(const) + (k in varying))
    undone = (work.trailing_constant(_K, varying)
              if cfg["targets"] == 1 else 0)
    return sum(steps[:48 - undone])
