"""The plain reference: what a mask job means, written once, slowly,
with nothing of `dprf_tpu` in it.

- index -> candidate: hashcat's built-in charsets (`?l ?u ?d ?s ?a`,
  `??`, literals) and a mixed-radix decode with the RIGHTMOST mask
  position as the least-significant digit (the order the
  configuration files state under `index_order`);
- MD5 from `hashlib`; MD4 written out here from RFC 1320 (OpenSSL 3
  no longer ships it), NTLM = MD4 over the UTF-16LE password.

The comparison (`compare.py`) uses it to make the plants from the
seed, to say which plants a run's covered intervals contain, and to
re-hash every line the program wrote to its potfile.
"""

import hashlib
import struct

LOWER = bytes(range(ord("a"), ord("z") + 1))
UPPER = bytes(range(ord("A"), ord("Z") + 1))
DIGIT = bytes(range(ord("0"), ord("9") + 1))
#: hashcat's ?s: the 33 printable ASCII symbols, space included
SYMBOL = (bytes(range(0x20, 0x30)) + bytes(range(0x3A, 0x41))
          + bytes(range(0x5B, 0x61)) + bytes(range(0x7B, 0x7F)))
CHARSETS = {"l": LOWER, "u": UPPER, "d": DIGIT, "s": SYMBOL,
            "a": LOWER + UPPER + DIGIT + SYMBOL}


def mask_charsets(mask):
    """Mask string -> one charset per position, left to right."""
    out, i = [], 0
    while i < len(mask):
        if mask[i] == "?":
            sel = mask[i + 1]
            out.append(b"?" if sel == "?" else CHARSETS[sel])
            i += 2
        else:
            out.append(mask[i].encode("latin-1"))
            i += 1
    return out


def keyspace(mask):
    n = 1
    for cs in mask_charsets(mask):
        n *= len(cs)
    return n


def candidate(mask, index):
    """The mask's candidate at a keyspace index (odometer order)."""
    out = bytearray()
    for cs in reversed(mask_charsets(mask)):
        index, digit = divmod(index, len(cs))
        out.append(cs[digit])
    if index:
        raise ValueError("index beyond the mask's keyspace")
    return bytes(reversed(out))


# ---------------------------------------------------------------------------
# MD4 (RFC 1320)

_M32 = 0xFFFFFFFF


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
            k = (i % 4) * 4 + i // 4
            s = (3, 5, 9, 13)[i % 4]
            g = (b & c) | (b & d) | (c & d)
            a, b, c, d = d, _rol((a + g + x[k] + 0x5A827999) & _M32, s), \
                b, c
        for i in range(16):                       # round 3: H
            k = (0, 8, 4, 12, 2, 10, 6, 14, 1, 9, 5, 13, 3, 11, 7, 15)[i]
            s = (3, 9, 11, 15)[i % 4]
            h = b ^ c ^ d
            a, b, c, d = d, _rol((a + h + x[k] + 0x6ED9EBA1) & _M32, s), \
                b, c
        a, b, c, d = ((a + aa) & _M32, (b + bb) & _M32,
                      (c + cc) & _M32, (d + dd) & _M32)
    return struct.pack("<4I", a, b, c, d)


def ntlm(password):
    """NTLM: MD4 over the password as UTF-16LE (bytes are latin-1)."""
    return md4(password.decode("latin-1").encode("utf-16-le"))


def md5(password):
    return hashlib.md5(password).digest()


#: engine name (as the configuration's `engine`) -> password -> digest
HASHES = {"md5": md5, "ntlm": ntlm}


def digest_hex(engine, password):
    return HASHES[engine](password).hex()


# ---------------------------------------------------------------------------
# potfile lines, as hashcat writes them: `hash:plain`, a plain with
# bytes outside printable ASCII (or a colon) as `$HEX[..]`

def decode_plain(text):
    if text.startswith("$HEX[") and text.endswith("]"):
        return bytes.fromhex(text[5:-1])
    return text.encode("latin-1")


def read_potfile(path):
    """[(hash line, plain bytes)] of a potfile, in file order."""
    out = []
    try:
        with open(path, encoding="latin-1") as fh:
            for line in fh.read().splitlines():
                if line:
                    h, _, p = line.partition(":")
                    out.append((h, decode_plain(p)))
    except FileNotFoundError:
        pass
    return out
