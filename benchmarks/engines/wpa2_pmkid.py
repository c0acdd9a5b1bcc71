"""Engine `wpa2-pmkid` (hashcat `-m 16800`, the PMKID lines of
`-m 22000`): PMK = PBKDF2-HMAC-SHA1(passphrase, ESSID, 4,096, 32
bytes); PMKID = HMAC-SHA1(PMK, "PMK Name" | MAC_AP | MAC_STA), its first
16 bytes (IEEE 802.11i, 8.5.1.2).  A hash line is
`pmkid*mac_ap*mac_sta*essid`, all four in hex.  The reference is
`hashlib.pbkdf2_hmac` and `hmac`.  Every line has an ESSID and two MACs
of its own, drawn from the generator: a one-target job sweeps one
network (`assumed`, for the configuration that brings the cell).

The operation count: FIPS 180-4's SHA-1 (6.1.2) by `work.py`'s rules,
times the compressions no implementation can avoid.

A compression is 80 steps of
`T = rol5(a) + f(b,c,d) + e + K + W[t]; c = rol30(b)`: two rotates, f,
and four adds where `W[t]` varies, three where it is the same for every
candidate (`K + W[t]` is one constant).  Ch as `d ^ (b & (c ^ d))`: 3
operations; Parity: 2; Maj as `(b & c) | (d & (b | c))`: 4.  A schedule
word `W[t] = rol1(W[t-3] ^ W[t-8] ^ W[t-14] ^ W[t-16])` costs its
rotate and one xor a varying tap beyond the first, one more where the
constant taps do not cancel to zero, and nothing where no tap varies.
Five adds put the block's initial state back on.  An HMAC's blocks are
mostly fixed padding, which is what folds: a 20-byte digest in a block
leaves words 5 to 15 constant (0x80000000, nine zeros, the length), a
key block all but the key's words (ipad or opad), and a block of salt
and counter, or of "PMK Name" and the MACs, varies in no word, so its
schedule is free.

The compressions of one candidate, against one ESSID and one target
(`compressions`): the passphrase's ipad and opad states once (2); for
each of the two 20-byte blocks that make 32 bytes of PMK, 4,096
iterations of an inner and an outer compression (16,384), the first
inner one over salt and counter, every other over a digest; the PMK's
ipad and opad states, and the inner and outer compression of the PMKID
(4): 16,390.  Beside them 4,095 xors of a digest into each block of the
PMK, 5 words in the first and the 3 that are used in the second.  Not
folded, and so counted a hair high (under 0.1 % together): the first
steps of the four compressions that start from SHA-1's constant
initial state, and the last compression's word that the 16-byte
truncation drops.  Nothing is met in the middle: every block's initial
state is the candidate's own.
"""

import hashlib
import hmac

ITERATIONS = 4096
_M32 = 0xFFFFFFFF
_F = [3] * 20 + [2] * 20 + [4] * 20 + [2] * 20      # Ch, Parity, Maj, Parity
#: a 20-byte digest in a block whose message began one block earlier:
#: five varying words, 0x80, zeros, the length of 84 bytes in bits
_DIGEST_BLOCK = [None] * 5 + [0x80000000] + [0] * 9 + [8 * (64 + 20)]


def _network(rng):
    """(MAC_AP, MAC_STA, ESSID) of a line, drawn from the generator."""
    essid = b"net-%08x" % rng.getrandbits(32)
    return (rng.getrandbits(48).to_bytes(6, "big"),
            rng.getrandbits(48).to_bytes(6, "big"), essid)


def _line(pmkid, mac_ap, mac_sta, essid):
    return "*".join(x.hex() for x in (pmkid, mac_ap, mac_sta, essid))


def pmkid(plain, essid, mac_ap, mac_sta):
    pmk = hashlib.pbkdf2_hmac("sha1", plain, essid, ITERATIONS, 32)
    return hmac.new(pmk, b"PMK Name" + mac_ap + mac_sta,
                    hashlib.sha1).digest()[:16]


def target_line(plain, rng, cfg):
    mac_ap, mac_sta, essid = _network(rng)
    return _line(pmkid(plain, essid, mac_ap, mac_sta), mac_ap, mac_sta,
                 essid)


def filler_line(rng, cfg):
    """A uniformly random PMKID on a network of its own: a candidate
    matches it with probability 2^-128."""
    return _line(rng.getrandbits(128).to_bytes(16, "big"), *_network(rng))


def matches(line, plain):
    try:
        want, mac_ap, mac_sta, essid = map(bytes.fromhex, line.split("*"))
    except ValueError:
        return False
    return pmkid(plain, essid, mac_ap, mac_sta) == want


def _rol1(x):
    return ((x << 1) | (x >> 31)) & _M32


def compression_ops(block):
    """One SHA-1 compression from a varying initial state.  block: its
    16 message words, an int where the word is the same for every
    candidate, None where it varies."""
    w, ops = list(block), 0
    for t in range(16, 80):
        taps = (w[t - 3], w[t - 8], w[t - 14], w[t - 16])
        varying, const = 0, 0
        for x in taps:
            if x is None:
                varying += 1
            else:
                const ^= x
        if varying:
            ops += varying - 1 + (const != 0) + 1
            w.append(None)
        else:
            w.append(_rol1(const))
    for t in range(80):
        ops += 2 + _F[t] + 3 + (w[t] is None)
    return ops + 5


def compressions(length):
    """[(message block, how many a candidate)] of a `length`-byte
    passphrase against one ESSID and one target."""
    n = -(-length // 4)
    key_block = [None] * n + [0x36363636] * (16 - n)    # any pad constant
    pmk_block = [None] * 8 + [0x36363636] * 8
    fixed = [0] * 16            # all constant: its values change no count
    return [(key_block, 2),                     # passphrase: ipad, opad
            (fixed, 2),                         # salt | counter, T1 and T2
            (_DIGEST_BLOCK, 2 * (2 * ITERATIONS - 1)),
            (pmk_block, 2),                     # PMK: ipad, opad
            (fixed, 1),                         # "PMK Name" | AP | STA
            (_DIGEST_BLOCK, 1)]


def ops_per_candidate(length, cfg):
    """One ESSID and one target (what `target_line` makes of a
    one-target job); `cfg` decides nothing yet."""
    hashes = sum(compression_ops(block) * n
                 for block, n in compressions(length))
    return hashes + (ITERATIONS - 1) * (5 + 3)
