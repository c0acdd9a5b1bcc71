"""Engine `md5` (hashcat `-m 0`): one unsalted MD5 of the password, the
hash line its 32 hex digits.  The reference is `hashlib`'s.

The operation count follows `work.py`'s rules on RFC 1321's step
functions and word orders (section 3.4): a step is
`a = b + rol(a + f(b,c,d) + M[k] + K[i], s)`, so f, add, add of the
constant, rotate, add of b, and one more add where `M[k]` varies
(a constant `M[k]` is folded into `K[i]`); F, G and I at 3 operations,
H at 2; one block; a single target met in the middle.
"""

import hashlib

import work

#: message word each step reads (RFC 1321 section 3.4)
_K = ([i for i in range(16)]
      + [(5 * i + 1) % 16 for i in range(16)]
      + [(3 * i + 5) % 16 for i in range(16)]
      + [(7 * i) % 16 for i in range(16)])
_F = [3] * 16 + [3] * 16 + [2] * 16 + [3] * 16


def md5(password):
    return hashlib.md5(password).digest()


def target_line(plain, rng, cfg):
    return md5(plain).hex()


def filler_line(rng, cfg):
    return "%032x" % rng.getrandbits(128)


def matches(line, plain):
    return md5(plain).hex() == line


def ops_per_candidate(length, cfg):
    """MD5 of a `length`-byte password (one block)."""
    varying = work.varying_words(length)
    steps = [_F[i] + 4 + (_K[i] in varying) for i in range(64)]
    undone = (work.trailing_constant(_K, varying)
              if cfg["targets"] == 1 else 0)
    return sum(steps[:64 - undone])
