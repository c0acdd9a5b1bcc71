"""Engine `sha1` (hashcat `-m 100`), as a test brings a third engine:
this file, `configs/tiny-sha1.json` and `workloads/tiny-sha1.crack.json`
under the tests' data root, and no line anywhere else.

The count is FIPS 180-4's SHA-1 as `engines/wpa2_pmkid.py` counts a
compression, over one block whose password words (with the 0x80) vary;
nothing is met in the middle, so it reads a little high.  No cell
reports it.
"""

import hashlib

from engines.wpa2_pmkid import compression_ops


def target_line(plain, rng, cfg):
    return hashlib.sha1(plain).hexdigest()


def filler_line(rng, cfg):
    return "%040x" % rng.getrandbits(160)


def matches(line, plain):
    return hashlib.sha1(plain).hexdigest() == line


def ops_per_candidate(length, cfg):
    n = (length + 1 + 3) // 4
    return compression_ops([None] * n + [0] * (15 - n) + [8 * length])
