"""Mask attack candidate generation.

Hashcat-style masks: ``?l?l?l?l?l?l`` is six lowercase letters,
``?a?a?a?a?a?a?a`` seven printable-ASCII characters.  Built-ins:

    ?l  a-z (26)          ?u  A-Z (26)         ?d  0-9 (10)
    ?s  printable symbols incl. space (33)     ?a  = ?l?u?d?s (95)
    ?b  all byte values 0x00-0xff (256)
    ?1..?4  user-defined custom charsets       ??  literal '?'

Any other character in the mask is a literal (radix-1 position).

The keyspace is the product of per-position charset sizes; the
index -> candidate map is a mixed-radix decode with the RIGHTMOST mask
position as the least-significant digit (odometer order).

TPU-first design: `decode_batch` materializes a whole batch of
candidates on device from a unit's *digit vector* plus each lane's
offset, using only int32 adds/mod/div plus a handful of vector
compare/selects per position (segment-mux decode; positions whose
charset exceeds MAX_SEGMENTS contiguous runs fall back to one gather
over the flat table) -- no 64-bit math, no host transfer of candidate
bytes, static shapes throughout.  Radices, charset offsets, and
segment tables are Python-level constants baked into the jitted
program.  The same segment model drives the Pallas kernels'
eligibility and in-kernel decode (ops/pallas_mask.py imports
`charset_segments` from here).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from dprf_tpu.generators.base import CandidateGenerator

_LOWER = bytes(range(ord("a"), ord("z") + 1))
_UPPER = bytes(range(ord("A"), ord("Z") + 1))
_DIGIT = bytes(range(ord("0"), ord("9") + 1))
# Printable ASCII symbols including space: 0x20-0x2F, 0x3A-0x40, 0x5B-0x60,
# 0x7B-0x7E (33 chars) -- matches hashcat's ?s.
_SYMBOL = bytes(range(0x20, 0x30)) + bytes(range(0x3A, 0x41)) + \
    bytes(range(0x5B, 0x61)) + bytes(range(0x7B, 0x7F))
_ALL95 = _LOWER + _UPPER + _DIGIT + _SYMBOL
_BYTES256 = bytes(range(256))

BUILTIN_CHARSETS = {
    "l": _LOWER, "u": _UPPER, "d": _DIGIT, "s": _SYMBOL,
    "a": _ALL95, "b": _BYTES256,
}


def parse_mask(mask: str,
               custom: Optional[Dict[int, bytes]] = None) -> list[bytes]:
    """Mask string -> per-position charsets (left to right)."""
    custom = custom or {}
    charsets: list[bytes] = []
    i = 0
    while i < len(mask):
        ch = mask[i]
        if ch == "?":
            if i + 1 >= len(mask):
                raise ValueError(f"dangling '?' at end of mask {mask!r}")
            sel = mask[i + 1]
            if sel == "?":
                charsets.append(b"?")
            elif sel in BUILTIN_CHARSETS:
                charsets.append(BUILTIN_CHARSETS[sel])
            elif sel.isdigit() and int(sel) in custom:
                cs = custom[int(sel)]
                if not cs:
                    raise ValueError(f"custom charset ?{sel} is empty")
                charsets.append(bytes(cs))
            else:
                raise ValueError(f"unknown mask token ?{sel} in {mask!r}")
            i += 2
        else:
            charsets.append(ch.encode("latin-1"))
            i += 1
    if not charsets:
        raise ValueError("empty mask")
    return charsets


#: segment-decode bound shared by the XLA mux and the Pallas kernels
#: (kernel eligibility: ops/pallas_mask.mask_supported).
MAX_SEGMENTS = 16


def charset_segments(charset: bytes):
    """Charset (digit order) -> [(start_digit, byte_delta)] pieces where
    byte = digit + delta for digit >= start_digit (until next piece).
    Single source of truth for the segment decode model: consumed by
    MaskGenerator.decode_batch's mux AND the Pallas kernel builders
    (ops/pallas_mask.py re-exports it)."""
    segs = []
    for d, byte in enumerate(charset):
        delta = byte - d
        if not segs or segs[-1][1] != delta:
            segs.append((d, delta))
    return segs


def segment_mux(digit, segs):
    """Vectorized piecewise charset lookup: digit array -> byte array.
    Piece starts are ascending, so the last satisfied select wins.
    Shared by decode_batch's XLA mux and the Pallas kernel decode
    (ops/pallas_mask._decode_byte)."""
    byte = digit + segs[0][1]
    for start, delta in segs[1:]:
        byte = jnp.where(digit >= start, digit + delta, byte)
    return byte


class MaskGenerator(CandidateGenerator):
    """index -> fixed-length candidate via mixed-radix decode."""

    def __init__(self, mask: str,
                 custom: Optional[Dict[int, bytes]] = None,
                 markov_counts: Optional[np.ndarray] = None):
        self.mask = mask
        self.charsets = parse_mask(mask, custom)
        if markov_counts is not None:
            # permute each position's charset into trained-frequency
            # order: low indices decode to likely candidates, keyspace
            # and bijection unchanged (generators/markov.py)
            from dprf_tpu.generators.markov import reorder_charsets
            self.charsets = reorder_charsets(self.charsets, markov_counts)
        self.length = len(self.charsets)
        self.max_length = self.length
        self.radices = tuple(len(cs) for cs in self.charsets)
        self.keyspace = 1
        for r in self.radices:
            self.keyspace *= r
        # Device tables: one flat uint8 charset array + per-position offsets.
        offsets, flat = [], bytearray()
        for cs in self.charsets:
            offsets.append(len(flat))
            flat.extend(cs)
        self._offsets = tuple(offsets)
        self._flat_np = np.frombuffer(bytes(flat), dtype=np.uint8)
        # segment-mux decode tables: a charset whose byte values form
        # few contiguous runs (every builtin: ?l/?u/?d/?b/?a are one
        # run, ?s is four) decodes with a handful of vector
        # compare/selects instead of a per-position batch-sized
        # gather -- the gather is the XLA mask path's bottleneck on
        # TPU.  None = too many runs (e.g.
        # markov-scrambled order): keep the gather.
        self._segments = tuple(
            segs if len(segs) <= MAX_SEGMENTS else None
            for segs in (charset_segments(cs) for cs in self.charsets))

    # ---------------- host (oracle) path ----------------

    def digits(self, index: int) -> list[int]:
        """Mixed-radix digit vector for a global index (arbitrary size int,
        handled in Python; rightmost position is least significant)."""
        if not 0 <= index < self.keyspace:
            raise IndexError(f"index {index} outside keyspace {self.keyspace}")
        out = [0] * self.length
        for p in range(self.length - 1, -1, -1):
            index, out[p] = divmod(index, self.radices[p])
        return out

    def candidate(self, index: int) -> bytes:
        return bytes(self.charsets[p][d]
                     for p, d in enumerate(self.digits(index)))

    def index_of(self, candidate: bytes) -> int:
        """Inverse map (host): candidate bytes -> global index."""
        if len(candidate) != self.length:
            raise ValueError("wrong candidate length for mask")
        index = 0
        for p, byte in enumerate(candidate):
            d = self.charsets[p].find(bytes([byte]))
            if d < 0:
                raise ValueError(
                    f"byte {byte:#x} not in charset for position {p}")
            index = index * self.radices[p] + d
        return index

    # ---------------- device path ----------------

    @property
    def flat_charsets(self) -> jnp.ndarray:
        return jnp.asarray(self._flat_np)

    def decode_batch(self, base_digits: jnp.ndarray, flat: jnp.ndarray,
                     batch: int, lane_offset=0) -> jnp.ndarray:
        """Materialize `batch` consecutive candidates on device.

        base_digits: int32[length] digit vector of the first candidate
        (from `digits()`, host-computed once per unit).  flat: the
        uint8 flat charset table (device-resident) -- consulted ONLY
        for positions whose charset exceeds MAX_SEGMENTS contiguous
        runs (markov-scrambled orders); every builtin charset decodes
        via the baked-in segment mux and ignores it.  lane_offset
        (int32 scalar, may be traced): decode candidates base+offset ..
        base+offset+batch -- the sharded path passes each chip's lane
        range start here.  Returns uint8[batch, length].  jit-traceable;
        radices/offsets/segments are baked in as constants so the
        per-position mod/div/selects lower to cheap int32 vector ops.
        """
        carry = lane_offset + jnp.arange(batch, dtype=jnp.int32)
        cols: list = [None] * self.length
        for p in range(self.length - 1, -1, -1):
            radix = self.radices[p]
            s = base_digits[p] + carry
            idx = s % radix
            segs = self._segments[p]
            if segs is not None:
                cols[p] = segment_mux(idx, segs).astype(jnp.uint8)
            else:
                cols[p] = flat[self._offsets[p] + idx]
            carry = s // radix
        # Lanes that carried past the most-significant digit wrapped around;
        # callers mask them out via the unit's valid-count.
        return jnp.stack(cols, axis=1)

    def __repr__(self) -> str:  # pragma: no cover
        return f"<MaskGenerator {self.mask!r} keyspace={self.keyspace}>"
