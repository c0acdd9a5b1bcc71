"""Device-resident probe tables: O(1)-per-candidate multi-target compare.

The replicated compare path (ops/compare.make_target_table) keeps every
target digest in one sorted device array and runs a searchsorted per
candidate -- right for the 10^3-hash list, but the bulk-recovery
scenario ("here are millions of leaked hashes") needs per-candidate
cost independent of N.  The probe table gets there in two stages:

  1. a blocked Bloom prefilter: one 512-bit block (16 uint32 words)
     per candidate, k double-hashed bit probes derived from the first
     two digest words -- constant work per candidate, sized on the
     host from N and a false-positive budget (DPRF_TARGETS_FP_BUDGET);
  2. the rare prefilter survivors are compacted into a small fixed
     buffer and verified EXACTLY against the sorted digest table --
     the same maybe-then-oracle discipline the krb5 DER prefilter
     uses, so a false positive can never surface as a hit.

Survivor-buffer overflow inflates the reported count past the lane
buffer, which lands in the workers' existing hit_capacity
rescan/redrive machinery; correctness never depends on the filter.

Sizing consults the devstats HBM-headroom plane before building: a
table that will not fit its byte budget degrades to the bloom-only
HOST-VERIFY layout (survivor lanes return to the host, one oracle
hash each) instead of OOMing the device.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from dprf_tpu.ops import compare as cmp_ops

#: words per Bloom block: 16 x uint32 = 512 bits, one lane-width row --
#: all k probes of a candidate land in the same block, so the gather
#: footprint per candidate is constant regardless of bitmap size
BLOCK_WORDS = 16
BLOCK_BITS = BLOCK_WORDS * 32

#: Knuth multiplicative constant spreading digest word0 over blocks
_GOLDEN = 0x9E3779B1

_MAX_K = 8
#: smallest bitmap a degraded (host-verify) table keeps: 8 KiB
_MIN_BITS = 1 << 16

MODE_DEVICE = "device"
MODE_HOST_VERIFY = "host-verify"


@dataclasses.dataclass(frozen=True)
class ProbeTable:
    """Host-built, device-resident multi-target probe structure."""

    #: the bloom_fill bitmap, block-minor: uint32[BLOCK_WORDS,
    #: n_blocks], row w holding word w of every block, which is how a
    #: step gathers it (bloom_maybe_words)
    blocks: jnp.ndarray
    block_bits: int          # log2(n_blocks); static
    k: int                   # bit probes per digest; static
    #: exact-verify buckets (device mode); None in host-verify mode
    table: Optional[cmp_ops.TargetTable]
    order: np.ndarray        # host: sorted pos -> original target idx
    num_targets: int
    mode: str                # MODE_DEVICE | MODE_HOST_VERIFY
    fp_est: float            # analytic false-positive rate of `bits`
    nbytes: int              # device bytes: bitmap + exact table

    @property
    def geometry(self) -> "ProbeGeometry":
        """What a step over this table is compiled for; everything
        else of the table is data (``device_args``)."""
        return ProbeGeometry(
            self.block_bits, self.k,
            self.table.window if self.table is not None else 0)

    def device_args(self) -> tuple:
        """The table as arguments of a step: (bitmap, sorted digest
        words, their first words), the last two left out in
        host-verify mode.  Lists of one geometry and one padded length
        have the same shapes, so one executable serves them all."""
        if self.table is None:
            return (self.blocks,)
        return (self.blocks, self.table.words, self.table.first)


class ProbeGeometry(NamedTuple):
    """The static half of a ProbeTable: log2 of the bitmap's blocks,
    probes a digest, and the exact compare's window (the longest run
    of sorted digests sharing their first word, rounded up to a power
    of two; 0: no exact table on the device, host-verify mode)."""
    block_bits: int
    k: int
    window: int


def _pow2ceil(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length() if x > 1 else 1


def _geometry(n: int, m_bits: int):
    """(k, fp_est) for n keys in an m_bits bitmap."""
    k = int(round(m_bits / n * math.log(2)))
    k = min(max(k, 1), _MAX_K)
    fp_est = (1.0 - math.exp(-k * n / m_bits)) ** k
    return k, fp_est


def byte_budget() -> Optional[int]:
    """Device-byte cap for a probe table, or None when unbounded.
    DPRF_TARGETS_MAX_BYTES wins when set; otherwise a fraction
    (DPRF_TARGETS_HEADROOM_FRAC) of the devstats free-HBM reading.
    Backends without memory stats (CPU) give no signal -> no cap."""
    from dprf_tpu.telemetry import devstats
    from dprf_tpu.utils import env as envreg
    hard = envreg.get_int("DPRF_TARGETS_MAX_BYTES")
    if hard and hard > 0:
        return hard
    free = devstats.bytes_free()
    if free is None:
        return None
    frac = envreg.get_float("DPRF_TARGETS_HEADROOM_FRAC")
    return int(free * min(max(frac, 0.0), 1.0))


def probe_eligible(targets: Sequence, engine=None) -> bool:
    """Should this target list use the probe-table path?  Needs enough
    targets to beat the replicated compare (DPRF_TARGETS_PROBE_MIN),
    uniform unsalted digests, and at least two uint32 words for the
    double-hashed probes."""
    from dprf_tpu.utils import env as envreg
    floor = envreg.get_int("DPRF_TARGETS_PROBE_MIN")
    if floor <= 0 or len(targets) < floor:
        return False
    if engine is not None and getattr(engine, "salted", False):
        return False
    dlen = len(targets[0].digest)
    if dlen < 8 or dlen % 4:
        return False
    return all(len(t.digest) == dlen and not t.params for t in targets)


def bloom_fill(rows: np.ndarray, m_bits: int, k: int) -> np.ndarray:
    """uint32[N, W>=2] digest words -> the blocked-Bloom bitmap as
    uint32[m_bits // 32].  This is the ONE definition of the bit
    layout: one 512-bit block per key picked by a multiplicative hash
    of word0, then k double-hashed probes inside the block.  Both the
    XLA-path ProbeTable and the Pallas in-kernel probe rows are filled
    through here, so the host builder and the kernel can never drift
    on which bit means what."""
    W = rows.shape[1]
    h1 = rows[:, 0].astype(np.uint64)
    h2 = (rows[:, 1].astype(np.uint64) | 1)
    # probes alternate between TWO independent double-hash pairs
    # (words 0/1 and words 2/3): inside one 512-bit block a single
    # pair carries only ~17 bits of entropy, so a lone progression
    # floors the false-positive rate near n_keys * 2^-17 no matter
    # how many probes run; requiring both pairs to collide squares
    # that floor away (every fast-hash digest has >= 4 words).
    h3 = rows[:, 2].astype(np.uint64) if W > 3 else h1
    h4 = (rows[:, 3].astype(np.uint64) | 1) if W > 3 else h2
    n_blocks = m_bits // BLOCK_BITS
    block_bits = n_blocks.bit_length() - 1
    if block_bits:
        block = ((h1 * _GOLDEN) & 0xFFFFFFFF) >> np.uint64(
            32 - block_bits)
    else:
        block = np.zeros(len(rows), dtype=np.uint64)
    words = np.zeros(m_bits // 32, dtype=np.uint32)
    for j in range(k):
        i = j >> 1
        a, b = (h3, h4) if j & 1 else (h1, h2)
        g = (a + (2 * i + 1) * b) & 0xFFFFFFFF
        bit = g & (BLOCK_BITS - 1)
        w = (block * BLOCK_WORDS + (bit >> np.uint64(5))).astype(np.int64)
        np.bitwise_or.at(
            words, w,
            np.uint32(1) << (bit & np.uint64(31)).astype(np.uint32))
    return words


def kernel_bloom_geometry(n: int, fp: float, max_bits: int):
    """(m_bits, k, fp_est) for an in-kernel probe bitmap: sized for the
    fp budget like build_probe_table, but capped at ``max_bits`` (the
    kernel gathers its block via a bounded per-group select tree, so
    the bitmap must stay VMEM-small -- the fp estimate reports what the
    cap actually buys)."""
    fp = min(max(fp, 1e-9), 0.5)
    m_bits = max(BLOCK_BITS, _pow2ceil(int(math.ceil(
        -n * math.log(fp) / (math.log(2) ** 2)))))
    m_bits = min(m_bits, _pow2ceil(max_bits))
    k, fp_est = _geometry(n, m_bits)
    return m_bits, k, fp_est


def build_probe_table(digests: Sequence[bytes],
                      little_endian: bool = True,
                      fp_budget: Optional[float] = None,
                      max_bytes: Optional[int] = None,
                      log=None) -> ProbeTable:
    """N raw digests -> a ProbeTable sized for the fp budget and the
    device byte budget (see module docstring for the degrade rule)."""
    from dprf_tpu.utils import env as envreg
    n = len(digests)
    if n == 0:
        raise ValueError("empty target list")
    dlen = len(digests[0])
    if dlen < 8 or dlen % 4:
        raise ValueError(
            "probe tables need digests of >= 2 whole uint32 words")
    if any(len(d) != dlen for d in digests):
        raise ValueError("inconsistent digest sizes in target list")
    fp = fp_budget if fp_budget is not None else \
        envreg.get_float("DPRF_TARGETS_FP_BUDGET")
    fp = min(max(fp, 1e-9), 0.5)
    m_bits = max(BLOCK_BITS, _pow2ceil(int(math.ceil(
        -n * math.log(fp) / (math.log(2) ** 2)))))
    budget = max_bytes if max_bytes is not None else byte_budget()
    exact_bytes = n * dlen + n * 4       # words[T,W] + first[T]
    mode = MODE_DEVICE
    if budget is not None and m_bits // 8 + exact_bytes > budget:
        # the exact table is what dominates at 10^7 targets; shed it
        # and shrink the bitmap until it fits -- never OOM the device
        mode = MODE_HOST_VERIFY
        while m_bits > _MIN_BITS and m_bits // 8 > budget:
            m_bits //= 2
    k, fp_est = _geometry(n, m_bits)

    rows = np.frombuffer(
        b"".join(digests),
        dtype="<u4" if little_endian else ">u4").reshape(n, dlen // 4)
    words = bloom_fill(rows, m_bits, k)
    block_bits = (m_bits // BLOCK_BITS).bit_length() - 1

    table = None
    order = np.arange(n, dtype=np.int64)
    if mode == MODE_DEVICE:
        table = _padded_table(cmp_ops.make_target_table(
            list(digests), little_endian=little_endian))
        order = table.order
        exact_bytes = int(table.words.nbytes + table.first.nbytes)
    nbytes = words.nbytes + (exact_bytes if table is not None else 0)
    if log is not None:
        log.info("built probe table", targets=n, mode=mode,
                 bits=m_bits, k=k, fp=round(fp_est, 8),
                 mbytes=round(nbytes / 1e6, 3))
    blocks = np.ascontiguousarray(words.reshape(-1, BLOCK_WORDS).T)
    return ProbeTable(blocks=jnp.asarray(blocks), block_bits=block_bits,
                      k=k, table=table, order=order, num_targets=n,
                      mode=mode, fp_est=fp_est, nbytes=nbytes)


def _padded_table(table: cmp_ops.TargetTable) -> cmp_ops.TargetTable:
    """The sorted table with its length padded to a power of two (by
    repeating its last digest) and its window to one (at least 2), so
    that its shapes and its compare depend on the list's size class
    and not on the list.  A pad equals the last real row and lies
    behind it, so the leftmost match of a run is always a real row;
    `order` is padded alike for whoever maps a pad back."""
    n = table.num_targets
    pad = _pow2ceil(n) - n
    words, first = np.asarray(table.words), np.asarray(table.first)
    if pad:
        words = np.concatenate([words, np.repeat(words[-1:], pad, 0)])
        first = np.concatenate([first, np.repeat(first[-1:], pad)])
    order = np.concatenate([table.order,
                            np.repeat(table.order[-1:], pad)])
    return cmp_ops.TargetTable(
        words=jnp.asarray(words), first=jnp.asarray(first),
        window=max(2, _pow2ceil(table.window)), order=order)


def bloom_maybe(digest: jnp.ndarray, pt: ProbeTable) -> jnp.ndarray:
    """uint32[B, W] candidate digests -> bool[B] "possibly a target"
    (bloom_maybe_words over the table's own bitmap)."""
    return bloom_maybe_words(digest.T, pt.blocks, pt.block_bits, pt.k)


def bloom_maybe_words(words, blocks, block_bits: int, k: int):
    """Word-major candidate digests (W arrays of one shape, the lanes')
    against a bloom_fill bitmap given block-minor, uint32[BLOCK_WORDS,
    n_blocks] (ProbeTable.blocks) -> bool of that shape, "possibly a
    target".

    Per candidate: one multiplicative block pick from word0, ONE gather
    of that 512-bit block, then the k double-hashed bit tests against
    its words with compares and selects: constant work in N, and one
    lookup a candidate.  The block comes word-major too (row w of the
    gather is word w of every lane's block), so on a TPU each of the
    tests is an elementwise pass over full tiles; give the lanes a
    shape of [.., 128] there.  (On a v5e a gather of 2^22 blocks costs
    9-12 ms and one of 2^22 single words 31-37 ms, whatever the
    table's size, so the k probes may not each be a gather of their
    own: that read 290 ms.  PERF.md has the readings.)"""
    W = len(words)
    h1 = words[0]
    h2 = words[1] | jnp.uint32(1)
    # the alternating probe pairs of bloom_fill (the ONE bit layout)
    h3 = words[2] if W > 3 else h1
    h4 = (words[3] | jnp.uint32(1)) if W > 3 else h2
    if block_bits:
        block = ((h1 * jnp.uint32(_GOLDEN))
                 >> (32 - block_bits)).astype(jnp.int32)
    else:
        block = jnp.zeros(h1.shape, jnp.int32)
    # a block index has block_bits bits: always in bounds
    rows = blocks.at[:, block].get(mode="promise_in_bounds")
    maybe = jnp.ones(h1.shape, bool)
    for j in range(k):
        i = j >> 1
        a, b = (h3, h4) if j & 1 else (h1, h2)
        g = a + jnp.uint32(2 * i + 1) * b
        bit = g & jnp.uint32(BLOCK_BITS - 1)
        widx = bit >> 5
        word = rows[0]
        for w in range(1, BLOCK_WORDS):
            word = jnp.where(widx == w, rows[w], word)
        maybe = maybe & (((word >> (bit & jnp.uint32(31)))
                          & jnp.uint32(1)) == 1)
    return maybe


def survivor_cap(pt: ProbeTable, batch: int) -> int:
    """Fixed survivor-buffer length for a batch-lane step: ~4x the
    expected false-positive count plus slack for real hits, clamped to
    [64, 8192]; DPRF_TARGETS_SURVIVOR_CAP overrides."""
    from dprf_tpu.utils import env as envreg
    fixed = envreg.get_int("DPRF_TARGETS_SURVIVOR_CAP")
    if fixed and fixed > 0:
        return fixed
    want = int(4 * batch * pt.fp_est) + 64
    return min(max(_pow2ceil(want), 64), 8192)


def probe_hits(digest: jnp.ndarray, pt: ProbeTable,
               valid: jnp.ndarray, hit_capacity: int,
               survivors: int):
    """uint32[B, W] digests against a ProbeTable held by the caller's
    closure -> the workers' (count, lanes, tpos) hit-buffer shape
    (probe_hits_words without its survivor count)."""
    return probe_hits_words(digest.T, pt.device_args(), pt.geometry,
                            valid, hit_capacity, survivors)[:3]


def compact_lanes(found: jnp.ndarray, capacity: int):
    """bool[B] -> (count, lanes int32[capacity]): the first `capacity`
    set lanes in order, unused slots -1.  A binary search for each
    slot in the running count (capacity x log2 B lookups) where
    compare.compact_hits scatters all B lanes: on a v5e the scatter of
    2^22 lanes costs 21 ms, this under 2 (PERF.md)."""
    run = jnp.cumsum(found.astype(jnp.int32))
    want = jnp.arange(1, capacity + 1, dtype=jnp.int32)
    at = jnp.searchsorted(run, want, side="left").astype(jnp.int32)
    return run[-1], jnp.where(want <= run[-1], at, jnp.int32(-1))


def probe_hits_words(words, table: tuple, geometry: ProbeGeometry,
                     valid: jnp.ndarray, hit_capacity: int,
                     survivors: int):
    """Word-major digests (uint32[W, B], or [W, B / 128, 128] with
    `valid` of the same lanes' shape: lane i in row-major order)
    against a probe table passed as DATA (ProbeTable.device_args /
    .geometry) -> (count, lanes int32[cap], tpos int32[cap], n_maybe):
    the workers' hit-buffer shape, and beside it how many lanes passed
    the bitmap.

    Device mode: Bloom survivors compact into a `survivors`-slot
    buffer, their digests are gathered and verified exactly against
    the sorted table, and true hits compact into the hit_capacity
    buffer; tpos is the position in the sorted table.  A survivor
    overflow (n_maybe > survivors) could hide a real hit, so the count
    is inflated past the lane buffer and the callers' existing
    overflow rescan/redrive path re-covers the window exactly.

    Host-verify mode (geometry.window 0: no exact table on device):
    the lane buffer IS the survivor buffer (tpos all -1) and count is
    the survivor count; the worker verifies each lane with one oracle
    hash.  Overflow falls out of the same count > capacity
    comparison."""
    maybe = bloom_maybe_words(words, table[0], geometry.block_bits,
                              geometry.k) & valid
    maybe = maybe.reshape(-1)
    words = [w.reshape(-1) for w in words]
    n_maybe, surv = compact_lanes(maybe, survivors)
    if not geometry.window:
        return (n_maybe, surv, jnp.full((survivors,), -1, jnp.int32),
                n_maybe)
    at = jnp.maximum(surv, 0)
    sdig = jnp.stack([w[at] for w in words], axis=-1)    # [S, W]
    found, tpos = cmp_ops.compare_multi(
        sdig, cmp_ops.TargetTable(words=table[1], first=table[2],
                                  window=geometry.window, order=None))
    found = found & (surv >= 0)
    count, slots, tpos = cmp_ops.compact_hits(found, tpos, hit_capacity)
    lanes = jnp.where(slots >= 0, surv[jnp.maximum(slots, 0)],
                      jnp.int32(-1))
    count = jnp.where(n_maybe <= survivors, count,
                      jnp.int32(hit_capacity) + n_maybe)
    return count, lanes, tpos, n_maybe
