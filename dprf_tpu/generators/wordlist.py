"""Wordlist and wordlist+rules candidate generation (benchmark config 3).

Keyspace layout: index = word_index * n_rules + rule_index, so a
contiguous WorkUnit covers whole words (all rules of one word are
adjacent) and a device step over a word batch covers a contiguous index
range — the property the Dispatcher's interval ledger and session
resume rely on (SURVEY.md section 2: Dispatcher "contiguous shards").

Rejected candidates (a rule that rejects, or whose result overflows
max_len) are *holes* in the keyspace: `candidate()` returns None and
workers skip them.  The index->candidate map for non-rejected indices is
still a bijection onto the generated candidate multiset, and resume
bookkeeping only needs index ranges, so holes cost nothing.

The packed word arrays (uint8[N_pad, L] + int32 lengths) are built once
on the host and uploaded to HBM once per job; device steps slice them
with `lax.dynamic_slice`, so after upload no candidate material crosses
the host boundary.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

from dprf_tpu.generators.base import CandidateGenerator
from dprf_tpu.rules.cpu import apply_rule as apply_rule_cpu
from dprf_tpu.rules.parser import Op, Opcode, load_rules

NOOP_RULE: tuple[Op, ...] = (Op(Opcode.NOOP),)


def load_words(path: str, max_len: int,
               encoding: str = "latin-1") -> tuple[list[bytes], int]:
    """Read a wordlist file -> (words, n_skipped_too_long).

    Lines are stripped of trailing CR/LF only (leading/interior spaces
    are part of the word).  Empty lines are dropped.  Words longer than
    max_len can never produce a <= max_len candidate through the
    common grow-only rule sets, but CAN through truncating rules — they
    are still skipped here (matching the fixed-width device layout) and
    counted so the CLI can report it.
    """
    words: list[bytes] = []
    skipped = 0
    with open(path, "rb") as fh:
        for raw in fh:
            word = raw.rstrip(b"\r\n")
            if not word:
                continue
            if len(word) > max_len:
                skipped += 1
                continue
            words.append(word)
    if not words:
        raise ValueError(f"wordlist {path!r} contains no usable words")
    return words, skipped


class WordlistRulesGenerator(CandidateGenerator):
    """words x rules keyspace with host oracle + packed device tables.

    Word storage is the packed pair (uint8[N, max_len] zero-padded rows,
    int32[N] lengths) -- the exact layout the device consumes -- built
    either from a list of words or directly by the native loader
    (dprf_tpu/native/wordlist.cpp) without ever materializing Python
    bytes objects.
    """

    def __init__(self, words: Optional[Sequence[bytes]] = None,
                 rules: Optional[Sequence[tuple[Op, ...]]] = None,
                 max_len: int = 55,
                 packed: Optional[tuple[np.ndarray, np.ndarray]] = None):
        if (words is None) == (packed is None):
            raise ValueError("pass exactly one of words / packed")
        self.rules = list(rules) if rules else [NOOP_RULE]
        self.max_len = self.max_length = max_len
        if packed is not None:
            buf, lens = packed
            if buf.ndim != 2 or buf.shape[1] != max_len or \
                    len(lens) != buf.shape[0]:
                raise ValueError("packed arrays disagree with max_len")
            self._buf = np.ascontiguousarray(buf, dtype=np.uint8)
            self._lens = np.asarray(lens, dtype=np.int32)
        else:
            if not words:
                raise ValueError("empty wordlist")
            if any(len(w) > max_len for w in words):
                raise ValueError(f"word longer than max_len={max_len}")
            self._buf = np.zeros((len(words), max_len), dtype=np.uint8)
            self._lens = np.zeros((len(words),), dtype=np.int32)
            for i, w in enumerate(words):
                self._buf[i, :len(w)] = np.frombuffer(w, dtype=np.uint8)
                self._lens[i] = len(w)
        self.n_words = self._buf.shape[0]
        if self.n_words == 0:
            raise ValueError("empty wordlist")
        self.n_rules = len(self.rules)
        self.keyspace = self.n_words * self.n_rules

    @classmethod
    def from_files(cls, wordlist_path: str,
                   rules_spec: Optional[str] = None,
                   max_len: int = 55) -> "WordlistRulesGenerator":
        """Build from files, preferring the native (C++) loader.  The
        count of skipped overlong lines lands on `gen.n_skipped_long`."""
        rules = load_rules(rules_spec, on_error="skip") if rules_spec else None
        from dprf_tpu import native
        loaded = native.load_words_packed(wordlist_path, max_len)
        if loaded is not None:
            buf, lens, skipped = loaded
            if len(lens) == 0:
                raise ValueError(
                    f"wordlist {wordlist_path!r} contains no usable words")
            gen = cls(rules=rules, max_len=max_len, packed=(buf, lens))
        else:
            words, skipped = load_words(wordlist_path, max_len)
            gen = cls(words, rules, max_len=max_len)
        #: which reader built the tables (the job log names it)
        gen.native_reader = loaded is not None
        gen.n_skipped_long = skipped
        return gen

    def content_id(self) -> str:
        """Digest of the word *content* (what an index decodes to), for
        job fingerprints: hashes the packed tables wholesale at memory
        bandwidth instead of a per-word Python loop."""
        import hashlib
        h = hashlib.sha256()
        h.update(b"dprf-wordlist-v2\0")
        h.update(str(self.n_words).encode())
        # feed the arrays' buffers directly: tobytes() would copy the
        # (potentially multi-GB) packed table just to hash it
        h.update(np.ascontiguousarray(self._lens))
        h.update(np.ascontiguousarray(self._buf))
        return h.hexdigest()[:16]

    # ---------------- host (oracle) path ----------------

    def word(self, w: int) -> bytes:
        return self._buf[w, :self._lens[w]].tobytes()

    def candidate(self, index: int) -> Optional[bytes]:
        """May return None: the (word, rule) pair rejected."""
        if not 0 <= index < self.keyspace:
            raise IndexError(f"index {index} outside keyspace {self.keyspace}")
        w, r = divmod(index, self.n_rules)
        return apply_rule_cpu(self.word(w), self.rules[r], self.max_len)

    def candidates(self, start: int, count: int) -> list:
        return [self.candidate(i)
                for i in range(start, min(start + count, self.keyspace))]

    def index_of(self, word_index: int, rule_index: int) -> int:
        return word_index * self.n_rules + rule_index

    # ---------------- device path ----------------

    def packed_words(self, pad_to: int = 1,
                     min_size: int = 0) -> tuple[np.ndarray, np.ndarray]:
        """(uint8[N_pad, max_len], int32[N_pad]) with N_pad a multiple of
        pad_to and >= min_size.  Callers slicing windows of size W from
        arbitrary word offsets must pass min_size = n_words + W - 1:
        `lax.dynamic_slice` CLAMPS out-of-range starts instead of
        erroring, which would silently re-hash earlier words under wrong
        indices.  Padding lanes have length 0 and are masked by n_valid.
        """
        n_pad = max(pad_to, min_size,
                    -(-self.n_words // pad_to) * pad_to)
        n_pad = -(-n_pad // pad_to) * pad_to
        buf = np.zeros((n_pad, self.max_len), dtype=np.uint8)
        lens = np.zeros((n_pad,), dtype=np.int32)
        buf[:self.n_words] = self._buf
        lens[:self.n_words] = self._lens
        return buf, lens

    def __repr__(self) -> str:  # pragma: no cover
        return (f"<WordlistRulesGenerator words={self.n_words} "
                f"rules={self.n_rules} keyspace={self.keyspace}>")
