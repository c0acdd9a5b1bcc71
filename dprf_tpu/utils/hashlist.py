"""Hashlist parsing: target file -> list of Target.

Lines are parsed by the selected engine (bare hex digests for fast
hashes, modular-crypt strings for bcrypt, 16800-format for PMKID).
Blank lines and '#' comments are skipped; duplicates are dropped
preserving first occurrence; malformed lines are collected, not fatal
-- a 1k-hash list with one bad line should still crack the other 999.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from dprf_tpu.engines.base import HashEngine, Target


@dataclasses.dataclass
class HashlistResult:
    targets: list
    skipped: list        # (line_number, text, error)
    duplicates: int


def _dedup_key(t: Target):
    """Duplicates are duplicate TARGETS, not duplicate lines: the same
    digest written twice (e.g. in different hex case) is one target, or
    the engines' digest->index maps would be ambiguous and one copy
    could never be reported cracked.  Salted targets are distinct
    unless digest AND params match."""
    params = tuple(sorted((t.params or {}).items()))
    return (t.digest, params)


def parse_lines(engine: HashEngine, lines: Sequence[str]) -> HashlistResult:
    targets: list[Target] = []
    seen: set = set()
    skipped, dups = [], 0
    # a bare hex digest a line (the engine keeps HashEngine's own
    # parse_target) is parsed here, with the same result and errors: a
    # bulk list is a million of them, and the calls were half its load
    bare = type(engine).parse_target is HashEngine.parse_target
    size = engine.digest_size
    for no, raw in enumerate(lines, 1):
        text = raw.strip()
        if not text or text.startswith("#"):
            continue
        try:
            digest = bytes.fromhex(text) if bare else b""
            t = (Target(raw=text, digest=digest) if len(digest) == size
                 and bare else engine.parse_target(text))
        except ValueError as e:
            skipped.append((no, text, str(e)))
            continue
        key = _dedup_key(t) if t.params else t.digest
        if key in seen:
            dups += 1
            continue
        seen.add(key)
        targets.append(t)
    return HashlistResult(targets=targets, skipped=skipped, duplicates=dups)


def load_hashlist(engine: HashEngine, path: str) -> HashlistResult:
    with open(path, encoding="utf-8", errors="replace") as fh:
        return parse_lines(engine, fh.readlines())
