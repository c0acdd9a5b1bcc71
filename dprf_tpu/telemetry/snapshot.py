"""Periodic JSONL telemetry snapshots, written next to the session
journal.

Post-mortems of wedged runs need data, not guesswork: a background
thread appends one ``{"ts": ..., "elapsed_s": ..., "metrics": {...}}``
line per interval, so the last line of the file is the fleet's state
at the moment the run died.  Append-only JSONL with the same torn-tail
tolerance as the session journal; snapshots are diagnostics, never
resume state.

The file is SIZE-CAPPED (``DPRF_TELEMETRY_MAX_BYTES``, default 16
MiB): when a write would exceed the cap the file rotates to a ``.1``
suffix (replacing any previous rotation) -- a serve session that runs
for weeks holds at most ~2x the cap on disk instead of growing without
limit.  The trace stream (telemetry/trace.py) rotates the same way.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from dprf_tpu.telemetry.registry import MetricsRegistry
from dprf_tpu.utils import env as envreg

#: suffix appended to a session journal path for its telemetry stream
TELEMETRY_SUFFIX = ".telemetry.jsonl"

#: default seconds between snapshot lines (override per-run with
#: DPRF_TELEMETRY_INTERVAL)
DEFAULT_INTERVAL_S = 30.0

#: size cap for the snapshot file before it rotates to `.1`
#: (DPRF_TELEMETRY_MAX_BYTES overrides; 0 disables the cap)
MAX_BYTES_ENV = "DPRF_TELEMETRY_MAX_BYTES"
DEFAULT_MAX_BYTES = 16 << 20


def cap_bytes(v: Optional[int]) -> Optional[int]:
    """Shared byte-cap semantics (telemetry snapshots AND the trace
    stream): 0 (or None) disables the cap."""
    return v if v and v > 0 else None


def snapshot_max_bytes(default: int = DEFAULT_MAX_BYTES) -> Optional[int]:
    return cap_bytes(envreg.get_int(MAX_BYTES_ENV, default))


def rotate_if_over(path: str, incoming: int,
                   max_bytes: Optional[int]) -> bool:
    """Move ``path`` aside to ``path + '.1'`` (replacing any previous
    rotation) when appending ``incoming`` bytes would push it over
    ``max_bytes``.  When the rotation target is unusable (unwritable
    dir, ``.1`` exists as a directory) the file is truncated in place
    instead -- a bounded file with lost history beats the unbounded
    growth the cap exists to prevent.  Returns True when the file was
    rotated or truncated."""
    if not max_bytes:
        return False
    try:
        size = os.path.getsize(path)
    except OSError:
        return False
    if size and size + incoming > max_bytes:
        try:
            os.replace(path, path + ".1")
            return True
        except OSError:
            try:
                open(path, "w").close()
                return True
            except OSError:
                return False
    return False


def telemetry_path(session_path: str) -> str:
    """Snapshot file location for a session journal path."""
    return session_path + TELEMETRY_SUFFIX


def snapshot_interval(default: float = DEFAULT_INTERVAL_S) -> float:
    return envreg.get_float("DPRF_TELEMETRY_INTERVAL", default)


class TelemetrySnapshotter:
    """Background writer: one registry snapshot line per interval plus
    a final line on stop() -- so a clean shutdown always journals the
    end-state even for runs shorter than one interval."""

    def __init__(self, path: str, registry: MetricsRegistry,
                 interval: float = DEFAULT_INTERVAL_S,
                 clock=time.time, max_bytes: Optional[int] = None):
        self.path = path
        self.registry = registry
        self.interval = max(0.25, float(interval))
        #: rotation cap; None = env default at write time
        self.max_bytes = max_bytes
        self._clock = clock
        self._t0 = time.monotonic()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def write_once(self) -> dict:
        line = {"ts": self._clock(),
                "elapsed_s": round(time.monotonic() - self._t0, 3),
                "metrics": self.registry.snapshot()}
        data = json.dumps(line, separators=(",", ":")) + "\n"
        with self._lock:
            cap = (snapshot_max_bytes() if self.max_bytes is None
                   else self.max_bytes)
            rotate_if_over(self.path, len(data), cap)
            with open(self.path, "a", encoding="utf-8") as fh:
                fh.write(data)
                fh.flush()
                os.fsync(fh.fileno())
        return line

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.write_once()
            except OSError:
                # a full/unwritable disk must not kill the job; the
                # next interval retries
                continue

    def start(self) -> "TelemetrySnapshotter":
        if self._thread is None:
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        try:
            self.write_once()
        except OSError:
            pass


def load_snapshots(path: str) -> list:
    """Read a snapshot JSONL file back (torn tail lines skipped, like
    SessionJournal.load)."""
    out = []
    if not os.path.exists(path):
        return out
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out
