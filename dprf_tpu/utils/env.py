"""The DPRF_* environment-knob registry: ONE declaration site.

Before this module, ~25 call sites read ``os.environ`` directly, each
re-stating the knob's name, default, and parse rule inline -- so a
renamed knob, a drifted default, or a knob documented in the README
but long deleted could not be caught anywhere.  Every ``DPRF_*`` knob
is now DECLARED here (name, default, type, docstring) and READ through
the typed getters below; ``dprf check`` (analysis/envknobs.py) forbids
raw ``os.environ``/``getenv`` reads of ``DPRF_*`` elsewhere, flags
getter calls naming undeclared knobs, asserts every declared knob has
a read site, and keeps the README knob table generated from (and in
sync with) this registry (``dprf check --write-env-docs``).

Parse rules (uniform across knobs -- the point of a registry):

  - int/float: junk values fall back to the declared default instead
    of crashing at import time;
  - bool: ``"0"`` is False; ``"1"``/``"true"``/``"yes"``/``"on"`` are
    True; anything else (including unset) is the declared default;
  - str/path: unset (or empty, for paths) means the declared default,
    which may be None ("resolve a fallback in code").

This module must stay dependency-free (stdlib only): it is imported
at module scope by the Pallas op modules and by tests/conftest.py
BEFORE jax initializes.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

#: README markers the generated knob table lives between
README_BEGIN = "<!-- dprf-env-knobs:begin (generated: dprf check --write-env-docs) -->"
README_END = "<!-- dprf-env-knobs:end -->"


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    default: object
    type: str            # "int" | "float" | "bool" | "str" | "path"
    doc: str
    #: secret values (tokens) are never echoed into docs or logs
    secret: bool = False


#: name -> Knob; populated by the _declare block below and NOWHERE else
KNOBS: dict = {}

_TYPES = ("int", "float", "bool", "str", "path")


def _declare(name: str, default, type: str, doc: str,
             secret: bool = False) -> None:
    if not name.startswith("DPRF_"):
        raise ValueError(f"knob {name!r} must be DPRF_-prefixed")
    if type not in _TYPES:
        raise ValueError(f"knob {name}: unknown type {type!r}")
    if name in KNOBS:
        raise ValueError(f"knob {name} declared twice")
    KNOBS[name] = Knob(name, default, type, doc, secret)


# ---------------------------------------------------------------------------
# the registry (alphabetical within each group)

# -- kernel / device tuning --------------------------------------------------
_declare("DPRF_7Z_DEVICE_DATA_CAP", 1024, "int",
         "Largest 7z payload (bytes) decrypted on-device; bigger "
         "archives fall back to the host AES tail.")
_declare("DPRF_BCRYPT_DISPATCH_S", 20.0, "float",
         "Per-dispatch wall budget (seconds) for the chunked bcrypt "
         "cost loop: bounds how long the host waits between progress "
         "/ lease-renewal callbacks while a high-cost batch runs.  "
         "20 s is the default unit length; not tuned on the chip.")
_declare("DPRF_BCRYPT_ROUTE", "auto", "str",
         "bcrypt routing: 'cpu' or 'device' forces a path, 'auto' "
         "measures on the TPU backend.")
_declare("DPRF_BCRYPT_SUBC", 64, "int",
         "bcrypt Pallas kernel: candidate lanes per grid cell.")
_declare("DPRF_KRB5AES_KERNEL", False, "bool",
         "Enable the krb5aes PBKDF2 device kernel on real hardware "
         "(default off until a recorded planted-crack run exists; "
         "interpret mode is always allowed).")
_declare("DPRF_KRB5_CHUNKS", 64, "int",
         "krb5 Pallas kernel: chunks per grid cell.")
_declare("DPRF_KRB5_SUBC", 32, "int",
         "krb5/pdf Pallas kernels: sublane count per chunk.")
_declare("DPRF_KRB5_UNROLL", False, "bool",
         "Unroll the krb5 kernel's inner rounds (compile-time/size "
         "trade; off by default).")
_declare("DPRF_PALLAS", "auto", "str",
         "Pallas kernel routing: '0' disables, '1' forces (interpret "
         "mode off-TPU, for tests), 'auto' uses kernels on real TPU "
         "only.")
_declare("DPRF_PALLAS_PROBE_FP", 1e-7, "float",
         "False-positive budget for the IN-KERNEL blocked probe "
         "bitmap (sharded/multi-target mask kernels).  Much tighter "
         "than DPRF_TARGETS_FP_BUDGET: kernel survivors drain through "
         "a tiny device-resident hit buffer per superstep window and "
         "cost one host oracle hash each, so false maybes must be "
         "rare per window, not merely per batch.")
_declare("DPRF_PALLAS_SUB", 128, "int",
         "Mask-attack Pallas kernels: sublanes per grid cell (tile = "
         "SUB*128 lanes).  Tuned on TPU v5 lite; tests pin 32.")
_declare("DPRF_PALLAS_SUBK", 32, "int",
         "Keccak Pallas kernel: sublanes per grid cell.")
_declare("DPRF_PDF_CHUNKS", 8, "int",
         "PDF Pallas kernel: chunks per grid cell (smaller default "
         "tile: the PDF body is ~21x heavier than krb5's).")
_declare("DPRF_PDF_K5_KERNEL", False, "bool",
         "Re-enable the 40-bit (key_len=5) PDF kernel on real "
         "hardware (gated off after a recorded Mosaic hang; "
         "interpret mode is always allowed).")
_declare("DPRF_RULES_SUBW", 8, "int",
         "Rules Pallas kernel: words per grid cell.")
_declare("DPRF_SCRYPT_MEM", 4 << 30, "int",
         "Device-memory budget (bytes) the scrypt engine sizes its "
         "V-array batches against.")
_declare("DPRF_SUPERSTEP", True, "bool",
         "Super-dispatch (multi-chunk scan loops fused into one "
         "dispatch); 0 falls back to per-batch dispatches.")
_declare("DPRF_SHARD_SUPER_CAP", 256, "int",
         "Batches fused into ONE sharded superstep dispatch "
         "(parallel/sharded.py; clamped to a power of two, and the "
         "int32 window budget still applies on top).  Each distinct "
         "power-of-two size compiles its own program, so the compile "
         "cache stays log-bounded.")

# -- runtime / distributed ---------------------------------------------------
_declare("DPRF_ASYNC_WARMUP", True, "bool",
         "Overlapped warmup: run the step compile on a background "
         "thread joined before the first dispatch; 0 restores "
         "synchronous warmup.")
_declare("DPRF_NATIVE", True, "bool",
         "Native (C) wordlist scanner; 0 forces the pure-Python "
         "fallback.")
_declare("DPRF_JOB_TTL_S", 86400.0, "float",
         "Age-based job GC: done/cancelled jobs older than this many "
         "seconds are reaped from the scheduler table (journaled as "
         "job_gc records) so long-lived fleets never wedge at the "
         "MAX_JOBS cap; 0 disables reaping.")
_declare("DPRF_ORDER_BLOCK_MIN", 1 << 16, "int",
         "Rank-ordered dispatch (--order markov): minimum suffix "
         "block size the order's prefix/suffix split preserves, so "
         "device batches and supersteps sweep contiguous index runs "
         "at least this long (bounds the steady-state H/s penalty of "
         "reordering).  An explicit per-job split pins the geometry "
         "instead; the wire job always carries the resolved split.")
_declare("DPRF_ORDER_PREFIX_MAX", 1 << 16, "int",
         "Rank-ordered dispatch: maximum number of rank-ordered "
         "prefix blocks, bounding how many index runs one rank "
         "interval can shatter into (journal snapshots, coverage "
         "digests, and resume all canonicalize over the index image "
         "of rank intervals).")
_declare("DPRF_PIPELINE_DEPTH", 2, "int",
         "Units submitted ahead of the oldest unresolved one in the "
         "local and remote worker loops (1 = serial fallback).")
_declare("DPRF_TOKEN", None, "str",
         "Shared secret for coordinator/worker mutual authentication "
         "(the --token flag wins when both are given).", secret=True)

# -- caches / tuning ---------------------------------------------------------
_declare("DPRF_COMPILE_CACHE", True, "bool",
         "Persistent XLA compile cache; 0 is the kill switch.  The "
         "cache lives where JAX's own JAX_COMPILATION_CACHE_DIR says, "
         "else at <checkout>/.cache/xla.")
_declare("DPRF_COMPILE_COLD_FLOOR_S", 5.0, "float",
         "Wall-time floor (seconds) separating a served cache hit "
         "from a cold compile when the cache-entry delta is zero.")
_declare("DPRF_TUNE_DIR", None, "path",
         "Tuning-cache directory (default: the session journal's "
         "directory, else ~/.cache/dprf).")

# -- multi-target probe tables -----------------------------------------------
_declare("DPRF_TARGETS_FP_BUDGET", 1e-4, "float",
         "Bloom false-positive budget the probe-table builder sizes "
         "its blocked bitmap against (dprf_tpu/targets/probe.py); "
         "smaller budgets spend more HBM on prefilter bits in "
         "exchange for fewer exact-verify survivors.")
_declare("DPRF_TARGETS_HEADROOM_FRAC", 0.5, "float",
         "Fraction of the devstats free-HBM reading a probe table may "
         "occupy; a table over the budget degrades to the bloom-only "
         "host-verify layout instead of OOMing the device.")
_declare("DPRF_TARGETS_MAX_BYTES", 0, "int",
         "Hard byte cap for the device probe table (bloom bitmap + "
         "exact-verify digest buckets); 0 means devstats-derived "
         "headroom only.")
_declare("DPRF_TARGETS_PROBE_MIN", 4096, "int",
         "Target count at which mask workers switch from the "
         "replicated compare_multi table to the probe-table path "
         "(Bloom prefilter + bucketed exact verify).")
_declare("DPRF_TARGETS_SURVIVOR_CAP", 0, "int",
         "Fixed per-batch survivor-buffer length for prefilter "
         "survivors awaiting exact verify; 0 sizes it from the "
         "batch and the built table's false-positive estimate.")

# -- observability -----------------------------------------------------------
_declare("DPRF_COVERAGE", True, "bool",
         "Coverage audit plane (telemetry/coverage.py): per-job "
         "gap/overlap ledger, coverage gauges, and worker-side "
         "redrive/window notes; 0 is the kill switch (coverage "
         "digests still compute -- resume correctness must not "
         "depend on a telemetry knob).")
_declare("DPRF_COVERAGE_MAX_GAPS", 64, "int",
         "Cap on the gap intervals the coverage ledger, `dprf "
         "audit`, and the report's Coverage section enumerate (the "
         "totals stay exact; only the listed ranges truncate).")
_declare("DPRF_DEVSTATS_POLL_S", 15.0, "float",
         "Seconds between device-memory polls (telemetry/devstats.py: "
         "device.memory_stats() -> dprf_hbm_bytes_in_use/_limit/_peak "
         "gauges; backends without memory stats publish nothing); 0 "
         "disables the background poller.")
_declare("DPRF_PROGRAM_ANALYSIS", True, "bool",
         "XLA-derived program introspection (telemetry/programs.py): "
         "compiled steps register their cost_analysis/memory_analysis "
         "record, feeding the analyzed roofline and the program "
         "registry; 0 is the kill switch (hand roofline models only).")
_declare("DPRF_ALERT_EVAL_S", 5.0, "float",
         "Seconds between fleet-health/alert evaluation passes "
         "(worker state machine, straggler detection, per-job SLOs, "
         "alert rules -- telemetry/health.py + telemetry/alerts.py).")
_declare("DPRF_ALERT_RULES", None, "path",
         "JSON file of extra alert rules loaded next to the default "
         "pack (list of rule objects; see README 'Fleet health & "
         "alerts').  `dprf check` validates every referenced metric "
         "name against the declared dprf_* registry.")
_declare("DPRF_ALERTS_MAX_BYTES", 4 << 20, "int",
         "Size cap for the session alert-event JSONL "
         "(<session>.alerts.jsonl) before it rotates to '.1' (0 "
         "disables the cap).")
_declare("DPRF_HEARTBEAT_S", 10.0, "float",
         "Worker heartbeat cadence: a remote worker sends "
         "op_heartbeat when its main connection has been quiet this "
         "long (lease/complete traffic counts as contact); the "
         "coordinator's health state machine ages workers in "
         "multiples of this interval.  0 disables explicit "
         "heartbeats.")
_declare("DPRF_JAX_PROFILE", None, "path",
         "Write a jax.profiler trace of the sweep loops to this "
         "directory (kernel-level drill-down beside the span "
         "timeline; routed through telemetry/profiler.py's "
         "single-flight capture guard).")
_declare("DPRF_AUTOPROFILE", True, "bool",
         "Alert-triggered kernel profiling: when a straggler or "
         "job_stalled alert FIRES, the coordinator's health tick "
         "requests one bounded jax.profiler capture window on the "
         "implicated worker (telemetry/profiler.py), rate-limited by "
         "DPRF_PROFILE_COOLDOWN_S; 0 disables auto-capture (manual "
         "`dprf profile --connect` still works).")
_declare("DPRF_PROFILE_COOLDOWN_S", 600.0, "float",
         "Minimum seconds between alert-triggered profile captures "
         "(global and per worker): a flapping fleet must not spend "
         "its cycles profiling itself.")
_declare("DPRF_PROFILE_SECONDS", 3.0, "float",
         "Default capture-window length for on-demand kernel "
         "profiles (`dprf profile --connect`, alert-triggered "
         "auto-capture): the worker keeps sweeping while the "
         "jax.profiler trace records, then stops and analyzes.")
_declare("DPRF_PROFILE_KEEP", 4, "int",
         "Capture dirs retained per profile root (oldest deleted "
         "first): bounded disk for repeated on-demand captures; 0 "
         "disables the reaper.")
_declare("DPRF_PROFILE_MAX_BYTES", 64 << 20, "int",
         "Per-capture raw-artifact size cap: a capture whose "
         "directory exceeds this drops its .xplane.pb bulk (the "
         "analyzed perfetto JSON is kept); 0 disables the cap.")
_declare("DPRF_PROFILE_DIR", None, "path",
         "Where a remote worker writes its on-demand capture dirs "
         "(raw traces stay on the worker host; the summary names "
         "the path).  Default: a per-process dir under the system "
         "temp root.")
_declare("DPRF_TELEMETRY_INTERVAL", 30.0, "float",
         "Seconds between telemetry snapshot lines.")
_declare("DPRF_TELEMETRY_MAX_BYTES", 16 << 20, "int",
         "Size cap for the telemetry snapshot JSONL before it "
         "rotates to '.1' (0 disables the cap).")
_declare("DPRF_TRACE", True, "bool",
         "Flight-recorder span recording; 0 is the kill switch.")
_declare("DPRF_TRACE_MAX_BYTES", 16 << 20, "int",
         "Size cap for the session trace JSONL before it rotates to "
         "'.1' (0 disables the cap).")

# -- test / bench harness ----------------------------------------------------
_declare("DPRF_TIER_BUDGET_S", 300.0, "float",
         "Smoke-tier wall-time budget enforced by tests/conftest.py "
         "(0 disables the guard).")


# ---------------------------------------------------------------------------
# typed getters (the ONLY sanctioned DPRF_* read path)

_UNSET = object()


def knob(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"undeclared env knob {name!r}: declare it in "
            "dprf_tpu/utils/env.py (the registry is the single "
            "declaration site)") from None


def get_raw(name: str) -> Optional[str]:
    """The raw environment string, or None when unset.  For call
    sites that must distinguish "unset" from "set to the default"
    (e.g. an explicit env override beating a caller-passed default)."""
    knob(name)
    return os.environ.get(name)


def get_str(name: str, default=_UNSET) -> Optional[str]:
    k = knob(name)
    v = os.environ.get(name)
    if v is None or v == "":
        return k.default if default is _UNSET else default
    return v


def get_path(name: str, default=_UNSET) -> Optional[str]:
    return get_str(name, default)


def get_int(name: str, default=_UNSET) -> Optional[int]:
    k = knob(name)
    fallback = k.default if default is _UNSET else default
    v = os.environ.get(name)
    if v is None:
        return fallback
    try:
        return int(v)
    except ValueError:
        return fallback


def get_float(name: str, default=_UNSET) -> Optional[float]:
    k = knob(name)
    fallback = k.default if default is _UNSET else default
    v = os.environ.get(name)
    if v is None:
        return fallback
    try:
        return float(v)
    except ValueError:
        return fallback


def get_bool(name: str, default=_UNSET) -> bool:
    k = knob(name)
    fallback = k.default if default is _UNSET else default
    v = os.environ.get(name)
    if v is None:
        return fallback
    if v == "0":
        return False
    if v.lower() in ("1", "true", "yes", "on"):
        return True
    return fallback


# ---------------------------------------------------------------------------
# README table generation (dprf check --write-env-docs)

def _default_repr(k: Knob) -> str:
    if k.secret:
        return "(unset)"
    if k.default is None:
        return "(unset)"
    if k.type == "bool":
        return "1" if k.default else "0"
    return str(k.default)


def render_markdown_table() -> str:
    """The knob table, one row per declared knob, sorted by name --
    the exact text kept between the README markers."""
    lines = ["| Knob | Type | Default | What it does |",
             "| --- | --- | --- | --- |"]
    for name in sorted(KNOBS):
        k = KNOBS[name]
        doc = " ".join(k.doc.split())
        lines.append(f"| `{name}` | {k.type} | `{_default_repr(k)}` "
                     f"| {doc} |")
    return "\n".join(lines)


def readme_block() -> str:
    return f"{README_BEGIN}\n{render_markdown_table()}\n{README_END}"


def _split_readme(text: str):
    """(before, after) around the generated block, or None when the
    markers are missing/malformed."""
    b = text.find(README_BEGIN)
    e = text.find(README_END)
    if b < 0 or e < 0 or e < b:
        return None
    return text[:b], text[e + len(README_END):]


def readme_sync_error(readme_path: str) -> Optional[str]:
    """None when the README's generated knob table matches the
    registry; otherwise a one-line description of the drift."""
    try:
        with open(readme_path, encoding="utf-8") as fh:
            text = fh.read()
    except OSError as e:
        return f"README unreadable: {e}"
    parts = _split_readme(text)
    if parts is None:
        return ("README has no generated knob table (markers "
                f"{README_BEGIN!r}..{README_END!r}); run "
                "`dprf check --write-env-docs`")
    current = text[len(parts[0]):len(text) - len(parts[1])]
    if current != readme_block():
        return ("README knob table is out of sync with the registry; "
                "run `dprf check --write-env-docs`")
    return None


def write_readme_table(readme_path: str) -> bool:
    """Regenerate the README's knob table in place; returns True when
    the file changed.  Raises when the markers are missing -- the
    surrounding prose is hand-written and a blind append would bury
    the table somewhere arbitrary."""
    with open(readme_path, encoding="utf-8") as fh:
        text = fh.read()
    parts = _split_readme(text)
    if parts is None:
        raise ValueError(
            f"{readme_path}: knob-table markers not found; add\n"
            f"{README_BEGIN}\n{README_END}\nwhere the table belongs")
    new = parts[0] + readme_block() + parts[1]
    if new == text:
        return False
    with open(readme_path, "w", encoding="utf-8") as fh:
        fh.write(new)
    return True
