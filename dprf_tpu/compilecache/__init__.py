"""Compile-cost elimination layer (ISSUE 3).

The dominant *fixed* cost of every job is the jit warmup compile
(runtime/worker.py calls it out; the krb5aes smoke tier once spent ~9
minutes almost entirely in XLA compiles).  Every step shape we compile
is deterministic and repeated across workers, sessions, and bench runs
-- so this package wires JAX's persistent XLA compilation cache into
every execution path and makes its behavior observable:

  - ``enable()``          one idempotent entrypoint that turns the
                          cache on with the persistence thresholds
                          lowered so our step compiles always persist.
                          Where ``$JAX_COMPILATION_CACHE_DIR`` is set
                          the cache lives there -- JAX reads the
                          variable itself and no code here sets
                          another directory; otherwise it lives at ONE
                          fixed path inside the checkout
                          (``<repo>/.cache/xla``, git-ignored: the
                          path is part of the cache key, so a
                          directory that moves never hits).  Called
                          from the CLI (crack/serve/worker/bench/tune/
                          prewarm), dprf_tpu/bench.py, and the batch
                          autotuner.  Advisory: an unwritable dir or a
                          ``DPRF_COMPILE_CACHE=0`` kill switch degrades
                          to "no cache", never to a crashed job.
  - ``compile_observer``  times one step compile, classifies it as a
                          cache hit/miss, and publishes
                          ``dprf_compile_seconds{engine,cache}`` plus
                          ``dprf_compile_cache_hits_total`` /
                          ``_misses_total`` -- so "a stalled fleet that
                          is really compiling" is diagnosable from a
                          scrape or a telemetry snapshot
                          (tools/compile_report.py).
  - ``prewarm``           ahead-of-time cache population for a fleet
                          image (the ``dprf prewarm`` subcommand; see
                          compilecache/prewarm.py).

Classification: the observer captures the compiler's own per-compile
"Persistent compilation cache hit/MISS" log lines -- the EXACT
classification (ISSUE 15).  The heuristic below stays the fallback for
windows the watch saw nothing in (every executable already live in
jax's in-memory cache): a compile that wrote new entries into the
cache dir is
a miss (exact -- JAX persists every compile at these thresholds); one
that wrote nothing and finished under the cold-compile floor
(``$DPRF_COMPILE_COLD_FLOOR_S``, default 5 s) is a hit.  A no-write
compile OVER the floor is still reported as a miss: that is what a
backend whose compiles cannot persist looks like, and calling it a hit
would hide exactly the cost this layer exists to eliminate.  Windows
that mix compile with real compute (an autotuner rung, a bench warmup
unit) classify by the entry delta alone -- ``classify_delta`` -- since
their wall time says nothing about the compile.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional

from dprf_tpu.utils import env as envreg

#: JAX's own variable: read by jax.config at import, and by
#: default_cache_dir() below so both agree on where the cache lives
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
#: where the cache lives when the variable is unset: one fixed path
#: inside the checkout, never under $HOME and never a temp name
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECKOUT_CACHE_DIR = os.path.join(_REPO_ROOT, ".cache", "xla")
#: kill switch: DPRF_COMPILE_CACHE=0 disables the persistent cache
DISABLE_ENV = "DPRF_COMPILE_CACHE"
COLD_FLOOR_ENV = "DPRF_COMPILE_COLD_FLOOR_S"
#: wall-time floor separating a deserialize-and-load cache hit from a
#: real XLA compile when the entry-count delta is zero.  The floor
#: only arbitrates that delta==0 case: a cold compile with the cache
#: enabled writes entries and is classified miss by the delta alone,
#: so the floor's job is telling a served hit (trace + executable
#: load, 0.2-2 s observed on a loaded CPU box) from a backend whose
#: compiles cannot persist at all (cold every time, typically tens of
#: seconds to minutes).  5 s splits those populations with headroom.
DEFAULT_COLD_FLOOR_S = 5.0

#: re-entrant: the log filter takes it too, on whichever thread
#: the compiler logs from, enable()/disable() included
_lock = threading.RLock()
#: "watch": the process-lifetime log watch counting EVERY compile's
#: persistent-cache hit/miss line while the cache is on
#: (process_cache_counts), whichever site compiled
_state: dict = {"dir": None, "watch": None}
#: exact-classifier log-watch bookkeeping (ISSUE 15): the watches the
#: jax._src.compiler filter counts into.  "saved" is the logger's own
#: level to restore when the last watch goes, "passes" the effective
#: level it had, below which the filter drops records while the logger
#: sits at DEBUG
_watch_state: dict = {"watches": [], "saved": logging.NOTSET,
                      "passes": logging.WARNING}

#: `dprf check` locks analyzer: module-global cache state, written by
#: enable()/disable() and read from every compile site -- the serve
#: plane calls those from multiple threads.
GUARDED_BY = {
    "<module>": {"_lock": ("_state", "_watch_state")},
}


def default_cache_dir() -> str:
    """$JAX_COMPILATION_CACHE_DIR, or the fixed in-checkout path."""
    return os.environ.get(CACHE_DIR_ENV) or CHECKOUT_CACHE_DIR


def cache_dir() -> Optional[str]:
    """The directory the cache is currently enabled on, or None."""
    with _lock:
        return _state["dir"]


def enabled() -> bool:
    with _lock:
        return _state["dir"] is not None


def enable(log=None) -> Optional[str]:
    """Turn on the persistent XLA compilation cache; returns the cache
    directory, or None when disabled/unusable.  Idempotent.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken
    the directory from it and this function sets no other; where it is
    not, the cache is pointed at the fixed in-checkout path.

    The persistence thresholds are lowered to "persist everything":
    the default min-compile-time gate (1 s) would silently drop the
    very step compiles (some take ~1 s on CPU, minutes on TPU) this
    cache exists for, and a dropped entry reads as an eternal miss.
    """
    if not envreg.get_bool(DISABLE_ENV):
        return None
    from_env = bool(os.environ.get(CACHE_DIR_ENV))
    d = os.path.abspath(default_cache_dir())
    with _lock:
        if _state["dir"] == d:
            return d
        try:
            os.makedirs(d, exist_ok=True)
            probe = os.path.join(d, ".dprf-write-probe")
            with open(probe, "w") as fh:
                fh.write("ok")
            os.unlink(probe)
        except OSError as e:
            _warn(log, "compile cache dir unwritable; persistent "
                  "compilation cache DISABLED", dir=d, error=str(e))
            return None
        import jax
        if not from_env:
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", -1)
        # jax materializes its cache object AT MOST ONCE, at the first
        # compile -- a setting changed after that is silently ignored
        # unless the cache is reset.  Without this, an enable() after
        # any prior jit dispatch in the process is a no-op that still
        # *reports* enabled.
        _reset_backend_cache()
        _state["dir"] = d
        if _state["watch"] is None:
            _state["watch"] = watch = _CacheLogWatch()
        else:
            watch = None
    if watch is not None:
        _watch_install(watch)
    if log is not None:
        log.info("persistent compile cache enabled", dir=d,
                 placed_by=CACHE_DIR_ENV if from_env else "checkout")
    return d


def process_cache_counts() -> dict:
    """{"cache_hits": n, "cache_misses": m}: every XLA compile of this
    process since enable(), by the cache layer's own per-compile log
    line -- observed site or not (lazily compiled steps of workers
    with their own sweep loops included).  A job whose log shows
    misses == 0 loaded every executable it ran."""
    with _lock:
        w = _state["watch"]
    return {"cache_hits": w.hits if w else 0,
            "cache_misses": w.misses if w else 0}


def _reset_backend_cache() -> None:
    """Drop jax's in-memory cache OBJECT so the next compile
    re-initializes it against the current config (on-disk entries are
    untouched)."""
    from jax.experimental.compilation_cache import (
        compilation_cache as _cc)
    _cc.reset_cache()


def disable() -> None:
    """Undo enable() (tests).  Leaves the directory setting and the
    on-disk entries alone: the cache is switched off, not moved."""
    with _lock:
        if _state["dir"] is None:
            return
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
        _reset_backend_cache()
        _state["dir"] = None
        watch, _state["watch"] = _state["watch"], None
    if watch is not None:
        _watch_remove(watch)


def _warn(log, msg: str, **kw) -> None:
    if log is not None:
        log.warn(msg, **kw)
    else:
        from dprf_tpu.utils.logging import DEFAULT
        DEFAULT.warn(msg, **kw)


def entry_count() -> Optional[int]:
    """Number of entries in the cache dir (None when disabled or
    unreadable).  JAX writes one flat file per cached executable, so a
    before/after count delta is an exact "did this compile persist
    anything new" signal for a single-process compile."""
    with _lock:
        d = _state["dir"]
    if d is None:
        return None
    try:
        return len(os.listdir(d))
    except OSError:
        return None


def cold_floor_s() -> float:
    return envreg.get_float(COLD_FLOOR_ENV, DEFAULT_COLD_FLOOR_S)


def classify_compile(seconds: float, entries_before: Optional[int] = None,
                     entries_after: Optional[int] = None) -> str:
    """"hit" | "miss" | "off" for one timed compile (see module
    docstring for the decision rule)."""
    if not enabled():
        return "off"
    if (entries_before is not None and entries_after is not None
            and entries_after > entries_before):
        return "miss"
    return "hit" if seconds < cold_floor_s() else "miss"


def classify_delta(entries_before: Optional[int],
                   entries_after: Optional[int]) -> str:
    """Entry-delta-only classification, for windows whose wall time
    mixes compile with real compute (autotuner rungs, bench warmup
    units): new entries -> miss, none -> hit.  The wall-time floor is
    deliberately NOT consulted -- a big rung's hashing would flip a
    genuine hit to 'miss' by sheer compute time."""
    if not enabled():
        return "off"
    if (entries_before is not None and entries_after is not None
            and entries_after > entries_before):
        return "miss"
    return "hit"


# ---------------------------------------------------------------------------
# exact hit/miss classification from the compiler's own log lines
# (ISSUE 15 satellite; closes the carried ROADMAP follow-up)

#: the logger jax's compile_or_get_cached path logs one line per
#: compile to: "Persistent compilation cache hit for '<module>'" /
#: "PERSISTENT COMPILATION CACHE MISS for '<module>'"
_JAX_COMPILER_LOGGER = "jax._src.compiler"
_HIT_MSG = "Persistent compilation cache hit"
_MISS_MSG = "PERSISTENT COMPILATION CACHE MISS"


class _CacheLogWatch:
    """Counts of the compiler's per-compile hit/miss log lines while it
    is installed -- the EXACT classification (one line per XLA compile,
    emitted by the cache layer itself), replacing the entry-delta +
    wall-floor guess whenever it saw anything."""

    __slots__ = ("hits", "misses")

    def __init__(self):
        self.hits = 0
        self.misses = 0


def _watch_filter(record: logging.LogRecord) -> bool:
    """The ONE filter on the compiler's logger while any watch is
    installed: counts the line into every installed watch, then lets
    through exactly the records the logger's own level would have let
    through before it was dropped to DEBUG (the hit line logs at DEBUG
    unless ``jax_log_compiles`` is on).  A filter, not a handler with
    propagation off: the compiler's WARNING and ERROR records still
    reach the operator's handlers.  One filter for all watches, because
    a logger stops at the first filter that rejects a record."""
    msg = record.msg if isinstance(record.msg, str) else str(record.msg)
    hit, miss = _HIT_MSG in msg, _MISS_MSG in msg
    with _lock:
        if hit or miss:
            for watch in _watch_state["watches"]:
                watch.hits += hit
                watch.misses += miss
        return record.levelno >= _watch_state["passes"]


def _watch_install(watch: _CacheLogWatch) -> None:
    """Start counting into `watch`; the first one in lowers the
    compiler logger to DEBUG and attaches the filter."""
    logger = logging.getLogger(_JAX_COMPILER_LOGGER)
    with _lock:
        if not _watch_state["watches"]:
            _watch_state["saved"] = logger.level
            _watch_state["passes"] = logger.getEffectiveLevel()
            if _watch_state["passes"] > logging.DEBUG:
                logger.setLevel(logging.DEBUG)
            logger.addFilter(_watch_filter)
        _watch_state["watches"].append(watch)


def _watch_remove(watch: _CacheLogWatch) -> None:
    """Stop counting into `watch`; the last one out restores the
    logger's level and detaches the filter."""
    logger = logging.getLogger(_JAX_COMPILER_LOGGER)
    with _lock:
        _watch_state["watches"].remove(watch)
        if not _watch_state["watches"]:
            logger.removeFilter(_watch_filter)
            logger.setLevel(_watch_state["saved"])


def compile_histogram(registry=None):
    """ONE declaration site for dprf_compile_seconds (worker warmup,
    bench, and prewarm all publish through here, so the label set can
    never drift).  The ``cache`` label is the hit/miss/off
    classification -- a scrape separates "fleet is cold-compiling"
    from "fleet is loading cached executables"."""
    from dprf_tpu.telemetry import get_registry
    return get_registry(registry).histogram(
        "dprf_compile_seconds", "step warmup/compile wall time",
        labelnames=("engine", "cache"))


def _cache_counters(registry=None) -> tuple:
    from dprf_tpu.telemetry import get_registry
    m = get_registry(registry)
    return (m.counter("dprf_compile_cache_hits_total",
                      "step compiles served from the persistent "
                      "compilation cache", labelnames=("engine",)),
            m.counter("dprf_compile_cache_misses_total",
                      "step compiles that ran XLA cold",
                      labelnames=("engine",)))


def observe_compile(engine: str, seconds: float, cache: str,
                    registry=None) -> None:
    """Publish one classified compile into the metric surface."""
    compile_histogram(registry).observe(seconds, engine=engine,
                                        cache=cache)
    hits, misses = _cache_counters(registry)
    if cache == "hit":
        hits.inc(engine=engine)
    elif cache == "miss":
        misses.inc(engine=engine)


class compile_observer:
    """Context manager around one step compile: times it, classifies
    hit/miss/off from the cache-dir entry delta + wall time, and
    publishes the metrics.  Build the compile's *arguments* before
    entering -- argument materialization can itself write tiny cache
    entries, which would misread a hit as a miss.

    Classification prefers the EXACT per-compile log lines the cache
    layer itself emits (ISSUE 15): a window whose watch saw any line
    classifies from it alone -- any miss makes the window a miss,
    hits-only is a hit.  A window the watch saw nothing in (every
    executable already live in jax's in-memory cache) falls back to
    the entry-delta + wall-floor heuristic.

    Attributes after exit: ``seconds``, ``cache``.  Nothing is
    published when the body raises (a failed compile is not a compile
    cost, it is an error the caller handles)."""

    __slots__ = ("engine", "registry", "publish", "seconds", "cache",
                 "_t0", "_before", "_watch")

    def __init__(self, engine: str, registry=None, publish: bool = True):
        self.engine = engine
        self.registry = registry
        self.publish = publish
        self.seconds = 0.0
        self.cache = "off"
        self._watch: Optional[_CacheLogWatch] = None

    def __enter__(self) -> "compile_observer":
        if enabled():
            self._watch = _CacheLogWatch()
            _watch_install(self._watch)
        self._before = entry_count()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        watch, self._watch = self._watch, None
        if watch is not None:
            _watch_remove(watch)
        if exc_type is not None:
            return False
        if watch is not None and (watch.hits or watch.misses):
            self.cache = "miss" if watch.misses else "hit"
        else:
            self.cache = classify_compile(self.seconds, self._before,
                                          entry_count())
        if self.publish:
            observe_compile(self.engine, self.seconds, self.cache,
                            registry=self.registry)
        return False


__all__ = ["CACHE_DIR_ENV", "CHECKOUT_CACHE_DIR", "DISABLE_ENV",
           "COLD_FLOOR_ENV", "DEFAULT_COLD_FLOOR_S", "cache_dir",
           "classify_compile", "classify_delta", "cold_floor_s",
           "compile_histogram", "compile_observer",
           "default_cache_dir", "disable", "enable", "enabled",
           "entry_count", "observe_compile", "process_cache_counts"]
