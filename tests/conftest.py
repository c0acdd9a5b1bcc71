"""Test configuration: hermetic CPU-mesh execution.

Tests run on the CPU backend with 8 virtual devices, so the multi-chip
sharding paths run without hardware (SURVEY.md section 4's fake-mesh
strategy).  JAX_PLATFORMS=cpu is set here, before anything imports
jax, for this process and for any subprocess a test spawns; plain JAX
honours the variable.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The production default tile (SUB=128) makes interpret-mode kernel
# tests 4x slower without changing semantics; keep the hermetic suite
# on the small tile.
os.environ.setdefault("DPRF_PALLAS_SUB", "32")

# Hermetic tuning cache: `--batch auto` is the CLI default now, so any
# e2e test would otherwise read/write the USER's ~/.cache/dprf tuning
# cache -- cross-contaminating real tuning state with test runs.
if "DPRF_TUNE_DIR" not in os.environ:
    import tempfile as _tempfile
    os.environ["DPRF_TUNE_DIR"] = _tempfile.mkdtemp(prefix="dprf-tune-test-")

# Persistent compile cache: the tests follow the program's own rule
# (dprf_tpu/compilecache): $JAX_COMPILATION_CACHE_DIR where it is set,
# else the fixed <checkout>/.cache/xla -- so a repeated run of the
# tests on one checkout loads what the last one compiled.  Tests that
# need a provably cold cache take the `fresh_cache` fixture below.

import pytest  # noqa: E402


@pytest.fixture
def cache_off():
    """Start from a disabled persistent cache; restore whatever was on
    afterwards."""
    from dprf_tpu import compilecache
    was = compilecache.enabled()
    compilecache.disable()
    yield
    compilecache.disable()
    if was:
        compilecache.enable()


@pytest.fixture
def fresh_cache(tmp_path):
    """Place the persistent compile cache in a test-owned EMPTY dir
    (so the first compile is provably cold) the way a user places it,
    through $JAX_COMPILATION_CACHE_DIR, and put everything back
    afterwards -- compilecache state is process-global.  JAX reads the
    variable when it is imported, which is long past by now, so the
    fixture mirrors it into jax.config itself; the program never sets
    the directory when the variable is there."""
    import jax

    from dprf_tpu import compilecache
    want = str(tmp_path / "xla")
    was_enabled = compilecache.enabled()
    prev_env = os.environ.get(compilecache.CACHE_DIR_ENV)
    prev_cfg = jax.config.jax_compilation_cache_dir
    os.environ[compilecache.CACHE_DIR_ENV] = want
    jax.config.update("jax_compilation_cache_dir", want)
    compilecache.disable()
    assert compilecache.enable() == want
    yield want
    compilecache.disable()
    if prev_env is None:
        del os.environ[compilecache.CACHE_DIR_ENV]
    else:
        os.environ[compilecache.CACHE_DIR_ENV] = prev_env
    jax.config.update("jax_compilation_cache_dir", prev_cfg)
    if was_enabled:
        compilecache.enable()


# ---------------------------------------------------------------------------
# smoke-tier time guard: pytest.ini promises the smoke tier under 5
# minutes; a silently-slowed tier is exactly the kind of unverifiable
# unverifiable claim, so the promise is machine-checked here.  Applies only to smoke-tier selections (`-m
# smoke...` without negation); DPRF_TIER_BUDGET_S overrides the
# budget, 0 disables.

import re as _re      # noqa: E402
import time as _time  # noqa: E402

_TIER_BUDGET_DEFAULT_S = 300.0


def _smoke_budget(config):
    # word-boundary match: a future marker merely CONTAINING "smoke"
    # (or an expression deselecting it) must not inherit the budget
    expr = (config.getoption("-m") or "").strip()
    if (not _re.search(r"\bsmoke\b", expr)
            or _re.search(r"\bnot\s+smoke\b", expr)):
        return None
    from dprf_tpu.utils import env as envreg
    budget = envreg.get_float("DPRF_TIER_BUDGET_S",
                              _TIER_BUDGET_DEFAULT_S)
    return budget if budget > 0 else None


def pytest_configure(config):
    config._dprf_tier_t0 = _time.monotonic()
    _run_static_checks()


def _run_static_checks():
    """One in-process `dprf check` pass (all six analyzers: markers,
    metrics, worker-contract, locks, protocol, env-knobs -- see
    dprf_tpu/analysis/) at the top of every tier run, so a
    lock-discipline race, a one-sided RPC key, or a rogue env read
    fails the run before the first test executes.  Budget: <2 s
    (the analyzers share one parse and prefilter on source text)."""
    import pytest

    from dprf_tpu import analysis

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    failure = analysis.run_for_conftest(repo)
    if failure is not None:
        raise pytest.UsageError(failure)


def _has_compileheavy(session) -> bool:
    # the <5-min promise is for the tier WITHOUT compileheavy cases; a
    # selection that includes them gets the wall-time line but not the
    # hard failure.  Read session.items (the post-deselection list) --
    # a collection_modifyitems hook would see compileheavy tests that
    # `-m "... and not compileheavy"` is about to drop.
    items = getattr(session, "items", None) or []
    return any(i.get_closest_marker("compileheavy") is not None
               for i in items)


def pytest_sessionfinish(session, exitstatus):
    budget = _smoke_budget(session.config)
    if budget is None or _has_compileheavy(session):
        return
    elapsed = _time.monotonic() - session.config._dprf_tier_t0
    if elapsed > budget and exitstatus == 0:
        print(f"\nFAIL: smoke tier took {elapsed:.0f}s, over its "
              f"{budget:.0f}s budget (pytest.ini promise).  Mark the "
              "offender compileheavy or shrink its traced shapes; "
              "DPRF_TIER_BUDGET_S=0 disables this guard.")
        session.exitstatus = 1


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    budget = _smoke_budget(config)
    if budget is None:
        return
    elapsed = _time.monotonic() - config._dprf_tier_t0
    verdict = "within" if elapsed <= budget else "OVER"
    terminalreporter.write_line(
        f"smoke tier wall time: {elapsed:.0f}s ({verdict} the "
        f"{budget:.0f}s budget)")
