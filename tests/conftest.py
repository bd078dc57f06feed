"""Test bootstrap: force an 8-device virtual CPU mesh before JAX import.

This replaces the reference's forked-process DistributedTest fixture
(SURVEY.md §4): JAX exposes N host devices via XLA_FLAGS, so multi-"chip"
sharding tests run on one box with no pod.

Compile-time economics (this box has ONE core, so XLA compile time IS the
suite's runtime): tests run with --xla_backend_optimization_level=0
(~40% faster compiles; numerics-identical, only execution speed of the
compiled code changes) and the persistent compilation cache
(``utils/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR`` where set, else
``<checkout>/.cache/jax``) so identical programs are compiled once across
processes, re-runs, and driver rounds.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in _flags and not os.environ.get("SXT_TEST_TPU"):
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags
# A hard override, not setdefault: the suite is a CPU suite whatever the
# shell exported (fleet workers inherit it). Set SXT_TEST_TPU=1 to run it
# against a real chip instead (single device; mesh tests will skip).
if not os.environ.get("SXT_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

from shuffle_exchange_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
os.environ.setdefault("SXT_LOG_LEVEL", "warning")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_topology():
    """Every test starts with no global mesh topology. Without this, a test
    that initialized e.g. tensor=2 leaks it into later tests in other files
    (InferenceEngine._place then tries to shard undividable vocab dims)."""
    from shuffle_exchange_tpu.parallel.mesh import reset_topology
    from shuffle_exchange_tpu.runtime.resilience import uninstall_preemption_hook

    reset_topology()
    yield
    # an engine with a save_dir installs a process-wide SIGTERM hook that
    # raises SystemExit; left behind, it kills a later test of the same
    # worker that sends itself SIGTERM (test_sigterm_triggers_drain)
    uninstall_preemption_hook()


@pytest.fixture(autouse=True)
def _sanitizer_guard():
    """Runtime concurrency sanitizer gate (ISSUE 13): when the suite runs
    under ``SXT_SANITIZE=1`` (scripts/ci_full.sh runs the threaded serving
    suites that way), every test fails on any NEW lock-order inversion /
    hold-while-blocking report, and fleet threads that survive teardown
    (``serving-*`` / replica watchdogs) are leak reports. Disarmed — the
    tier-1 default — this is two attribute reads."""
    from shuffle_exchange_tpu.testing import sanitizer

    if not sanitizer.armed():
        yield
        return
    baseline = sanitizer.thread_baseline()
    before = len(sanitizer.reports())
    yield
    sanitizer.check_thread_leaks(baseline)
    bad = [r for r in sanitizer.reports()[before:]
           if r.kind in ("inversion", "hold_while_blocking", "thread_leak")]
    assert not bad, (
        f"concurrency sanitizer: {len(bad)} report(s) during this test:\n"
        + "\n\n".join(repr(r) for r in bad))


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]
