"""Test configuration: force CPU backend with 8 virtual devices.

Mirrors the reference's test strategy (SURVEY.md §4): correctness tests run
against a host build; distributed tests simulate a cluster on one machine
(reference: tests/distributed/_test_distributed.py spawns N local CLI
processes). Here the 8 virtual XLA CPU devices stand in for an 8-chip TPU
slice so sharding/collective paths compile and execute for real.
"""
import os

# must happen before any backend initialization: the suite runs on the CPU
# backend (JAX_PLATFORMS is honoured by the installed jax)
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu"

# persistent compile cache: the suite is compile-dominated on CPU. The
# repo's one cache rule (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache); the 0.5 s floor keeps trivial programs off disk
from lightgbm_tpu.analysis.guards import (  # noqa: E402
    CACHE_DIR_ENV, checkout_cache_dir, configure_compile_cache)

_CACHE_FLOOR_S = 0.5
configure_compile_cache(checkout_cache_dir(), min_compile_secs=_CACHE_FLOOR_S)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _route_flight_dumps_to_tmp(tmp_path, monkeypatch):
    """Flight dumps must never land in the checkout: a test that trips a
    crash dump without LGBM_TPU_FLIGHT_PATH or a checkpoint dir used to
    fall back to the CWD (a stray lgbm_tpu_flight_*.jsonl once sat at
    the repo root). Point the recorder's last-resort fallback directory
    at the test's tmpdir; explicit env/path/dump-dir routing (what the
    flight tests assert) is untouched."""
    from lightgbm_tpu.obs import flight
    monkeypatch.setattr(flight, "_FALLBACK_DIR", str(tmp_path))


@pytest.fixture
def cache_config_restored(monkeypatch):
    """For tests that point the compile cache somewhere of their own
    (``tpu_compile_cache_dir``, ``configure_compile_cache``): the knob
    yields to JAX_COMPILATION_CACHE_DIR, so the variable is cleared for
    the test, and the suite's own cache is put back afterwards."""
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    yield
    # still inside the cleared environment (monkeypatch tears down after
    # this fixture), so the helper really re-points the directory; it
    # also resets jax's cached is-cache-used decision
    configure_compile_cache(prev, min_compile_secs=_CACHE_FLOOR_S)


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def compile_guard():
    """Count jit compilations inside a test; call
    ``compile_guard.assert_no_compiles()`` (or read ``.lowerings``) after
    the steady-state region (lightgbm_tpu.analysis.guards)."""
    from lightgbm_tpu.analysis import guards
    with guards.compile_counter() as counts:
        yield counts


@pytest.fixture
def lock_order_witness():
    """Instrument every lock created inside the test with the runtime
    lock-order witness (lightgbm_tpu.analysis.guards.lock_witness); at
    teardown the test fails if any cross-thread lock-order cycle was
    observed. Arm it by listing the fixture BEFORE constructing servers
    or boosters so their locks are created instrumented."""
    from lightgbm_tpu.analysis import guards
    with guards.lock_witness() as w:
        yield w
    w.assert_no_cycles("lock_order_witness fixture")


@pytest.fixture
def resource_leak_witness():
    """Snapshot live threads / open fds / entered trace sessions /
    retained-program cache sizes at fixture setup; at teardown the test
    fails (guards.ResourceLeakError) if the scope did not give
    everything back — the runtime half of tpulint R012. Warm compiles
    and long-lived fixtures must happen BEFORE this fixture in the
    argument list (or inside the test before the chaos region) so cache
    warms don't read as leaks."""
    from lightgbm_tpu.analysis import guards
    with guards.resource_witness() as w:
        yield w
    w.assert_no_leaks("resource_leak_witness fixture")


@pytest.fixture
def no_d2h_guard():
    """Fail the test on any device->host materialization
    (lightgbm_tpu.analysis.guards.no_host_transfers)."""
    from lightgbm_tpu.analysis import guards
    with guards.no_host_transfers():
        yield
