"""Test fixtures.

Mirrors the reference's test strategy (SURVEY §4): a real in-process cluster
per test (``ray_start_regular``) and a multi-node-in-one-machine cluster
builder (``ray_start_cluster``), plus a virtual 8-device CPU mesh for all
JAX sharding tests (the reference tests distributed paths with multiple
raylets on one machine; we additionally test multi-chip SPMD with
``--xla_force_host_platform_device_count``).
"""

import os

# Must be set before jax initializes anywhere in the test session (workers
# inherit this environment too). Forced, not defaulted: a machine with a
# chip sets JAX_PLATFORMS=tpu,cpu — tests always run on the virtual
# 8-device CPU mesh; only chip_smoke.py (and the bench scripts) touch the
# real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# A pytest plugin may import jax before this conftest runs, freezing the
# env-derived config defaults — update the live config too (backends are
# still uninitialized at this point, so this takes effect).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite's XLA programs are identical
# across runs (static shapes, fixed configs), so repeat invocations skip
# most compiles. The one helper decides where it lives
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache); workers
# inherit the env var. Safe to share: the cache is keyed by program hash.
from ray_tpu.util import compile_cache  # noqa: E402

compile_cache.configure()

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    core = ray_tpu.init(num_cpus=4)
    yield core
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    yield cluster
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster.shutdown()


# ---------------------------------------------------------------- timeouts
# The reference caps every test at 3 minutes (pytest.ini); pytest-timeout
# isn't in this image, so a SIGALRM watchdog provides the same guarantee
# (VERDICT weak #3). Override per test with @pytest.mark.timeout_s(N).

import signal

DEFAULT_TEST_TIMEOUT_S = 180


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout_s(n): per-test timeout override (seconds)")
    config.addinivalue_line("markers", "slow: long-running test")
    # Chaos tests are fault-injection tests (SIGKILL, stalled peers,
    # dropped connections). They are NOT slow-marked: the fast ones run
    # in every tier-1 pass (`-m 'not slow'`), and `-m chaos` selects
    # just the fault-injection surface.
    config.addinivalue_line(
        "markers", "chaos: fault-injection test (replica kill, stalled "
                   "peer); fast ones run in tier-1")


@pytest.fixture(autouse=True)
def _test_timeout(request):
    marker = request.node.get_closest_marker("timeout_s")
    seconds = marker.args[0] if marker else DEFAULT_TEST_TIMEOUT_S

    def _on_timeout(signum, frame):
        raise TimeoutError(
            f"test exceeded {seconds}s (see conftest watchdog)")

    old = signal.signal(signal.SIGALRM, _on_timeout)
    signal.alarm(int(seconds))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
