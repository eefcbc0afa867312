"""Test config: run everything on a virtual 8-device CPU mesh so distributed
code paths (sharding, collectives) are exercised without TPU hardware — the
analogue of the reference's fake-multi-node trick (Engine.init(nodeNumber=4)
with local[1] Spark, test/.../optim/DistriOptimizerSpec.scala:46)."""

import os

# Eight devices for the mesh; and no backend optimisation: most of the
# tier's time is XLA compiling thousands of tiny CPU programs (an eager op
# is one), and the tests assert what a program computes, not how fast the
# CPU runs it. Measured in PR 24: test_decode 133 -> 76 s, test_models
# 134 -> 64 s, test_serializer_sweep2 110 -> 66 s, same passes.
for _flag in ("--xla_force_host_platform_device_count=8",
              "--xla_backend_optimization_level=0"):
    if _flag.split("=")[0] not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " " + _flag
os.environ["JAX_PLATFORMS"] = "cpu"

import contextlib  # noqa: E402
import signal  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Seconds one phase (setup, call or teardown) of a test may take before that
# test fails and the run goes on: no hung subprocess or socket may eat the
# time of the whole tier. `slow` tests keep the time they need.
TEST_LIMIT_S = 120.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: the tier every PR is held to (-m 'not slow') does not run "
        "it. Put on the test where it is defined, with one line of why "
        "(docs/testing.md has the rule).")


@contextlib.contextmanager
def _limited(item, phase):
    if item.get_closest_marker("slow") is not None:
        yield
        return

    def expired(signum, frame):
        pytest.fail(f"{item.nodeid}: {phase} took more than "
                    f"{TEST_LIMIT_S:g} s (tests/conftest.py)")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _limited(item, "setup"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _limited(item, "call"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    with _limited(item, "teardown"):
        yield


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """Drop JAX's caches after each test file. A compile gets slower the
    more programs the process already holds (measured in PR 24: 78 ms for
    a small program in a new process, 237 ms after 4,800 others, flat
    with the caches cleared every 400; 14 files in a row take 245 s
    without this and 224 s with it, 232 s when cleared after every
    test), and the tier compiles tens of thousands in one process; a
    file's programs are of no use to the next file."""
    yield
    jax.clear_caches()


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.RandomState(0)
