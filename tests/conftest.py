"""Test config: run everything on a virtual 8-device CPU mesh so distributed
code paths (sharding, collectives) are exercised without TPU hardware — the
analogue of the reference's fake-multi-node trick (Engine.init(nodeNumber=4)
with local[1] Spark, test/.../optim/DistriOptimizerSpec.scala:46)."""

import os

if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running scenarios (the multi-transition chaos soak) "
        "excluded from tier-1 (-m 'not slow') to keep it within budget")
    config.addinivalue_line(
        "markers",
        "examples: subprocess-runs examples/*.py (slow; deselect with "
        "-m 'not examples' for the inner loop)")
    config.addinivalue_line(
        "markers",
        "tier2: slow/external tier — external-framework goldens, "
        "multi-process multihost, training-to-convergence, full-scale "
        "int8 (the reference's Parallel/Serial/Integration partition, "
        "spark/dl/pom.xml:332-346). Fast inner loop: -m 'not tier2 and "
        "not examples'; second tier: -m 'tier2 or examples'. The layer "
        "closure meta-tests stay in the FAST tier by design (coverage "
        "can never silently rot).")


# Tier-2 membership by module (docs/testing.md): golden suites against
# external frameworks (torch/tf/keras subprocess oracles), multi-process
# tests, and training-to-convergence tests. test_layer_closure is
# deliberately NOT here.
_TIER2_MODULES = {
    "test_golden_keras_real", "test_golden_tf_real", "test_golden_torch",
    "test_golden_torch2", "test_golden_torch3", "test_golden_torch4",
    "test_golden_torch5", "test_golden_models", "test_golden_oracle",
    "test_multihost", "test_maskrcnn_train", "test_int8_accuracy",
    "test_gradcheck2", "test_serializer_sweep2", "test_examples",
}


def pytest_collection_modifyitems(config, items):
    import os as _os
    for item in items:
        mod = _os.path.basename(str(item.fspath))[:-3]
        if mod in _TIER2_MODULES:
            item.add_marker(pytest.mark.tier2)


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.RandomState(0)
