"""Test harness config (reference test strategy, SURVEY §4).

Forces an 8-device virtual CPU mesh BEFORE jax initializes, mirroring the
reference's trick of testing multi-device semantics on CPU contexts
(tests/python/unittest/test_multi_device_exec.py uses mx.cpu(0)/mx.cpu(1)).
"""
import os

# unit tests follow the reference's CPU-only strategy whatever the session
# env says; the chip is exercised by chip_smoke.py and bench.py
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # registered here (no pytest.ini): tier-1 filters on `-m 'not slow'`,
    # and the graph-lint CI step tags its end-to-end analyzer sweeps
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 runs")
    config.addinivalue_line(
        "markers",
        "lint_graphs: CI step running tools/graph_lint.py --strict over "
        "the model-zoo exemplar graphs")


@pytest.fixture(autouse=True)
def _seed_everything():
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield

