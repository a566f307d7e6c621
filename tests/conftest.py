"""Test environment: force an 8-device virtual CPU platform BEFORE jax import.

Mirrors SURVEY.md §4's implication: distributed logic is tested single-host on a
virtual device mesh (the analogue of the reference's multi-process-on-one-host
collective tests, test/legacy_test/test_dist_base.py:1209).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture
def mesh8():
    import jax

    assert jax.device_count() == 8
    return jax.devices()
