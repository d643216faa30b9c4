"""Test harness: force an 8-device virtual CPU platform BEFORE jax
imports, so sharding/collective tests run anywhere (the reference's
analogous trick is GuaguaMRUnitDriver — run the whole distributed app
in one JVM; see SURVEY.md §4.3)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(12306)


@pytest.fixture()
def model_set(tmp_path, rng):
    """A synthetic binary-classification model set on disk: raw delimited
    data + ModelConfig.json, mimicking the bundled cancer-judgement
    tutorial layout (reference test fixtures under
    src/test/resources/example/)."""
    from tests.synth import make_model_set
    return make_model_set(tmp_path, rng, n_rows=2000)
