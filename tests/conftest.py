"""Test harness: force an 8-device virtual CPU platform BEFORE jax
imports, so sharding/collective tests run anywhere (the reference's
analogous trick is GuaguaMRUnitDriver — run the whole distributed app
in one JVM; see SURVEY.md §4.3)."""

import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Every test process (each xdist worker imports this file) starts from
# a COLD compile cache of its own, placed the way a user places one.
# Left alone the program would use the persistent `<repo>/.jax_cache`,
# and whatever earlier runs or sibling workers left there would decide
# the no-recompile guards (`compile_cache_misses == 0`): a steady-state
# retrace reads as a hit on a warm cache. Children the tests start
# inherit the directory; tests of the placement rule unset it.
_cache_dir = tempfile.mkdtemp(prefix="shifu_tpu_test_jaxcc_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

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
