"""Where the persistent compile cache lives, and who may touch a
backend.

The cache rule (`profiling.enable_compile_cache`):
`JAX_COMPILATION_CACHE_DIR` places the cache from outside and nothing
in the program overrides it; unset, the cache is ONE fixed directory in
the checkout — never under a model set or the temp dir, because a
directory that moves never hits. And the process rule: a DAG parent
never creates a jax backend, or on one chip it would take the chip from
the device children it starts.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def restore_cache_config():
    import jax
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_compilation_cache_max_size)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_compilation_cache_max_size", was[1])


def test_outside_placement_wins_over_the_knob(tmp_path, monkeypatch,
                                              restore_cache_config):
    import jax
    from shifu_tpu import profiling
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    monkeypatch.setenv("SHIFU_TPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "knob"))
    assert profiling.enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert not (tmp_path / "knob").exists()
    # bounded, because only a bounded jax cache takes the directory's
    # file lock around every read and write — processes share it
    assert jax.config.jax_compilation_cache_max_size > 0
    # not even the disable value un-places it
    monkeypatch.setenv("SHIFU_TPU_COMPILE_CACHE_DIR", "off")
    assert profiling.enable_compile_cache() == placed


def test_default_is_one_fixed_dir_in_the_checkout(tmp_path, monkeypatch,
                                                  restore_cache_config):
    import jax
    from shifu_tpu import profiling
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("SHIFU_TPU_COMPILE_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert profiling.default_cache_dir() == want
    for cwd in (tmp_path, tmp_path / "ModelSetA", tmp_path / "ModelSetB"):
        cwd.mkdir(exist_ok=True)
        monkeypatch.chdir(cwd)             # wherever the model set is
        assert profiling.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    assert not any("jax_cache" in n for _, names, _ in os.walk(tmp_path)
                   for n in names)


@pytest.mark.parametrize("value", ["0", "off", "none"])
def test_disable_value_still_disables(value, monkeypatch,
                                      restore_cache_config):
    from shifu_tpu import profiling
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("SHIFU_TPU_COMPILE_CACHE_DIR", value)
    assert profiling.enable_compile_cache() is None


def test_knob_places_the_cache_when_nothing_outside_does(
        tmp_path, monkeypatch, restore_cache_config):
    from shifu_tpu import profiling
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("SHIFU_TPU_COMPILE_CACHE_DIR", str(tmp_path / "k"))
    assert profiling.enable_compile_cache() == str(tmp_path / "k")


PARENT = textwrap.dedent("""
    import json, os, sys
    from shifu_tpu.cli import main
    from shifu_tpu.pipeline.nodes import pipeline_nodes
    from shifu_tpu.pipeline.scheduler import run_dag
    from shifu_tpu import profiling
    root = sys.argv[1]
    profiling.enable_compile_cache()       # what cli.main does first
    nodes = [n for n in pipeline_nodes(root, eval_sets=[])
             if n.name in ("init", "stats")]
    assert [n.name for n in nodes] == ["init", "stats"]
    with profiling.step_metrics(root, "dag-parent"):
        rep = run_dag(nodes, workers=2, root=root)
    # public-API probe: with a platform that does not exist, asking for
    # devices succeeds ONLY if a backend was already created
    import jax
    jax.config.update("jax_platforms", "no_such_platform")
    try:
        jax.devices()
        created = True
    except RuntimeError:
        created = False
    print(json.dumps({"states": {r["node"]: r["state"]
                                 for r in rep["nodes"]},
                      "backend_created": created}))
""")


def test_dag_parent_creates_no_backend_and_children_keep_the_cache(
        tmp_path):
    """A two-node DAG (init → stats) under a parent: the device child
    runs as `python -m shifu_tpu stats`; the parent takes the device
    inventory through a child that exits first, records its own step
    metrics, and never creates a backend. The children compile into the
    directory JAX_COMPILATION_CACHE_DIR names although
    SHIFU_TPU_COMPILE_CACHE_DIR is set too."""
    import numpy as np
    from tests.synth import make_model_set
    root = make_model_set(tmp_path, np.random.default_rng(5), n_rows=300)
    env = dict(os.environ)
    env.pop("SHIFU_TPU_DAG_DEVICES", None)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "placed"),
                "SHIFU_TPU_COMPILE_CACHE_DIR": str(tmp_path / "knob")})
    r = subprocess.run([sys.executable, "-c", PARENT, root],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["states"] == {"init": "done", "stats": "done"}
    assert out["backend_created"] is False
    assert os.listdir(tmp_path / "placed")          # children compiled
    assert not (tmp_path / "knob").exists()
    assert not os.path.exists(os.path.join(root, "tmp", "jax_cache"))
    with open(os.path.join(root, "tmp", "metrics", "steps.jsonl")) as f:
        steps = [json.loads(ln) for ln in f if ln.strip()]
    by = {s["step"]: s for s in steps}
    assert "backend" in by["stats"]                 # the child's device
    assert "backend" not in by["dag-parent"]        # the parent has none
