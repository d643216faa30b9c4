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
    size_was = jax.config.jax_compilation_cache_max_size
    assert profiling.enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert not (tmp_path / "knob").exists()
    # a directory placed from outside gets the directory and nothing
    # else: no size bound, so no eviction from what is not ours
    assert jax.config.jax_compilation_cache_max_size == size_was
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
    monkeypatch.delenv("JAX_COMPILATION_CACHE_MAX_SIZE", raising=False)
    jax.config.update("jax_compilation_cache_max_size", -1)
    for cwd in (tmp_path, tmp_path / "ModelSetA", tmp_path / "ModelSetB"):
        cwd.mkdir(exist_ok=True)
        monkeypatch.chdir(cwd)             # wherever the model set is
        assert profiling.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    # the program's own directory is shared by its processes, and only
    # a bounded jax cache takes the directory's file lock around every
    # read and write
    assert jax.config.jax_compilation_cache_max_size > 0
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


def test_background_compiles_move_this_threads_events_and_no_others():
    """Inside `background_compiles()` a compile counts under
    `background_compile_*`; another thread compiling at the same time, and
    this thread once it left the block, count under the bare names the
    zero-recompile gates read."""
    import threading

    import jax
    import jax.numpy as jnp
    from shifu_tpu import profiling
    from shifu_tpu.data import pipeline
    profiling.enable_compile_cache()

    def compile_fresh(k):
        # a new program every call: nothing in-process or on disk has it
        return jax.jit(lambda v: v * k + (k + 0.5))(jnp.ones(3 + k))

    def requests(stages, prefix=""):
        return stages.get(prefix + "compile_cache_hits", 0) + \
            stages.get(prefix + "compile_cache_misses", 0)

    base = int.from_bytes(os.urandom(2), "big")   # fresh constants
    pipeline.drain_stage_timers()
    with profiling.background_compiles():
        compile_fresh(base + 10)
        inside = pipeline.drain_stage_timers()
        other = threading.Thread(target=compile_fresh, args=(base + 11,))
        other.start()
        other.join()
        from_other_thread = pipeline.drain_stage_timers()
    compile_fresh(base + 12)
    after = pipeline.drain_stage_timers()

    assert requests(inside, "background_") >= 1 and requests(inside) == 0
    assert inside.get("background_compile_s", 0) > 0
    assert "compile_s" not in inside
    assert requests(from_other_thread) >= 1
    assert requests(from_other_thread, "background_") == 0
    assert requests(after) >= 1 and requests(after, "background_") == 0


def test_compile_s_is_the_compiler_alone_and_a_cache_read_is_its_own_key(
        tmp_path, monkeypatch, restore_cache_config):
    """The stage timers of one program built twice: first by the compiler
    (`compile_s`, a miss), then, jit's own caches forgotten, traced and
    lowered again and read back from the persistent cache (`trace_s`,
    `lower_s`, `compile_cache_read_s`, a hit) with no `compile_s` at all:
    what a warm run's `steps.jsonl` shows."""
    import jax
    import jax.numpy as jnp
    from shifu_tpu import profiling
    from shifu_tpu.data import pipeline
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setenv("SHIFU_TPU_COMPILE_CACHE_MIN_S", "0")
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    assert profiling.enable_compile_cache() == str(tmp_path / "cc")
    salt = int.from_bytes(os.urandom(2), "big") + 2.5

    ones = jnp.ones(9)

    def build():
        return jax.jit(lambda v: jnp.tanh(v * salt).sum())(ones)

    try:
        pipeline.drain_stage_timers()
        build()
        cold = pipeline.drain_stage_timers()
        jax.clear_caches()
        build()
        warm = pipeline.drain_stage_timers()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
    assert cold["compile_s"] > 0 and cold["compile_cache_misses"] >= 1
    assert cold["trace_s"] > 0 and cold["lower_s"] > 0
    assert warm["compile_cache_hits"] >= 1
    assert "compile_s" not in warm and "compile_cache_misses" not in warm
    assert warm["compile_cache_read_s"] > 0
    assert warm["trace_s"] > 0 and warm["lower_s"] > 0


def test_background_builds_keep_every_stage_off_the_bare_timers():
    """`background_compiles()` keeps its meaning for the stages the
    listeners hear since they hear all of them: trace and lowering of a
    background build are `background_*` too."""
    import jax
    import jax.numpy as jnp
    from shifu_tpu import profiling
    from shifu_tpu.data import pipeline
    profiling.enable_compile_cache()
    salt = int.from_bytes(os.urandom(2), "big") + 7.5
    pipeline.drain_stage_timers()
    with profiling.background_compiles():
        jax.jit(lambda v: jnp.cos(v * salt))(jnp.ones(11))
    stages = pipeline.drain_stage_timers()
    assert stages["background_trace_s"] > 0
    assert stages["background_lower_s"] > 0
    assert stages.get("background_compile_s", 0) + \
        stages.get("background_compile_cache_read_s", 0) > 0
    assert not {"trace_s", "lower_s", "compile_s",
                "compile_cache_read_s"} & set(stages)


def test_device_command_records_its_device_whatever_route_it_takes(
        model_set):
    """`cli.main` takes the devices through the lease seam for every
    command that does device work in its own process, so the step
    record names the backend and device kind even where the command's
    route never builds a mesh — and a roofline asks the runtime, not a
    flag some other path may have set."""
    from shifu_tpu import profiling
    from shifu_tpu.cli import main
    from shifu_tpu.parallel import mesh
    assert main(["--dir", model_set, "init"]) == 0
    mesh._devices_enumerated = False
    assert main(["--dir", model_set, "stats"]) == 0
    with open(os.path.join(model_set, "tmp", "metrics",
                           "steps.jsonl")) as f:
        by = {r["step"]: r for r in map(json.loads, f)}
    assert by["stats"]["backend"] == "cpu"
    assert by["stats"]["deviceKind"] and by["stats"]["deviceCount"] >= 1
    mesh._devices_enumerated = False
    assert profiling.device_peaks() is None        # a CPU: not in the table
    assert mesh.devices_enumerated()               # it asked the runtime
    assert profiling.device_peaks("TPU v5 lite")["flops_per_s"] == 197e12


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
