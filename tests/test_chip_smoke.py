"""`chip_smoke.py` — the CPU rehearsal of the chip check.

The driver runs `python3 chip_smoke.py` on a one-chip machine after
every PR; here the same script is rehearsed at a tiny size on the CPU
(kernels in interpret mode) so a wrong path, argument or check is found
without chip time — and its refusals are pinned: no TPU and no
`--rehearse` fails before the first phase, a phase that dies fails the
run, and a rehearsal never reports `"platform": "tpu"`.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(tmp_path, *args, **env_extra):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                # one virtual device: the one-chip shape of the run
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    env.pop("SHIFU_TPU_FAULT", None)
    env.update(env_extra)
    return subprocess.run(
        # a RELATIVE workdir, as a user would type it: ModelConfig paths
        # must still come out absolute
        [sys.executable, SMOKE, "--workdir", "ModelSet", *args],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(tmp_path))


def test_rehearsal_runs_every_phase_and_places_the_cache(tmp_path):
    r = _run(tmp_path, "--rehearse",
             SHIFU_TPU_COMPILE_CACHE_DIR=str(tmp_path / "other_cache"))
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    phases = [ln.get("phase") for ln in lines[:-1]]
    assert phases == ["data", "nn", "gbt", "serve.nn", "serve.gbt",
                      "total"]
    # the last line is the contract's, with the platform it REALLY ran on
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert '"platform": "tpu"' not in r.stdout
    by = {ln["phase"]: ln for ln in lines[:-1]}
    assert by["data"]["rows"] == 4000 and by["data"]["columns"] == 28
    assert by["data"]["reader"] in ("native", "pandas")
    assert by["gbt"]["bins"] == 64 and by["gbt"]["depth"] == 6
    assert by["gbt"]["trees"] == 20 and by["nn"]["epochs"] == 20
    # every kernel route really went through its Pallas kernel
    routes = {**by["nn"]["routes"], **by["gbt"]["routes"]}
    assert {k: v["route"] for k, v in routes.items()} == {
        "score": "pallas", "hist": "pallas", "split": "pallas",
        "trees": "pallas"}
    for kind in ("nn", "gbt"):
        assert by[kind]["auc"] >= by[kind]["auc_floor"]
        assert by[kind]["max_abs_score_diff"] <= by[kind]["score_tol"]
        sv = by[f"serve.{kind}"]
        assert sv["requests"] == 35
        assert sv["steady_compile_cache_misses"] == 0
        assert set(sv["served_vs_eval_by_size"]) == {
            "1", "3", "8", "13", "64", "100", "512"}
        for n, diff in sv["served_vs_eval_by_size"].items():
            assert diff <= sv["score_tol_by_size"][n], (kind, n)
    # the cache went where JAX_COMPILATION_CACHE_DIR says — not where
    # SHIFU_TPU_COMPILE_CACHE_DIR says, and not under the model set
    cache = str(tmp_path / "cache")
    assert {ln["cache_dir"] for ln in lines[:-1]} == {cache}
    assert os.listdir(cache)
    assert not (tmp_path / "other_cache").exists()
    strays = [os.path.join(d, n) for d, names, _ in os.walk(tmp_path)
              for n in names if "jax_cache" in n]
    assert strays == []


def test_four_device_rehearsal_runs_only_the_mesh_phase(tmp_path):
    """`--chips 4` (the builder's run on a four-chip host) compares a
    4-device data mesh with a 1-device one and runs no one-chip phase;
    its last line counts four devices."""
    r = _run(tmp_path, "--rehearse", "--chips", "4",
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    assert [(ln["phase"], ln.get("mesh_devices")) for ln in lines[:-1]] \
        == [("mesh.data", None), ("mesh.nn", 4), ("mesh.nn", 1),
            ("mesh.gbt", 4), ("mesh.gbt", 1), ("mesh.compare", None),
            ("total", None)]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    cmp = lines[-3]
    assert abs(cmp["nn"]["auc_many"] - cmp["nn"]["auc_one"]) \
        <= cmp["nn"]["auc_tol"]
    assert cmp["nn"]["max_abs_weight_diff"] <= cmp["nn"]["weight_tol"]
    assert cmp["gbt"]["split_flips"] <= cmp["gbt"]["max_flips"]
    # a wrong device count is refused, not run on what is there
    r = _run(tmp_path, "--rehearse", "--chips", "4")
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_the_size_of_the_run_is_not_an_option(tmp_path):
    """No flag shrinks the run: rows, epochs, trees, depth are what the
    script proves, so a toy-sized run can never print the ok line."""
    for flag in ("--rows", "--eval-rows", "--epochs", "--trees",
                 "--depth", "--serve-passes"):
        r = _run(tmp_path, "--rehearse", flag, "1")
        assert r.returncode == 2 and r.stdout.strip() == "", flag
        assert "unrecognized arguments" in r.stderr


def test_no_tpu_and_no_rehearse_fails_before_the_first_phase(tmp_path):
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""          # no phase line, no result
    assert "no TPU" in r.stderr
    assert not (tmp_path / "ModelSet").exists()


def test_a_phase_that_dies_fails_the_run(tmp_path):
    r = _run(tmp_path, "--rehearse",
             SHIFU_TPU_FAULT="step.train:oserror:1+")
    assert r.returncode != 0
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert [json.loads(ln)["phase"] for ln in lines] == ["data"]
    assert '"ok"' not in r.stdout
