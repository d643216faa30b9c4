"""Tier-1's view of the one benchmark (`BENCHMARK.json`, `benchmark/`).

The driver judges every PR by `python3 benchmark/run.py` on the chip, and
its test command runs `tests/` only, so this file is the bridge: a black
box over the command line the driver uses, one pair of cases a cell, the
cells read from the manifest. A change to `train_nn`, `build_gbt` or
`train_wdl` that breaks a cell's entry fails here on the CPU and not at
the cost of a chip run; and a benchmark that would measure on a CPU,
which the replay bench this one replaced did, fails here too.
`benchmark/tests/` holds the harness's own tests; nothing is imported
from there.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CHIPS = {w["name"]: w["chips"] for w in MANIFEST["workloads"]}
CELLS = list(CHIPS)


def _python(cell, *args):
    """A child on a CPU that shows as many devices as the cell's `chips`
    (the test rig's own eight would not be them): one for a one-chip
    cell, with no flag at all, and four virtual ones for a cell of a
    four-chip host."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    if CHIPS[cell] > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={CHIPS[cell]}"
    return subprocess.run([sys.executable, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


def _arguments(cell, *extra):
    return ["--workload", cell, "--seed", "3000000028", "--seconds", "0.5",
            *extra]


def _run(cell, *extra):
    """One run of the harness as the driver starts it."""
    return _python(cell, *MANIFEST["command"][1:], *_arguments(cell, *extra))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_and_is_correct(cell):
    """`--rehearse` drives the cell's whole path at its rehearsal sizes:
    data from the seed, the program's entry, the plain reference. The
    run is `correct`, every compared number is within its limit, and no
    device metric is printed: a CPU's number never carries one's name."""
    r = _run(cell, "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["checks"]
    for name, check in result["checks"].items():
        assert check["value"] <= check["limit"], (name, check)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_refused_without_the_chip(cell):
    """Asked to measure, with no accelerator: a non-zero exit and no
    result line. There is no CPU fallback to mistake for the chip."""
    r = _run(cell)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


# The harness's main() under its own counter of the window's programs:
# `compiles` is what a traced run on the chip prints as `window_compiles`,
# `loads` as `window_program_loads` (a rehearsal's line carries neither).
_WINDOW_PROGRAMS = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmark import run as harness
made = []
class Kept(harness.CompileCounter):
    def __init__(self, jax):
        super().__init__(jax)
        made.append(self)
harness.CompileCounter = Kept
rc = harness.main(sys.argv[1:])
print(json.dumps({{"rc": rc, "compiles": made[0].compiles,
                  "loads": made[0].loads}}))
"""


@pytest.mark.parametrize("cell", CELLS)
def test_a_window_builds_and_loads_no_program(cell):
    """The warm-up call leaves every program the window's calls run in
    jit's own cache: a window call compiles nothing and traces, lowers
    and reads back nothing (`program_loads_per_call` 0). A static
    argument of `train_bags_carry` that is made anew a call again (a
    closure, an optax transformation) fails here, before the chip."""
    r = _python(cell, "-c", _WINDOW_PROGRAMS.format(repo=REPO),
                *_arguments(cell, "--rehearse"))
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-2])["attempted"] >= 1
    assert json.loads(lines[-1]) == {"rc": 0, "compiles": 0, "loads": 0}
