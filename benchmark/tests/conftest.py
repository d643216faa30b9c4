"""Keeps `test_benchmark.py` collecting when the manifest holds a family
that file has no row for.

`test_benchmark.py` plants its faults from a table keyed by family
(`FAULTS`, `_plant`: `mlp` and `gbt`) and builds its cases from every cell
of `BENCHMARK.json` while it is imported, so one cell of another family
ends the import in a `KeyError` and all of the file's tests go dark, the
planted faults of the accepted cells among them. The file is the accepted
benchmark's: a PR that brings a family may not edit it. So a family brings
its tests in a file of its own, `test_<family>_family.py` (rehearsal,
control, every fault, faults planted under a run), and while
`test_benchmark.py` is being imported it reads the manifest without the
cells of such a family. Its tests of the accepted cells run as they did;
nothing else reads the shortened manifest.

This file goes when `FAULTS` reads `family.faults()` (PERF.md section 7,
for the next `benchmark` issue).
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
from benchmark import run as harness  # noqa: E402


def tested_in_its_own_file(family: str) -> bool:
    return os.path.isfile(os.path.join(HERE, f"test_{family}_family.py"))


def without_self_tested_families(manifest, read_json):
    family_of = {c["name"]: read_json(os.path.join(ROOT, c["file"]))["family"]
                 for c in manifest["configs"]}
    return {**manifest, "workloads": [
        w for w in manifest["workloads"]
        if not tested_in_its_own_file(family_of[w["config"]])]}


@pytest.hookimpl(hookwrapper=True)
def pytest_make_collect_report(collector):
    if not (isinstance(collector, pytest.Module)
            and collector.path.name == "test_benchmark.py"):
        yield
        return
    real = harness.read_json

    def read_json(path):
        data = real(path)
        if os.path.basename(path) == "BENCHMARK.json":
            return without_self_tested_families(data, real)
        return data

    harness.read_json = read_json
    try:
        yield
    finally:
        harness.read_json = real
