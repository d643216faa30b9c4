"""Tests of `first_job.py` and the seven readers built on it, on hand-made
job records in place of the program's.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import first_job  # noqa: E402
from benchmark import run as harness  # noqa: E402
from shifu_tpu.obs import trace as program_trace  # noqa: E402

READERS = {"before_first_job_s": 12.5, "first_job_s": 5.25,
           "first_job_trace_s": 1.5, "first_job_lower_s": 0.75,
           "first_job_load_s": 0.5, "first_job_compile_s": 0.0,
           "first_job_programs": 48}


def record(start_s, seconds, **builds):
    return {"attrs": {"family": "nn", "rows": 7}, "start_s": start_s,
            "seconds": seconds,
            "builds": {"trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0,
                       "compile_s": 0.0, "traced": 0, "loaded": 0,
                       "compiled": 0, "functions": [], **builds}}


WARM_UP = record(12.5, 5.25, trace_s=1.5, lower_s=0.75, load_s=0.5,
                 traced=48, loaded=48)


def read_all():
    return {name: harness.load("layer_metrics", name).read({})
            for name in READERS}


def test_readers_read_the_first_record_and_no_later_one(monkeypatch):
    monkeypatch.setattr(program_trace, "job_records",
                        lambda: [WARM_UP, record(17.75, 0.25),
                                 record(18.0, 0.25, trace_s=9.0, traced=9)])
    assert read_all() == READERS
    got = read_all()
    parts = sum(got[f"first_job_{k}_s"]
                for k in ("trace", "lower", "load", "compile"))
    assert parts <= got["first_job_s"]


def test_a_program_without_job_records_reads_none(monkeypatch):
    """The parent of the PR that brought the records: `shifu_tpu.obs.trace`
    has no `job_records`, every reader returns None and the line leaves the
    metric out."""
    monkeypatch.delattr(program_trace, "job_records")
    assert first_job.first_record() is None
    assert read_all() == dict.fromkeys(READERS)


def test_no_job_yet_reads_none(monkeypatch):
    monkeypatch.setattr(program_trace, "job_records", lambda: [])
    assert read_all() == dict.fromkeys(READERS)


def test_a_host_without_proc_leaves_only_the_start_out(monkeypatch):
    monkeypatch.setattr(program_trace, "job_records",
                        lambda: [{**WARM_UP, "start_s": None}])
    got = read_all()
    assert got["before_first_job_s"] is None
    assert got["first_job_s"] == 5.25 and got["first_job_programs"] == 48


def test_no_program_at_all_reads_none(monkeypatch):
    monkeypatch.setitem(sys.modules, "shifu_tpu.obs", None)
    assert first_job.first_record() is None


def test_manifest_entries_move_setup_s_in_every_cell():
    manifest = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in manifest["workloads"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    layers = {"before_first_job_s": "entry",
              "first_job_s": "trainers, host side"}
    for name in READERS:
        m = entries[name]
        assert (m["moves"], m["source"], m["better"]) == \
            ("setup_s", "program_counter", "lower")
        assert m["workloads"] == cells
        assert m["layer"] == layers.get(name, "compile cache")
        assert m["unit"] == ("count" if name.endswith("programs") else "s")
    # appended: what the benchmark had comes first, as it was
    assert list(entries)[-7:] == list(READERS)


@pytest.mark.parametrize("name", sorted(READERS))
def test_layer_metrics_line_holds_the_reader(monkeypatch, name):
    """Through `run.layer_metrics`, as a traced run prints it."""
    monkeypatch.setattr(program_trace, "job_records", lambda: [WARM_UP])
    manifest = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    only = {**manifest, "per_layer": [m for m in manifest["per_layer"]
                                      if m["name"] == name]}
    line = harness.layer_metrics(only, "nn-higgs.train", {})
    assert line == {name: {"value": float(READERS[name]),
                           "unit": only["per_layer"][0]["unit"]}}
