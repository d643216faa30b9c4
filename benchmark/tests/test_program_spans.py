"""Tests of `program_spans.py` and the five readers built on it, on
hand-made planes through `reduce_planes`, as a traced run hands them over.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import program_spans  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

MS = 1_000_000
READERS = ["train_prepare_ms", "train_place_ms", "train_program_ms",
           "train_fetch_ms", "host_unnamed_ms"]


def reduced(host_events, window_ms=1000):
    """A trace of one device and one host thread; times in milliseconds
    from the window's start, which lies 5 s into the trace."""
    t0 = 5000
    events = [("bench:window", t0 * MS, window_ms * MS, "")] + [
        (name, (t0 + start) * MS, dur * MS, "")
        for name, start, dur in host_events]
    return tr.reduce_planes([
        ("/host:CPU", [("main", events)]),
        ("/device:TPU:0", [("XLA Ops", [("fusion.1", (t0 + 1) * MS, MS, "")]),
                           ("XLA Modules", [])])])


def read_all(trace):
    return {name: harness.load("layer_metrics", name).read({"trace": trace})
            for name in READERS}


# two calls of 400 ms; each a job of 380 ms that starts 10 ms in
TWO_CALLS = [
    ("bench:call", 0, 400), ("shifu:train.job", 10, 380),
    ("shifu:train.prepare", 10, 40), ("shifu:train.place", 60, 20),
    ("shifu:train.program", 80, 100),
    ("PjitFunction(train_bags_carry)", 85, 90),      # jax's own: not a span
    ("shifu:train.wait", 180, 150), ("shifu:host.sync", 185, 100),
    ("shifu:train.fetch", 330, 10), ("shifu:train.fetch", 345, 30),
    ("bench:call", 500, 400), ("shifu:train.job", 510, 380),
    ("shifu:train.prepare", 510, 60), ("shifu:train.place", 570, 20),
    ("shifu:train.program", 590, 140), ("shifu:train.wait", 730, 120),
    ("shifu:train.fetch", 850, 40),
]


def test_phases_are_means_over_the_calls():
    got = read_all(reduced(TWO_CALLS))
    assert got["train_prepare_ms"] == pytest.approx(50)     # (40 + 60) / 2
    assert got["train_place_ms"] == pytest.approx(20)
    assert got["train_program_ms"] == pytest.approx(120)    # (100 + 140) / 2
    assert got["train_fetch_ms"] == pytest.approx(40)       # (10 + 30 + 40) / 2
    # call 1: 400 - 40 - 20 - 100 - 150 - 40 = 50; call 2: 400 - 380 = 20
    assert got["host_unnamed_ms"] == pytest.approx(35)


def test_unnamed_is_the_self_time_of_call_and_job():
    calls = program_spans.spans_by_call(reduced(TWO_CALLS))
    (call1, in1), (call2, in2) = calls
    job1 = next(e for e in in1 if e.name == program_spans.JOB_SPAN)
    assert call1.self_s == pytest.approx(0.020)              # outside the job
    assert job1.self_s == pytest.approx(0.030)               # gaps inside it
    assert call2.self_s == pytest.approx(0.020)
    # a span of another family nests inside a phase and takes nothing
    # from it; jax's own events are no spans
    assert {e.name for e in in1} == {
        "shifu:train." + p for p in
        ("job", "prepare", "place", "program", "wait", "fetch")}


def test_a_phase_nested_in_a_phase_is_subtracted_once():
    trace = reduced([("bench:call", 0, 100), ("shifu:train.job", 0, 100),
                     ("shifu:train.program", 10, 60),
                     ("shifu:train.wait", 20, 30)])
    got = read_all(trace)
    assert got["train_program_ms"] == pytest.approx(60)
    assert got["host_unnamed_ms"] == pytest.approx(40)       # 100 - 60


def test_a_span_cut_by_the_windows_end_counts_to_the_cut():
    """A window ends on a call boundary, but a reducer clips whatever
    crosses it: the span is read as far as the window goes."""
    trace = reduced([("bench:call", 0, 100), ("shifu:train.job", 0, 100),
                     ("shifu:train.program", 0, 100),
                     ("bench:call", 900, 200), ("shifu:train.job", 900, 200),
                     ("shifu:train.prepare", 900, 50),
                     ("shifu:train.program", 950, 150)])
    assert trace.window_s == 1.0
    got = read_all(trace)
    assert got["train_program_ms"] == pytest.approx(75)      # (100 + 50) / 2
    assert got["train_prepare_ms"] == pytest.approx(25)
    assert got["host_unnamed_ms"] == pytest.approx(0)


def test_a_span_under_a_millisecond_reads_as_unnamed():
    """`read_planes` drops host events under a millisecond, so the reader
    never sees them; with a job span in the call the phase reads 0."""
    got = read_all(reduced([("bench:call", 0, 10),
                            ("shifu:train.job", 0, 10),
                            ("shifu:train.program", 2, 6)]))
    assert got["train_prepare_ms"] == 0
    assert got["train_program_ms"] == pytest.approx(6)
    assert got["host_unnamed_ms"] == pytest.approx(4)


@pytest.mark.parametrize("events", [
    [("bench:call", 0, 400), ("PjitFunction(train_bags_carry)", 10, 90)],
    [("bench:call", 0, 400), ("shifu:host.sync", 10, 90)],
    [],
], ids=["parent-checkout", "no-job-span", "no-call"])
def test_no_job_span_reads_none(events):
    assert read_all(reduced(events)) == dict.fromkeys(READERS)


def test_the_manifest_lists_the_five_for_every_cell():
    manifest = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in manifest["workloads"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert entries[name]["workloads"] == cells
        assert entries[name]["layer"] == "trainers, host side"
        assert entries[name]["moves"] == "train_rows_per_s"
