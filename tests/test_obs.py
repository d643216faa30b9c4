"""Trace-plane tests (tier-1): span recording semantics, ring-buffer
accounting, the disabled-path zero-cost contract, Perfetto export +
cross-host merge, serving span/timing parity, the /metrics exposition,
and the acceptance drill — a traced `shifu test` DAG run yields one
merged trace with a span per node, correctly parented.
"""

import gc
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from shifu_tpu.cli import main as cli_main
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.profiling import TRACE_FIELDS


@pytest.fixture(autouse=True)
def _trace_isolation(monkeypatch):
    """Every test starts with tracing off and no inherited workspace;
    a test that enables tracing does so explicitly."""
    monkeypatch.delenv("SHIFU_TPU_TRACE", raising=False)
    monkeypatch.delenv("SHIFU_TPU_TRACE_DIR", raising=False)
    monkeypatch.delenv("SHIFU_TPU_TRACE_BUF", raising=False)
    assert obs_trace._RUN is None
    yield
    obs_trace._RUN = None


# ---------------------------------------------------------------------------
# span semantics
# ---------------------------------------------------------------------------

def test_span_nesting_parentage_and_attrs(tmp_path, monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    with obs_trace.trace_run(str(tmp_path), "train") as run:
        assert obs_trace.active()
        with obs_trace.span("ckpt.stage", step=7) as outer:
            with obs_trace.span("ckpt.publish", step=7) as inner:
                pass
        rid = obs_trace.record_span("input.h2d", 1.0, 1.5, bytes=64)
    spans = {s["id"]: s for s in run.tracer.spans()}
    o, i = spans[outer.id], spans[inner.id]
    assert i["parent"] == outer.id
    assert o["parent"] == run.tracer.root_id
    assert o["args"] == {"step": 7}
    assert spans[rid]["name"] == "input.h2d"
    assert spans[rid]["args"] == {"bytes": 64}
    assert spans[rid]["dur"] == pytest.approx(0.5)
    # the root run.step span closed last, carrying the step attr
    root = spans[run.tracer.root_id]
    assert root["name"] == "run.step" and root["parent"] is None
    assert root["args"] == {"step": "train"}


def test_span_error_attr_recorded(tmp_path, monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    with obs_trace.trace_run(str(tmp_path), "train") as run:
        with pytest.raises(ValueError):
            with obs_trace.span("ckpt.stage") as sp:
                raise ValueError("boom")
    rec = {s["id"]: s for s in run.tracer.spans()}[sp.id]
    assert "boom" in rec["args"]["error"]


def test_ring_buffer_drops_oldest_and_counts(tmp_path):
    tr = obs_trace.Tracer("r", str(tmp_path), True, cap=8)
    ids = []
    for _ in range(20):
        sid = tr.new_id()
        ids.append(sid)
        tr.closed(sid, "input.h2d", None, 0.0, 0.001, {})
    kept = tr.spans()
    assert len(kept) == 8
    assert [s["id"] for s in kept] == ids[-8:]   # oldest dropped
    s = tr.summary()
    assert tuple(s) == TRACE_FIELDS
    assert s["span_count"] == 20
    assert s["dropped_spans"] == 12


def test_summary_top_self_excludes_child_time(tmp_path):
    tr = obs_trace.Tracer("r", str(tmp_path), True, cap=100)
    parent = tr.new_id()
    child = tr.new_id()
    tr.closed(child, "ckpt.publish", parent, 0.0, 0.9, {})
    tr.closed(parent, "ckpt.stage", None, 0.0, 1.0, {})
    top = {t["name"]: t["self_s"] for t in tr.summary()["top_self"]}
    assert top["ckpt.publish"] == pytest.approx(0.9, abs=1e-6)
    assert top["ckpt.stage"] == pytest.approx(0.1, abs=1e-6)


def test_open_spans_cited_by_watchdog_dump(tmp_path, monkeypatch):
    from shifu_tpu import resilience
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    with obs_trace.trace_run(str(tmp_path), "train"):
        with obs_trace.span("dist.collective", tag="allgather"):
            names = [s["name"] for s in obs_trace.open_spans()]
            assert "dist.collective" in names
            dump = resilience.dump_thread_stacks("test probe")
            assert "open spans:" in dump
            assert "dist.collective" in dump


# ---------------------------------------------------------------------------
# disabled path: zero files, bounded overhead
# ---------------------------------------------------------------------------

def test_disabled_records_nothing_and_writes_no_files(tmp_path):
    with obs_trace.trace_run(str(tmp_path), "train") as run:
        assert run is None
        assert not obs_trace.active()
        # disabled = a bare profiler annotation, never the ring's span
        sp = obs_trace.span("input.h2d")
        assert not isinstance(sp, obs_trace._Span)
        with sp:
            pass
        assert obs_trace.record_span("input.h2d", 0.0, 1.0) is None
        assert obs_trace.open_spans() == []
    assert not os.path.exists(os.path.join(str(tmp_path), "tmp", "trace"))


def _work():
    s = 0
    for i in range(4000):
        s += i * i
    return s


def test_disabled_span_overhead_under_5_percent():
    """The ISSUE gate: with the knob unset, wrapping the work in
    span() must cost ≤5% over the untraced loop. Plain/traced reps are
    interleaved (both sides see the same machine conditions), compared
    best-of-15 against best-of-15 so GC pauses and scheduler
    preemptions fall out of the minima, with up to three attempts —
    the gate asserts the capability (true disabled-path cost is ~0.1%
    here), not the worst case of a noisy shared box."""
    assert not obs_trace.active()
    n = 100

    def plain():
        t0 = time.perf_counter()
        for _ in range(n):
            _work()
        return time.perf_counter() - t0

    def traced():
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_trace.span("input.h2d"):
                _work()
        return time.perf_counter() - t0

    plain(), traced()   # warm both paths
    last = None
    for _attempt in range(3):
        bases, wraps = [], []
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for _ in range(15):
                bases.append(plain())
                wraps.append(traced())
        finally:
            if gc_was_enabled:
                gc.enable()
        last = (min(wraps), min(bases))
        if last[0] <= last[1] * 1.05:
            return
    assert last[0] <= last[1] * 1.05, last


# ---------------------------------------------------------------------------
# export + merge
# ---------------------------------------------------------------------------

def test_export_writes_wellformed_chronological_perfetto_json(
        tmp_path, monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    with obs_trace.trace_run(str(tmp_path), "train") as run:
        for i in range(5):
            obs_trace.record_span("input.host_parse",
                                  10.0 - i, 10.5 - i, chunk=i)
    out = os.path.join(str(tmp_path), "tmp", "trace",
                       f"{run.run_id}.trace.json")
    assert os.path.exists(out)
    with open(out, encoding="utf-8") as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert len(events) == 6   # 5 parses + the run.step root
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 1
        assert e["cat"] == e["name"].split(".", 1)[0]
        assert "id" in e["args"]
    # per-process span file kept alongside the merge
    assert os.path.exists(os.path.join(
        str(tmp_path), "tmp", "trace", run.run_id,
        f"spans.{os.getpid()}.jsonl"))


def test_two_host_merge_orders_by_corrected_clocks(tmp_path):
    tdir = str(tmp_path / "run1")
    os.makedirs(tdir)

    def _host(pid, offset, ts, name):
        with open(os.path.join(tdir, f"spans.{pid}.jsonl"), "w") as f:
            f.write(json.dumps({"clock": {"pid": pid,
                                          "offset_s": offset}}) + "\n")
            f.write(json.dumps({"id": f"{pid}:1", "parent": None,
                                "name": name, "ts": ts, "dur": 0.5,
                                "pid": pid, "tid": 1,
                                "thread": "MainThread"}) + "\n")

    # host B's clock runs 5s ahead: its raw ts is later but its
    # corrected time is EARLIER than host A's span
    _host(100, 0.0, 100.0, "dist.collective")
    _host(200, 5.0, 104.0, "dag.node")
    out = os.path.join(str(tmp_path), "merged.trace.json")
    doc = obs_trace.merge_trace(tdir, out)
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["dag.node", "dist.collective"]
    assert doc["traceEvents"][0]["ts"] == int(99.0 * 1e6)
    with open(out, encoding="utf-8") as f:
        assert json.load(f) == doc


def test_participant_mode_exports_but_never_merges(tmp_path, monkeypatch):
    """With SHIFU_TPU_TRACE_DIR inherited (DAG subprocess node, remote
    host), trace_run adopts the coordinator's workspace + run_id and
    leaves merging to the coordinator."""
    tdir = str(tmp_path / "tmp" / "trace" / "shared-run")
    os.makedirs(tdir)
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    monkeypatch.setenv("SHIFU_TPU_TRACE_DIR", tdir)
    with obs_trace.trace_run(str(tmp_path), "norm") as run:
        assert run.run_id == "shared-run"
        assert not run.tracer.coordinator
    assert os.path.exists(os.path.join(
        tdir, f"spans.{os.getpid()}.jsonl"))
    assert not os.path.exists(tdir + ".trace.json")
    # participants must not pop the coordinator's exported knob
    assert os.environ.get("SHIFU_TPU_TRACE_DIR") == tdir


def test_export_failure_never_fails_the_step(tmp_path, monkeypatch):
    from shifu_tpu import resilience
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    monkeypatch.setenv("SHIFU_TPU_FAULT", "obs.export:oserror:1")
    resilience.reset_faults()
    try:
        with obs_trace.trace_run(str(tmp_path), "train") as run:
            obs_trace.record_span("input.h2d", 0.0, 1.0)
        # absorbed: no exception escaped, no merged trace either
        assert not os.path.exists(os.path.join(
            str(tmp_path), "tmp", "trace",
            f"{run.run_id}.trace.json"))
    finally:
        monkeypatch.delenv("SHIFU_TPU_FAULT")
        resilience.reset_faults()


# ---------------------------------------------------------------------------
# serving span parity + /metrics
# ---------------------------------------------------------------------------

def test_serving_spans_match_submit_timed_splits(tmp_path, monkeypatch):
    from tests.test_serve import _tiny_nn_dir
    from shifu_tpu.serve.service import ScorerService

    models = _tiny_nn_dir(str(tmp_path / "models"))
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    with obs_trace.trace_run(str(tmp_path), "serve") as run:
        with ScorerService(models_dir=models, max_delay=0.005,
                           aot_compile=False) as svc:
            _, timing = svc.submit_timed(
                dense=np.zeros((3, 12), np.float32), timeout=60.0)
        spans = run.tracer.spans()
    req = [s for s in spans if s["name"] == "serve.request"]
    assert len(req) == 1
    children = {s["name"]: s for s in spans
                if s.get("parent") == req[0]["id"]}
    assert set(children) == {"serve.queue", "serve.pad", "serve.h2d",
                             "serve.device", "serve.d2h"}
    # spans are cut from the SAME timestamps the timing dict is
    # computed from — durations agree exactly, not approximately
    for stage in ("queue", "pad", "h2d", "device", "d2h"):
        assert children[f"serve.{stage}"]["dur"] == pytest.approx(
            timing[f"{stage}_s"], abs=1e-9), stage
    assert req[0]["dur"] == pytest.approx(timing["total_s"], abs=1e-9)
    flush = [s for s in spans if s["name"] == "serve.flush"]
    assert flush and flush[0]["args"]["requests"] == 1
    # synthetic track: every serving span rides the "serve" track
    assert req[0]["thread"] == "serve"


def test_metrics_endpoint_parses_as_prometheus_text(tmp_path):
    from tests.test_serve import _tiny_nn_dir
    from shifu_tpu.serve.http import HttpFrontEnd
    from shifu_tpu.serve.service import ScorerService

    models = _tiny_nn_dir(str(tmp_path / "models"))
    with ScorerService(models_dir=models, max_delay=0.005,
                       aot_compile=False) as svc:
        svc.submit(dense=np.zeros((2, 12), np.float32), timeout=60.0)
        front = HttpFrontEnd(svc, host="127.0.0.1", port=0).start()
        try:
            host, port = front.address
            with urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=30) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                body = r.read().decode()
        finally:
            front.close()
    samples = {}
    for line in body.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE "))
            continue
        name_labels, value = line.rsplit(" ", 1)
        samples[name_labels] = float(value)   # every sample parses
    assert samples["shifu_serve_requests_total"] == 1.0
    assert samples["shifu_serve_rows_total"] == 2.0
    assert 'shifu_serve_latency_ms{quantile="0.5"}' in samples


# ---------------------------------------------------------------------------
# acceptance: traced DAG run, steps.jsonl block, CLI surfaces
# ---------------------------------------------------------------------------

def _tiny_model_set(tmp_path):
    # a PRIVATE generator: drawing from the session-scoped `rng`
    # fixture here would shift the stream under the golden-file tests
    # that share it
    from tests.synth import make_model_set
    return make_model_set(tmp_path, np.random.default_rng(7), n_rows=300)


def test_traced_dag_run_produces_merged_trace_with_node_parentage(
        tmp_path, monkeypatch, capsys):
    model_set = _tiny_model_set(tmp_path)
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    assert cli_main(["--dir", model_set, "test"]) == 0
    monkeypatch.delenv("SHIFU_TPU_TRACE")

    import glob
    merged = glob.glob(os.path.join(model_set, "tmp", "trace",
                                    "*.trace.json"))
    assert len(merged) == 1
    with open(merged[0], encoding="utf-8") as f:
        events = json.load(f)["traceEvents"]
    roots = [e for e in events if e["name"] == "run.step"]
    assert len(roots) == 1
    root_id = roots[0]["args"]["id"]
    nodes = [e for e in events if e["name"] == "dag.node"]
    assert {e["args"]["node"] for e in nodes} == {
        "test.config", "test.filter", "test.eval.Eval1", "test.plan"}
    node_ids = set()
    for e in nodes:
        assert e["args"]["parent"] == root_id
        assert e["args"]["state"] == "done"
        node_ids.add(e["args"]["id"])
    for kid in (e for e in events if e["name"] in ("dag.queue",
                                                   "dag.run")):
        assert kid["args"]["parent"] in node_ids

    # the step record carries the TRACE_FIELDS summary block
    steps = os.path.join(model_set, "tmp", "metrics", "steps.jsonl")
    recs = [json.loads(l) for l in open(steps, encoding="utf-8")
            if l.strip()]
    traced = [r for r in recs if r["step"] == "test" and "trace" in r]
    assert traced, "no steps.jsonl record carries a trace block"
    block = traced[-1]["trace"]
    assert tuple(block) == TRACE_FIELDS
    assert block["span_count"] >= 1 + 3 * len(nodes)
    assert block["dropped_spans"] == 0

    # knob stayed unset for the untraced rerun → no NEW trace files
    assert cli_main(["--dir", model_set, "test"]) == 0
    assert glob.glob(os.path.join(model_set, "tmp", "trace",
                                  "*.trace.json")) == merged

    # CLI surfaces: `trace ls` pairs the run's artifacts, `top` renders
    # the step records with the trace summary
    capsys.readouterr()
    assert cli_main(["--dir", model_set, "trace", "ls"]) == 0
    out = capsys.readouterr().out
    run_id = os.path.basename(merged[0])[:-len(".trace.json")]
    assert run_id in out and "run_id" in out
    assert cli_main(["--dir", model_set, "top"]) == 0
    out = capsys.readouterr().out
    assert "test" in out and "dag.run" in out


def test_profile_output_named_after_trace_run_id(tmp_path, monkeypatch):
    """maybe_profile's directory and the span trace share a run_id so
    `shifu trace ls` pairs device and host traces."""
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    with obs_trace.trace_run(str(tmp_path), "train") as run:
        assert obs_trace.current_run_id("train") == run.run_id
    rows = obs_trace.trace_ls(str(tmp_path))
    assert [r["run_id"] for r in rows] == [run.run_id]
    assert rows[0]["trace"] and rows[0]["span_files"] == 1
    # untraced: a fresh id still namespaced by step + pid
    rid = obs_trace.current_run_id("eval")
    assert rid.startswith("eval-") and rid.endswith(str(os.getpid()))
