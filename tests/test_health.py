"""Model-health-plane tests (tier-1): the persistent metrics store
(disabled-path zero-file contract, restart survival, rollup
compaction), rolling drift parity against the one-shot `stats -psi`,
SLO transitions with hysteresis, and the acceptance drill — a `shifu
watch --monitor-only` tick over injected drift produces a breach
that is visible in the store, in `shifu health`, in `shifu top`, and
as `watch.*` spans in the merged trace — plus the chaos contract
(obs.metrics_flush / obs.alert / watch.window faults are absorbed).
"""

import glob
import json
import logging
import os

import numpy as np
import pandas as pd
import pytest

from shifu_tpu import resilience
from shifu_tpu.cli import main as cli_main
from shifu_tpu.obs.health import store as health_store
from shifu_tpu.obs.health.drift import RollingDrift
from shifu_tpu.obs.health.slo import SloEvaluator, load_slos
from shifu_tpu.processor.base import ProcessorContext


@pytest.fixture(autouse=True)
def _health_isolation(monkeypatch):
    """Every test starts with the metrics knob off and no inherited
    SLO/webhook config; a test that records does so explicitly."""
    for k in ("SHIFU_TPU_METRICS", "SHIFU_TPU_METRICS_ROLLUP",
              "SHIFU_TPU_SLO_FILE", "SHIFU_TPU_ALERT_WEBHOOK",
              "SHIFU_TPU_TRACE", "SHIFU_TPU_FAULT"):
        monkeypatch.delenv(k, raising=False)
    resilience.reset_faults()
    yield
    resilience.reset_faults()


def _tiny_model_set(tmp_path, n_rows=300, seed=7):
    # PRIVATE generator: the golden-file tests share the session rng
    # stream, and these fixtures must not shift it
    from tests.synth import make_model_set
    return make_model_set(tmp_path, np.random.default_rng(seed),
                          n_rows=n_rows)


def _raw_frame(model_set):
    dpath = os.path.join(model_set, "data", "part-00000")
    hpath = os.path.join(model_set, "data", ".pig_header")
    header = open(hpath).read().strip().split("|")
    return pd.read_csv(dpath, sep="|", names=header, dtype=str), header


def _shift_numerics(df, delta=5.0):
    """A drifted copy: every num_* value moves +delta (missing tokens
    kept), so the window's distribution piles into the top training
    bin → large PSI vs the frozen baseline."""
    out = df.copy()
    for col in out.columns:
        if not col.startswith("num_"):
            continue
        v = out[col].to_numpy(dtype=object).copy()
        for i, s in enumerate(v):
            try:
                v[i] = f"{float(s) + delta:.6f}"
            except (TypeError, ValueError):
                pass
        out[col] = v
    return out


# ---------------------------------------------------------------------------
# metrics store: disabled path, persistence, rollup
# ---------------------------------------------------------------------------

def test_disabled_path_writes_no_files_enabled_survives_restart(
        tmp_path, monkeypatch):
    root = str(tmp_path)
    st = health_store.MetricsStore(root)
    st.emit("serve.p99_ms", 12.5)
    st.counter("step.completed", step="stats")
    assert st.flush() == 0
    # the whole knob-off path is inert: no buffer, no directory
    assert not os.path.exists(os.path.join(root, "tmp", "metrics"))
    assert st.series("serve.p99_ms") == []

    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    st.emit("serve.p99_ms", 12.5, ts=100.0)
    st.emit("serve.p99_ms", 14.0, ts=101.0, model="nn")
    st.event("drift", features="num_0")
    assert st.flush() == 3
    assert os.path.exists(health_store.metrics_path(root))

    # a NEW store instance (process restart) reads the same history
    st2 = health_store.MetricsStore(root)
    assert st2.series("serve.p99_ms") == [(100.0, 12.5), (101.0, 14.0)]
    ev = st2.events(names=["drift"])
    assert len(ev) == 1 and ev[0]["tags"]["features"] == "num_0"
    pt = st2.read_points(names=["serve.p99_ms"])[1]
    # schema pinned by profiling.METRIC_FIELDS
    from shifu_tpu.profiling import METRIC_FIELDS
    assert tuple(pt) == METRIC_FIELDS
    assert pt["tags"] == {"model": "nn"}

    # the read path keeps working after the knob goes away (the
    # `shifu health` inspect-someone-else's-history contract)
    monkeypatch.delenv("SHIFU_TPU_METRICS")
    assert health_store.MetricsStore(root).series("serve.p99_ms") \
        == [(100.0, 12.5), (101.0, 14.0)]


def test_rollup_compacts_but_preserves_recent_queries(
        tmp_path, monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    monkeypatch.setenv("SHIFU_TPU_METRICS_ROLLUP", "1500")
    root = str(tmp_path)
    st = health_store.MetricsStore(root)
    base = 1_786_000_000.0
    n = 300
    for i in range(n):
        st.emit("serve.p99_ms", float(i), ts=base + 10.0 * i)
        if i % 25 == 0:
            st.flush()
    st.flush()

    path = health_store.metrics_path(root)
    pts = health_store.MetricsStore(root).read_points()
    by_kind = {}
    for p in pts:
        by_kind.setdefault(p["kind"], []).append(p)
    assert "rollup" in by_kind, "size bound never triggered compaction"
    # compacted: far fewer lines than points emitted
    assert sum(1 for _ in open(path)) < n

    # conservation: rollup counts + surviving raw points == everything
    # ever emitted (compaction aggregates, it never drops)
    total = sum(p["value"]["count"] for p in by_kind["rollup"]) \
        + len(by_kind["gauge"])
    assert total == n
    for p in by_kind["rollup"]:
        assert set(p["value"]) == {"count", "sum", "min", "max", "last"}

    # the recent window reads back verbatim and time-ordered, with the
    # newest RAW value last (a rollup may never shadow newer points)
    ser = health_store.MetricsStore(root).series("serve.p99_ms")
    ts = [t for t, _ in ser]
    assert ts == sorted(ts)
    assert ser[-1] == (base + 10.0 * (n - 1), float(n - 1))
    gauges = by_kind["gauge"]
    assert len(gauges) >= 8   # compaction must keep a raw tail
    raw_tail = [v for _, v in ser][-len(gauges):]
    assert raw_tail == [float(v) for v in range(n - len(gauges), n)]
    # every rollup is older than every surviving raw point, so a
    # since= window over the raw tail sees only raw points
    first_raw_ts = min(p["ts"] for p in gauges)
    assert all(p["ts"] <= first_raw_ts for p in by_kind["rollup"])
    recent = health_store.MetricsStore(root).read_points(
        names=["serve.p99_ms"], since=first_raw_ts)
    assert all(p["kind"] == "gauge" for p in recent)
    assert len(recent) == len(gauges)


# ---------------------------------------------------------------------------
# rolling drift: parity with the one-shot `stats -psi`
# ---------------------------------------------------------------------------

def test_rolling_psi_windows_reproduce_one_shot_cohort_psi(tmp_path):
    """Feed the one-shot PSI job's cohorts to RollingDrift as arriving
    windows: `mean_psi_vs_global()` must reproduce `columnStats.psi`
    (same counts, same float64 psi_metric) to 1e-8."""
    from shifu_tpu.config.column_config import load_column_configs

    model_set = _tiny_model_set(tmp_path, n_rows=1000, seed=11)
    # the test_psi month-cohort surgery: append a month column and
    # point psiColumnName at it
    df, header = _raw_frame(model_set)
    df["month"] = np.where(np.arange(len(df)) % 2 == 0, "m1", "m2")
    df.to_csv(os.path.join(model_set, "data", "part-00000"), sep="|",
              header=False, index=False)
    with open(os.path.join(model_set, "data", ".pig_header"), "w") as f:
        f.write("|".join(header + ["month"]) + "\n")
    mc_path = os.path.join(model_set, "ModelConfig.json")
    mc = json.load(open(mc_path))
    mc["stats"]["psiColumnName"] = "month"
    with open(mc["dataSet"]["metaColumnNameFile"], "a") as f:
        f.write("month\n")
    json.dump(mc, open(mc_path, "w"))

    for cmd in (["init"], ["stats"], ["stats", "-psi"]):
        assert cli_main(["--dir", model_set] + cmd) == 0

    ctx = ProcessorContext.load(model_set)
    drift = RollingDrift(ctx)
    full, _ = _raw_frame(model_set)
    for cohort in ("m1", "m2"):
        win = full[full["month"] == cohort].reset_index(drop=True)
        snap = drift.observe(win)
        assert snap["rows"] > 0 and snap["features"]
        # random even/odd cohorts vs the full-table baseline: no drift
        assert snap["psi_max"] < 0.05

    rolling = drift.mean_psi_vs_global()
    ccs = load_column_configs(os.path.join(model_set,
                                           "ColumnConfig.json"))
    compared = {"num": 0, "cat": 0}
    for cc in ccs:
        if cc.columnStats.psi is None or cc.columnName not in rolling:
            continue
        assert rolling[cc.columnName] == pytest.approx(
            cc.columnStats.psi, abs=1e-8), cc.columnName
        compared["cat" if cc.is_categorical else "num"] += 1
    assert compared["num"] >= 4 and compared["cat"] >= 2, compared


def test_drift_monitor_requires_frozen_bins(tmp_path):
    model_set = _tiny_model_set(tmp_path)
    assert cli_main(["--dir", model_set, "init"]) == 0
    with pytest.raises(ValueError, match="run `shifu stats` first"):
        RollingDrift(ProcessorContext.load(model_set))


def test_drift_monitor_flags_shifted_window(tmp_path):
    model_set = _tiny_model_set(tmp_path, n_rows=600, seed=13)
    for cmd in (["init"], ["stats"]):
        assert cli_main(["--dir", model_set] + cmd) == 0
    drift = RollingDrift(ProcessorContext.load(model_set))
    df, _ = _raw_frame(model_set)
    calm = drift.observe(df)
    assert calm["psi_max"] < 0.05 and calm["drifted"] == []
    hot = drift.observe(_shift_numerics(df))
    assert hot["psi_max"] > 0.25
    assert any(f.startswith("num_") for f in hot["drifted"])
    # categorical columns did not move
    assert not any(f.startswith("cat_") for f in hot["drifted"])


# ---------------------------------------------------------------------------
# SLO watchdog: classification, hysteresis, alert fan-out
# ---------------------------------------------------------------------------

_LAT_SLO = {"name": "lat", "metric": "serve.p99_ms", "op": "<=",
            "warn": 50.0, "breach": 200.0, "window_s": 3600.0,
            "agg": "last"}


def test_slo_transitions_hysteresis_and_sinks(tmp_path, monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    root = str(tmp_path)
    st = health_store.store(root)
    ev = SloEvaluator(root, slos=[dict(_LAT_SLO)], clear=2)
    seen = []
    ev.register_sink(seen.append)

    def tick(value):
        st.emit("serve.p99_ms", value)
        return ev.evaluate()[0]["state"]

    # no data → ok; absence of evidence never pages anyone
    assert ev.evaluate()[0]["state"] == "ok"
    assert tick(10.0) == "ok"
    # degrade IMMEDIATELY: one bad sample is a real warn/breach
    assert tick(120.0) == "warn"
    assert tick(500.0) == "breach"
    # recovery is damped: `clear`=2 consecutive better samples needed
    assert tick(10.0) == "breach"
    assert tick(10.0) == "ok"

    states = [r["state"] for r in ev.drain_transitions()]
    assert states == ["warn", "breach", "ok"]
    assert ev.drain_transitions() == []          # drained
    assert [r["state"] for r in seen] == states  # custom sink saw all
    from shifu_tpu.profiling import HEALTH_FIELDS
    assert set(HEALTH_FIELDS) <= set(seen[0])    # pinned record shape
    # the file sink persisted every transition next to the store
    alerts = os.path.join(root, "tmp", "metrics", "alerts.jsonl")
    recs = [json.loads(l) for l in open(alerts) if l.strip()]
    assert [r["state"] for r in recs] == states
    # every evaluation left a health.<slo> gauge rank series
    ranks = [v for _, v in st.series("health.lat")]
    assert ranks == [0.0, 0.0, 1.0, 2.0, 2.0, 0.0]


def test_slo_larger_is_better_orientation(tmp_path, monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    root = str(tmp_path)
    st = health_store.store(root)
    auc = {"name": "auc", "metric": "eval.auc", "op": ">=",
           "warn": 0.75, "breach": 0.70, "window_s": 3600.0}
    ev = SloEvaluator(root, slos=[auc], clear=1)
    for value, want in ((0.9, "ok"), (0.72, "warn"), (0.6, "breach")):
        st.emit("eval.auc", value)
        assert ev.evaluate()[0]["state"] == want, value


def test_slo_file_precedence(tmp_path, monkeypatch):
    root = str(tmp_path)
    defaults = load_slos(root)
    assert {s["name"] for s in defaults} >= {"serve_p99", "drift", "auc"}
    with open(os.path.join(root, "slo.json"), "w") as f:
        json.dump({"slos": [dict(_LAT_SLO)]}, f)
    assert [s["name"] for s in load_slos(root)] == ["lat"]
    other = tmp_path / "override.json"
    other.write_text(json.dumps([dict(_LAT_SLO, name="ovr")]))
    monkeypatch.setenv("SHIFU_TPU_SLO_FILE", str(other))
    assert [s["name"] for s in load_slos(root)] == ["ovr"]
    # malformed rules are rejected loudly, not half-loaded
    other.write_text(json.dumps([{"name": "x", "metric": "m"}]))
    with pytest.raises(ValueError, match="missing"):
        load_slos(root)


# ---------------------------------------------------------------------------
# acceptance drill: watch tick over injected drift → breach everywhere
# ---------------------------------------------------------------------------

def test_watch_drill_breach_visible_in_health_top_and_trace(
        tmp_path, monkeypatch, capsys, caplog):
    model_set = _tiny_model_set(tmp_path)
    for cmd in (["init"], ["stats"]):
        assert cli_main(["--dir", model_set] + cmd) == 0

    # AFTER stats froze the bins, the arriving data shifts: rewrite the
    # dataPath so the watch loop's first window is drifted production
    # traffic vs the frozen training baseline
    df, _ = _raw_frame(model_set)
    _shift_numerics(df).to_csv(
        os.path.join(model_set, "data", "part-00000"), sep="|",
        header=False, index=False)
    with open(os.path.join(model_set, "slo.json"), "w") as f:
        json.dump({"slos": [
            {"name": "drift", "metric": "drift.psi_max", "op": "<=",
             "warn": 0.05, "breach": 0.2, "window_s": 86400.0,
             "agg": "last"}]}, f)

    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    with caplog.at_level(logging.WARNING):
        assert cli_main(["--dir", model_set, "watch", "--monitor-only",
                         "--iterations", "1", "--interval-s", "0"]) == 0
    monkeypatch.delenv("SHIFU_TPU_TRACE")
    # monitor-only leaves the retrain loop open and says so
    assert "no refresh controller attached" in caplog.text

    # 1. persisted: drift + breach events and the psi gauge on DISK
    # (a fresh store instance — restart-visible, not buffer state)
    st = health_store.MetricsStore(model_set)
    names = {e["name"] for e in st.events(limit=20)}
    assert {"event.drift", "event.breach"} <= names
    assert st.series("drift.psi_max")[-1][1] > 0.2
    alerts = os.path.join(model_set, "tmp", "metrics", "alerts.jsonl")
    assert any(json.loads(l)["state"] == "breach"
               for l in open(alerts) if l.strip())

    # 2. `shifu health`: breach status (exit 1), the rule, the events
    monkeypatch.delenv("SHIFU_TPU_METRICS")   # read path needs no knob
    capsys.readouterr()
    assert cli_main(["--dir", model_set, "health"]) == 1
    out = capsys.readouterr().out
    assert "status: BREACH" in out
    assert "drift.psi_max" in out and "recent events:" in out

    # 3. `shifu top`: the health/drift event tail renders
    assert cli_main(["--dir", model_set, "top"]) == 0
    out = capsys.readouterr().out
    assert "health/drift events:" in out and "event.breach" in out

    # 4. the watch tick was span-traced into the merged trace
    merged = glob.glob(os.path.join(model_set, "tmp", "trace",
                                    "*.trace.json"))
    assert len(merged) == 1
    events = json.load(open(merged[0]))["traceEvents"]
    spans = {e["name"] for e in events}
    assert {"watch.window", "watch.evaluate"} <= spans
    win = next(e for e in events if e["name"] == "watch.window")
    assert win["args"]["rows"] == len(df)


def test_watch_full_mode_routes_breach_to_refresh(tmp_path, monkeypatch):
    """`shifu watch` (no --monitor-only) attaches a RefreshController
    and a breach lands in its handle_breach — the loop is closed."""
    from shifu_tpu.obs.health import refresh as refresh_mod

    model_set = _tiny_model_set(tmp_path)
    for cmd in (["init"], ["stats"]):
        assert cli_main(["--dir", model_set] + cmd) == 0
    df, _ = _raw_frame(model_set)
    _shift_numerics(df).to_csv(
        os.path.join(model_set, "data", "part-00000"), sep="|",
        header=False, index=False)
    with open(os.path.join(model_set, "slo.json"), "w") as f:
        json.dump({"slos": [
            {"name": "drift", "metric": "drift.psi_max", "op": "<=",
             "warn": 0.05, "breach": 0.2, "window_s": 86400.0,
             "agg": "last"}]}, f)
    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    calls = []
    monkeypatch.setattr(
        refresh_mod.RefreshController, "handle_breach",
        lambda self, rec: calls.append(rec) or "promoted")
    noted = []
    monkeypatch.setattr(
        refresh_mod.RefreshController, "note_window",
        lambda self, w: noted.append(len(w)))
    assert cli_main(["--dir", model_set, "watch",
                     "--iterations", "1", "--interval-s", "0"]) == 0
    assert calls and calls[0]["state"] == "breach"
    # every observed window also fed the controller as retrain fodder
    assert noted == [len(df)]


# ---------------------------------------------------------------------------
# chaos: health-plane faults are absorbed, never fatal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site", ["obs.metrics_flush", "obs.alert",
                                  "watch.window"])
def test_health_plane_faults_absorbed(tmp_path, monkeypatch, site):
    from shifu_tpu.obs.health import watch as watch_mod

    model_set = _tiny_model_set(tmp_path)
    for cmd in (["init"], ["stats"]):
        assert cli_main(["--dir", model_set] + cmd) == 0
    df, _ = _raw_frame(model_set)
    with open(os.path.join(model_set, "slo.json"), "w") as f:
        json.dump({"slos": [
            {"name": "drift", "metric": "drift.psi_max", "op": "<=",
             "warn": 0.05, "breach": 0.2, "window_s": 86400.0}]}, f)

    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    monkeypatch.setenv("SHIFU_TPU_FAULT", f"{site}:oserror:1")
    resilience.reset_faults()
    ctx = ProcessorContext.load(model_set)
    rc = watch_mod.run_monitor(ctx, interval_s=0.0, iterations=1,
                               windows=[_shift_numerics(df)])
    assert rc == 0, f"{site}: monitor must absorb the fault"
    monkeypatch.delenv("SHIFU_TPU_FAULT")
    resilience.reset_faults()

    st = health_store.MetricsStore(model_set)
    if site == "watch.window":
        # the window was skipped (counted), drift never computed — and
        # the monitor lived to flush the skip counter
        assert st.series("watch.window_failed") != []
        assert st.series("drift.psi_max") == []
    else:
        # the drift window itself survived; a flush retry (rebuffered
        # points) / the surviving sinks carried the evidence to disk
        assert st.series("drift.psi_max")[-1][1] > 0.2
        assert {e["name"] for e in st.events(limit=20)} >= \
            {"event.drift", "event.breach"}
    if site == "obs.alert":
        # one sink dispatch died; the OTHERS still fired (per-sink
        # absorption) — the file sink's record reached disk
        alerts = os.path.join(model_set, "tmp", "metrics",
                              "alerts.jsonl")
        assert os.path.exists(alerts)


# ---------------------------------------------------------------------------
# webhook alert sink: a REAL bounded-timeout HTTP POST, retried through
# the obs.webhook site, absorbed by the alert fan-out when dead
# ---------------------------------------------------------------------------

def _webhook_server():
    import http.server
    import threading
    received = []

    class _Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", "0"))
            received.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *_a):   # keep pytest output quiet
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), _Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, received


def test_webhook_sink_posts_and_retries_through_fault(monkeypatch):
    """The sink delivers the breach record to a live receiver, and a
    transient fault at the obs.webhook site is retried away — the
    POST still lands."""
    from shifu_tpu.obs.health import slo as slo_mod
    srv, received = _webhook_server()
    try:
        url = f"http://127.0.0.1:{srv.server_address[1]}/alert"
        monkeypatch.setenv("SHIFU_TPU_ALERT_WEBHOOK", url)
        monkeypatch.setenv("SHIFU_TPU_RETRY_BASE_S", "0.01")
        slo_mod.webhook_sink({"slo": "drift", "state": "breach",
                              "value": 0.41})
        assert received and received[-1]["slo"] == "drift"
        monkeypatch.setenv("SHIFU_TPU_FAULT", "obs.webhook:oserror:1")
        resilience.reset_faults()
        slo_mod.webhook_sink({"slo": "auc", "state": "warn"})
        assert received[-1]["slo"] == "auc"
        assert len(received) == 2   # retry did not double-deliver
    finally:
        srv.shutdown()


def test_dead_webhook_never_fails_the_watch_tick(tmp_path, monkeypatch,
                                                 caplog):
    """Nothing listens on the configured port: the bounded timeout +
    retry budget exhausts, the failure raises out of the sink, and the
    alert fan-out ABSORBS it — the transition still reaches the other
    sinks (alerts.jsonl) and the caller never sees an error."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    monkeypatch.setenv("SHIFU_TPU_ALERT_WEBHOOK",
                       f"http://127.0.0.1:{port}/alert")
    monkeypatch.setenv("SHIFU_TPU_ALERT_WEBHOOK_TIMEOUT_S", "0.2")
    monkeypatch.setenv("SHIFU_TPU_RETRY_ATTEMPTS", "2")
    monkeypatch.setenv("SHIFU_TPU_RETRY_BASE_S", "0.01")
    root = str(tmp_path)
    ev = SloEvaluator(root, slos=[], clear=1)
    with caplog.at_level(logging.WARNING):
        ev.alert({"slo": "lat", "state": "breach", "value": 999.0})
    assert "webhook_sink" in caplog.text and "absorbed" in caplog.text
    alerts = os.path.join(root, "tmp", "metrics", "alerts.jsonl")
    recs = [json.loads(l) for l in open(alerts) if l.strip()]
    assert recs and recs[-1]["slo"] == "lat"
