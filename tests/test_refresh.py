"""ROADMAP item 1, closed loop (tier-1): drift breach → warm-start
retrain in a challenger workspace → eval guardrail vs the incumbent →
atomic registry promotion → in-place hot swap into the running fleet →
instant rollback.

Contracts drilled here:

- END-TO-END: a shifted window arrives at the watch loop, the PSI SLO
  breaches, the controller retrains warm, the guardrail passes, the
  challenger publishes atomically and hot-swaps into the live fleet —
  observed by a concurrently-scoring client with ZERO failed requests,
  zero steady-state compile misses, and the SAME service object (no
  restart).
- ADVERSARIAL TWIN: the same drill with a sabotaged challenger is
  REFUSED by the guardrail — HEAD unmoved, incumbent still serving.
- GUARDRAIL MATRIX: better / within-tolerance / worse / eval-faulted
  → promote / promote / hold / hold, each decision a `refresh` event
  in the metrics store.
- CHAOS: an injected fault at EVERY `refresh.*` site leaves the
  incumbent serving and HEAD unmoved or cleanly rolled back, with no
  `.tmp` residue, and a clean rerun promotes (rerun-recovers). SIGKILL
  mid-refresh holds the same invariant across a process boundary.
- HYSTERESIS: breaches during an in-flight refresh or inside the
  cooldown window coalesce into the running one — counted, evented,
  and visible in `shifu health`.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from shifu_tpu import registry, resilience
from shifu_tpu.cli import main as cli_main
from shifu_tpu.data import pipeline
from shifu_tpu.obs.health import store as health_store
from shifu_tpu.obs.health.refresh import RefreshController
from shifu_tpu.processor.base import ProcessorContext
from shifu_tpu.serve.fleet import FleetService

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LADDER = (1, 4)   # two tiny buckets keep AOT warms cheap in tier-1


@pytest.fixture(autouse=True)
def _refresh_isolation(monkeypatch):
    for k in ("SHIFU_TPU_METRICS", "SHIFU_TPU_SLO_FILE",
              "SHIFU_TPU_ALERT_WEBHOOK", "SHIFU_TPU_TRACE",
              "SHIFU_TPU_FAULT"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("SHIFU_TPU_RETRY_BASE_S", "0.01")
    resilience.reset_faults()
    yield
    resilience.reset_faults()


@pytest.fixture(scope="module")
def trained_set(tmp_path_factory):
    """ONE trained tiny model set per module (private rng — the
    golden-file tests share the session stream); tests copy it."""
    from tests.synth import make_model_set
    base = tmp_path_factory.mktemp("refresh_base")
    ms = make_model_set(base, np.random.default_rng(11), n_rows=400)
    cfg_path = os.path.join(ms, "ModelConfig.json")
    with open(cfg_path) as f:
        cfg = json.load(f)
    cfg["train"]["numTrainEpochs"] = 8
    with open(cfg_path, "w") as f:
        json.dump(cfg, f, indent=2)
    for cmd in ("init", "stats", "norm", "train"):
        assert cli_main(["--dir", ms, cmd]) == 0, cmd
    return ms


def _clone_set(trained_set, tmp_path):
    """Per-test copy. Its configs still point at the ORIGINAL data
    dirs (absolute paths) — fine for reads; tests inject drift through
    in-process windows, never by rewriting the shared data files."""
    ms = os.path.join(str(tmp_path), "ModelSet")
    shutil.copytree(trained_set, ms)
    return ms


def _raw_frame(trained_set):
    import pandas as pd
    hdr = open(os.path.join(trained_set, "data",
                            ".pig_header")).read().strip().split("|")
    return pd.read_csv(os.path.join(trained_set, "data", "part-00000"),
                       sep="|", names=hdr, dtype=str)


def _shift_numerics(df, delta):
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


def _publish_incumbent(ms, tmp_path, name="m"):
    reg = os.path.join(str(tmp_path), "reg")
    v1 = registry.publish(reg, name, os.path.join(ms, "models"),
                          ladder=LADDER)
    return reg, v1


def _no_tmp_residue(root):
    return [os.path.join(d, f) for d, _dirs, fs in os.walk(root)
            for f in fs if f.startswith(".tmp.")]


def _controller(ms, reg, fleet=None, **kw):
    kw.setdefault("tolerance", 0.2)
    kw.setdefault("cooldown_s", 0.0)
    return RefreshController(ProcessorContext.load(ms),
                             registry_root=reg, model_name="m",
                             fleet=fleet, **kw)


# ---------------------------------------------------------------------------
# the acceptance drill: shift → breach → retrain → guardrail → promote
# → in-place swap, observed by a live scoring client
# ---------------------------------------------------------------------------

def test_refresh_drill_end_to_end(trained_set, tmp_path, monkeypatch):
    from shifu_tpu.obs.health import watch as watch_mod

    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    ms = _clone_set(trained_set, tmp_path)
    reg, v1 = _publish_incumbent(ms, tmp_path)
    with open(os.path.join(ms, "slo.json"), "w") as f:
        json.dump({"slos": [
            {"name": "drift", "metric": "drift.psi_max", "op": "<=",
             "warn": 0.02, "breach": 0.05, "window_s": 86400.0,
             "agg": "last"}]}, f)
    df = _raw_frame(trained_set)
    shifted = _shift_numerics(df, delta=0.5)

    with FleetService(reg, workspace_root=ms, hbm_budget_mb=0) as fleet:
        _, _, man = registry.resolve(reg, "m")
        x = np.random.default_rng(3).normal(
            0, 1, (3, man["input_dim"])).astype(np.float32)
        before = np.asarray(fleet.submit("m", dense=x)["mean"])
        svc_before = fleet._entries["m"].service
        ctl = _controller(ms, reg, fleet=fleet)
        # the window accumulates old + newly-arrived shifted traffic
        ctl.note_window(df)

        # live scoring client rides through the whole refresh
        stop, failures, served = threading.Event(), [], [0]

        def client():
            while not stop.is_set():
                try:
                    fleet.submit("m", dense=x, timeout=30.0)
                    served[0] += 1
                except Exception as e:  # noqa: BLE001 — any miss fails
                    failures.append(e)

        pipeline.drain_stage_timers()   # fence off warm-up compiles
        t = threading.Thread(target=client, daemon=True)
        t.start()
        try:
            # one watch tick over the shifted window: drift observe →
            # PSI breach → the controller's full pipeline
            rc = watch_mod.run_monitor(ProcessorContext.load(ms),
                                       interval_s=0.0, iterations=1,
                                       windows=[shifted], refresh=ctl)
        finally:
            stop.set()
            t.join(timeout=30)
        stages = pipeline.drain_stage_timers()

        assert rc == 0
        assert ctl.last_outcome == "promoted", ctl.stats()
        # atomic promotion: HEAD moved, manifest carries the verdict
        assert registry.head(reg, "m") == "v002"
        _, _, man2 = registry.resolve(reg, "m")
        assert man2["refresh"]["refreshed_from"] == v1
        assert man2["refresh"]["challenger_auc"] >= \
            man2["refresh"]["incumbent_auc"] - 0.2
        # in-place swap: same service object (no restart), counted,
        # and NOTHING recompiled anywhere in the breach→swap window
        assert fleet._entries["m"].service is svc_before
        assert fleet.stats()["fleet"]["swaps"] == 1
        assert stages.get("compile_cache_misses", 0) == 0, stages
        # ... while the retrain and the guardrail, which DO compile
        # their programs at this window's row counts, were seen doing
        # so under their own name — moved, not lost
        assert stages.get("background_compile_cache_misses", 0) + \
            stages.get("background_compile_cache_hits", 0) > 0, stages
        assert stages.get("refresh_train_s", 0) > 0
        assert stages.get("fleet_swap_s", 0) > 0
        # the live client never saw a failed request, and the swap
        # really changed what scores come back
        assert not failures, failures[:3]
        assert served[0] > 0
        after = np.asarray(fleet.submit("m", dense=x)["mean"])
        assert not np.array_equal(before, after)

    # the full story landed in the store: drift → breach → refresh
    st = health_store.store(ms)
    names = [e["name"] for e in st.events(limit=50)]
    for want in ("event.drift", "event.breach", "event.refresh"):
        assert want in names, names
    phases = [e["tags"]["phase"] for e in st.events(limit=50,
                                                    names=["refresh"])]
    for want in ("scheduled", "guardrail", "promoted"):
        assert want in phases, phases
    assert not _no_tmp_residue(ms) and not _no_tmp_residue(reg)


def test_sabotaged_challenger_is_held_by_guardrail(trained_set,
                                                   tmp_path,
                                                   monkeypatch):
    """The adversarial twin: identical drill, but the challenger is
    scrambled after training — the guardrail must refuse it."""
    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    ms = _clone_set(trained_set, tmp_path)
    reg, v1 = _publish_incumbent(ms, tmp_path)

    def sabotage(clone):
        import jax

        from shifu_tpu.models.spec import (list_models, load_model,
                                           save_model)
        p = list_models(os.path.join(clone, "models"))[0]
        kind, meta, params = load_model(p)
        bad = jax.tree.map(lambda a: np.zeros_like(np.asarray(a)) - 3.0,
                           params)
        save_model(p, kind, meta, bad)

    with FleetService(reg, workspace_root=ms, hbm_budget_mb=0) as fleet:
        _, _, man = registry.resolve(reg, "m")
        x = np.random.default_rng(3).normal(
            0, 1, (3, man["input_dim"])).astype(np.float32)
        before = np.asarray(fleet.submit("m", dense=x)["mean"])
        ctl = _controller(ms, reg, fleet=fleet, post_train=sabotage,
                          tolerance=0.005)
        ctl.note_window(_raw_frame(trained_set))
        out = ctl.handle_breach({"slo": "drift", "state": "breach"})

        assert out == "held"
        assert ctl.stats()["held"] == 1
        # nothing moved: HEAD, the resident version, the scores
        assert registry.head(reg, "m") == v1
        assert fleet.stats()["fleet"]["swaps"] == 0
        after = np.asarray(fleet.submit("m", dense=x)["mean"])
        np.testing.assert_array_equal(before, after)

    st = health_store.store(ms)
    recs = st.events(limit=20, names=["refresh"])
    decisions = [e["tags"].get("decision") for e in recs
                 if e["tags"].get("phase") == "guardrail"]
    assert decisions == ["hold"], recs
    assert not _no_tmp_residue(ms) and not _no_tmp_residue(reg)


# ---------------------------------------------------------------------------
# guardrail decision matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("incumbent,challenger,tolerance,want,why", [
    (0.80, 0.85, 0.005, "promote", "challenger improved"),
    (0.80, 0.80, 0.005, "promote", "challenger improved"),
    (0.80, 0.798, 0.005, "promote", "within tolerance"),
    (0.80, 0.70, 0.005, "hold", "regressed beyond tolerance"),
    (0.80, 0.79, 0.0, "hold", "regressed beyond tolerance"),
])
def test_guardrail_decision_matrix(incumbent, challenger, tolerance,
                                   want, why):
    decision, reason = RefreshController.decide(incumbent, challenger,
                                                tolerance)
    assert (decision, reason) == (want, why)


def test_guardrail_eval_fault_holds_and_events(trained_set, tmp_path,
                                               monkeypatch):
    """A faulted eval can never promote: the run fails closed, HEAD
    stays, and the failure is an event in the store."""
    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    ms = _clone_set(trained_set, tmp_path)
    reg, v1 = _publish_incumbent(ms, tmp_path)
    ctl = _controller(ms, reg)
    ctl.note_window(_raw_frame(trained_set))
    monkeypatch.setenv("SHIFU_TPU_FAULT", "refresh.guardrail:oserror:1")
    resilience.reset_faults()
    out = ctl.handle_breach({"slo": "auc", "state": "breach"})
    assert out == "failed"
    assert registry.head(reg, "m") == v1
    st = health_store.store(ms)
    recs = st.events(limit=20, names=["refresh"])
    assert any(e["tags"].get("phase") == "failed" and
               "refresh.guardrail" in e["tags"].get("error", "")
               for e in recs), recs


# ---------------------------------------------------------------------------
# chaos: every refresh.* site — incumbent serving, HEAD sane, rerun
# recovers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site", ["refresh.schedule", "refresh.guardrail",
                                  "refresh.promote"])
def test_refresh_fault_leaves_head_unmoved_and_rerun_recovers(
        site, trained_set, tmp_path, monkeypatch):
    assert site in resilience.FAULT_SITES
    ms = _clone_set(trained_set, tmp_path)
    reg, v1 = _publish_incumbent(ms, tmp_path)
    ctl = _controller(ms, reg)
    df = _raw_frame(trained_set)
    ctl.note_window(df)
    monkeypatch.setenv("SHIFU_TPU_FAULT", f"{site}:oserror:1")
    resilience.reset_faults()
    out = ctl.handle_breach({"slo": "drift", "state": "breach"})
    assert out == "failed"
    assert registry.head(reg, "m") == v1
    assert not _no_tmp_residue(ms) and not _no_tmp_residue(reg)
    # rerun recovers: clear the fault, next breach promotes cleanly
    monkeypatch.delenv("SHIFU_TPU_FAULT")
    resilience.reset_faults()
    ctl.note_window(df)
    assert ctl.handle_breach({"slo": "drift", "state": "breach"}) \
        == "promoted"
    assert registry.head(reg, "m") == "v002"
    assert not _no_tmp_residue(ms) and not _no_tmp_residue(reg)


def test_swap_fault_rolls_back_instantly(trained_set, tmp_path,
                                         monkeypatch):
    """A failed swap AFTER the publish commit triggers the instant
    rollback: HEAD returns to the incumbent, the fleet never mutated,
    and the next breach promotes cleanly (roll forward)."""
    assert "refresh.swap" in resilience.FAULT_SITES
    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    ms = _clone_set(trained_set, tmp_path)
    reg, v1 = _publish_incumbent(ms, tmp_path)
    with FleetService(reg, workspace_root=ms, hbm_budget_mb=0) as fleet:
        _, _, man = registry.resolve(reg, "m")
        x = np.random.default_rng(3).normal(
            0, 1, (3, man["input_dim"])).astype(np.float32)
        before = np.asarray(fleet.submit("m", dense=x)["mean"])
        ctl = _controller(ms, reg, fleet=fleet)
        df = _raw_frame(trained_set)
        ctl.note_window(df)
        monkeypatch.setenv("SHIFU_TPU_FAULT", "refresh.swap:oserror:1")
        resilience.reset_faults()
        out = ctl.handle_breach({"slo": "drift", "state": "breach"})

        assert out == "rolled_back"
        assert ctl.stats()["rolled_back"] == 1
        # HEAD is back on the incumbent; v002 stays as an orphan dir
        # (roll forward is another publish); the incumbent still serves
        assert registry.head(reg, "m") == v1
        after = np.asarray(fleet.submit("m", dense=x)["mean"])
        np.testing.assert_array_equal(before, after)
        st = health_store.store(ms)
        phases = [e["tags"]["phase"]
                  for e in st.events(limit=20, names=["refresh"])]
        assert "rolled_back" in phases

        # rerun recovers across the rollback
        monkeypatch.delenv("SHIFU_TPU_FAULT")
        resilience.reset_faults()
        ctl.note_window(df)
        assert ctl.handle_breach({"slo": "drift", "state": "breach"}) \
            == "promoted"
        assert registry.head(reg, "m") == "v003"
        assert fleet.stats()["fleet"]["swaps"] == 1
    assert not _no_tmp_residue(ms) and not _no_tmp_residue(reg)


_KILL_DRILL = textwrap.dedent("""\
    import os, sys
    import pandas as pd
    ms, reg, data = sys.argv[1], sys.argv[2], sys.argv[3]
    from shifu_tpu.obs.health.refresh import RefreshController
    from shifu_tpu.processor.base import ProcessorContext
    hdr = open(os.path.join(data, ".pig_header")).read().strip().split("|")
    df = pd.read_csv(os.path.join(data, "part-00000"), sep="|",
                     names=hdr, dtype=str)
    ctl = RefreshController(ProcessorContext.load(ms), registry_root=reg,
                            model_name="m", tolerance=0.2, cooldown_s=0.0)
    ctl.note_window(df)
    # the injected SIGKILL fires inside refresh_once — raise if it
    # somehow completes
    ctl.refresh_once({"slo": "drift", "state": "breach"})
    raise SystemExit("refresh survived an injected kill")
""")


def test_sigkill_mid_refresh_incumbent_survives(trained_set, tmp_path):
    """SIGKILL at the promote point, across a real process boundary:
    HEAD unmoved, registry readable, no residue — and the rerun
    promotes."""
    ms = _clone_set(trained_set, tmp_path)
    reg, v1 = _publish_incumbent(ms, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               SHIFU_TPU_FAULT="refresh.promote:kill:1")
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_DRILL, ms, reg,
         os.path.join(trained_set, "data")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == -9, (proc.returncode, proc.stdout,
                                   proc.stderr)
    assert registry.head(reg, "m") == v1
    registry.resolve(reg, "m")   # raises if HEAD dangles
    assert not _no_tmp_residue(ms) and not _no_tmp_residue(reg)
    # rerun recovers in a clean process (this one)
    ctl = _controller(ms, reg)
    ctl.note_window(_raw_frame(trained_set))
    assert ctl.handle_breach({"slo": "drift", "state": "breach"}) \
        == "promoted"
    assert registry.head(reg, "m") == "v002"


# ---------------------------------------------------------------------------
# hysteresis: cooldown + in-flight coalescing
# ---------------------------------------------------------------------------

def test_breach_storm_coalesces_and_is_visible(trained_set, tmp_path,
                                               monkeypatch, capsys):
    monkeypatch.setenv("SHIFU_TPU_METRICS", "1")
    ms = _clone_set(trained_set, tmp_path)
    ctl = RefreshController(ProcessorContext.load(ms),
                            cooldown_s=3600.0)
    reentrant = []

    def fake_refresh(rec):
        # a second breach lands while this refresh is in flight
        reentrant.append(ctl.handle_breach({"slo": "auc",
                                            "state": "breach"}))
        return "promoted"

    monkeypatch.setattr(ctl, "refresh_once", fake_refresh)
    out = ctl.handle_breach({"slo": "drift", "state": "breach"})
    assert out == "promoted"
    assert reentrant == ["coalesced"]
    # third breach inside the cooldown window also coalesces
    assert ctl.handle_breach({"slo": "drift", "state": "breach"}) \
        == "coalesced"
    assert ctl.stats()["coalesced"] == 2

    st = health_store.store(ms)
    coal = [e for e in st.events(limit=20, names=["refresh"])
            if e["tags"].get("phase") == "coalesced"]
    assert len(coal) == 2 and coal[-1]["tags"]["count"] == 2
    assert st.series("refresh.coalesced")

    # `shifu health` surfaces the coalesced refresh events
    monkeypatch.delenv("SHIFU_TPU_METRICS")
    capsys.readouterr()
    cli_main(["--dir", ms, "health"])
    out_text = capsys.readouterr().out
    assert "refresh" in out_text and "phase=coalesced" in out_text


def test_window_accumulation_is_bounded(trained_set, tmp_path):
    import pandas as pd
    ms = _clone_set(trained_set, tmp_path)
    ctl = RefreshController(ProcessorContext.load(ms), window_rows=100)
    frame = pd.DataFrame({"a": np.arange(60)})
    for _ in range(5):
        ctl.note_window(frame)
    assert ctl.stats()["window_rows_pending"] <= 160   # ≤ cap + 1 frame
    got = ctl._take_window()
    assert len(got) == 100                             # hard cap on take
    assert ctl.stats()["window_rows_pending"] == 0
