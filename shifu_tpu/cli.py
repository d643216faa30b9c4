"""Command-line interface — the `shifu` command surface, TPU-native.

Mirrors `shifu/ShifuCLI.java:162,887-941` (command parse + dispatch to
one processor per step, `-Dkey=value` overrides into a global
Environment). Commands:

  new <name>      create a model-set scaffold (CreateModelProcessor)
  init            header → ColumnConfig.json (InitModelProcessor)
  stats           column stats + binning       (StatsModelProcessor)
  norm|normalize  normalized/cleaned matrices  (NormalizeModelProcessor)
  varsel|varselect variable selection          (VarSelectModelProcessor)
  train           train models                 (TrainModelProcessor)
  posttrain       bin-avg scores + feature importance
  eval [-run name] score + confusion + perf    (EvalModelProcessor)
  export [-t ...] columnstats / correlation export
  test            dry-run filter expressions   (ShifuTestProcessor)
  version

Run inside a model-set directory (where ModelConfig.json lives), like
the reference CLI.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
from typing import List, Optional

logging.basicConfig(level=logging.INFO,
                    format="%(asctime)s [%(levelname)s] %(message)s")
log = logging.getLogger("shifu_tpu")


def _ctx(args):
    from shifu_tpu.processor.base import ProcessorContext
    return ProcessorContext.load(args.dir)


def cmd_new(args) -> int:
    """`shifu new <name>` — scaffold ModelConfig.json + columns/ dir
    (CreateModelProcessor)."""
    from shifu_tpu.config.model_config import ModelConfig
    name = args.name
    root = os.path.join(args.dir, name)
    if os.path.exists(os.path.join(root, "ModelConfig.json")):
        log.error("model set %s already exists", name)
        return 1
    os.makedirs(os.path.join(root, "columns"), exist_ok=True)
    mc = ModelConfig()
    mc.basic.name = name
    mc.basic.author = os.environ.get("USER", "user")
    mc.basic.description = f"Created at {time.strftime('%Y-%m-%d %H:%M:%S')}"
    mc.dataSet.dataPath = "./data"
    mc.dataSet.metaColumnNameFile = "columns/meta.column.names"
    mc.dataSet.categoricalColumnNameFile = "columns/categorical.column.names"
    mc.varSelect.forceSelectColumnNameFile = "columns/forceselect.column.names"
    mc.varSelect.forceRemoveColumnNameFile = "columns/forceremove.column.names"
    mc.train.params = {"NumHiddenLayers": 1, "NumHiddenNodes": [50],
                       "ActivationFunc": ["tanh"], "LearningRate": 0.1,
                       "Propagation": "Q", "RegularizedConstant": 0.0}
    mc.save(root)
    for f in ("meta", "categorical", "forceselect", "forceremove"):
        open(os.path.join(root, "columns", f + ".column.names"), "a").close()
    log.info("created model set %s", root)
    return 0


def cmd_init(args) -> int:
    from shifu_tpu.processor import init as p
    return p.run(_ctx(args))


def cmd_stats(args) -> int:
    ctx = _ctx(args)
    if args.correlation:
        from shifu_tpu.processor import correlation as p
        return p.run(ctx)
    if args.psi:
        from shifu_tpu.processor import psi as p
        return p.run(ctx)
    from shifu_tpu.processor import stats as p
    if args.rebin:
        return p.run_rebin(ctx, request_vars=args.vars,
                           expect_bin_num=args.n,
                           iv_keep_ratio=args.ivr, min_inst_cnt=args.bic)
    if args.seg is not None:
        return p.run_segment(ctx, args.seg)
    if args.seg_merge:
        return p.run_segment_merge(ctx)
    return p.run(ctx, base_only=args.base_only)


def cmd_norm(args) -> int:
    from shifu_tpu.processor import norm as p
    return p.run(_ctx(args))


def cmd_varselect(args) -> int:
    from shifu_tpu.processor import varselect as p
    return p.run(_ctx(args), recursive=args.recursive,
                 reset=args.reset, list_only=args.list,
                 select_file=args.file)


def cmd_train(args) -> int:
    from shifu_tpu.processor import train as p
    return p.run(_ctx(args))


def cmd_posttrain(args) -> int:
    from shifu_tpu.processor import posttrain as p
    return p.run(_ctx(args))


def cmd_eval(args) -> int:
    from shifu_tpu.processor import eval as p
    if args.list:
        return p.run_list(_ctx(args))
    if args.new:
        return p.run_new(_ctx(args), args.new)
    if args.delete:
        return p.run_delete(_ctx(args), args.delete)
    if args.norm:
        return p.run_norm(_ctx(args), eval_name=args.run)
    if args.audit:
        return p.run_audit(_ctx(args), eval_name=args.run,
                           n_records=args.n)
    if args.score is not False:
        return p.run_score(_ctx(args), eval_name=args.score or args.run)
    if args.confmat is not False:
        return p.run_confmat(_ctx(args),
                             eval_name=args.confmat or args.run)
    if args.perf is not False:
        return p.run_perf(_ctx(args), eval_name=args.perf or args.run)
    return p.run(_ctx(args), eval_name=args.run)


def cmd_serve(args) -> int:
    """`shifu serve` — persistent low-latency scorer over the trained
    model set: AOT-warms every shape bucket, micro-batches submits
    behind a bounded-latency admission queue, and (unless --no-http)
    answers POST /score on a stdlib HTTP/JSON listener. With
    --registry the process instead hosts a model FLEET: every
    published model (or just --models) behind POST /score/<model>,
    sharing the compile cache, LRU-evicting under the HBM budget, and
    shedding low-priority load when the high-priority p99 breaches
    the SLO. SIGTERM/SIGINT drain and stop the service (the
    graceful_shutdown contract the trainers use); --duration-s bounds
    the run for scripted use."""
    import json as _json
    import time as _time

    from shifu_tpu import resilience

    owner = None
    front = None
    if args.registry:
        from shifu_tpu.serve.fleet import FleetService
        names = [n for n in (args.models or "").split(",") if n] or None
        owner = FleetService(args.registry, names=names,
                             workspace_root=args.dir).start()
        log.info("fleet warm: %s", owner.stats()["fleet"])
        if not args.no_http:
            from shifu_tpu.serve.http import HttpFrontEnd
            front = HttpFrontEnd(fleet=owner, port=args.port).start()
            log.info("serving fleet HTTP on %s:%d", *front.address)
    else:
        from shifu_tpu.serve.service import ScorerService
        ctx = _ctx(args)
        owner = ScorerService(models_dir=ctx.path_finder.models_path(),
                              workspace_root=args.dir)
        owner.start()
        log.info("scorer service warm: %s", owner.stats())
        if not args.no_http:
            from shifu_tpu.serve.http import HttpFrontEnd
            front = HttpFrontEnd(owner, port=args.port).start()
            log.info("serving HTTP on %s:%d", *front.address)
    deadline = _time.monotonic() + args.duration_s if args.duration_s \
        else None
    try:
        with resilience.graceful_shutdown("serving"):
            while not resilience.preempt_requested():
                if deadline is not None and _time.monotonic() >= deadline:
                    break
                _time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    finally:
        if front is not None:
            front.close()
        owner.close()
    print(_json.dumps(owner.stats()))
    return 0


def cmd_registry(args) -> int:
    """`shifu registry` — versioned model publishing: publish the
    workspace's trained model set as an immutable version (atomic
    HEAD flip), list what's registered, roll HEAD back, or gc old
    versions. Pure file operations — no device is touched."""
    import json as _json

    from shifu_tpu import registry as reg

    root = args.registry or os.path.join(
        getattr(args, "dir", ".") or ".", "registry")
    if args.action == "publish":
        if not args.name:
            raise SystemExit("registry publish: --name is required")
        models_dir = args.models or \
            _ctx(args).path_finder.models_path()
        version = reg.publish(root, args.name, models_dir,
                              priority=args.priority,
                              max_delay_ms=args.max_delay_ms)
        print(_json.dumps({"name": args.name, "version": version,
                           "head": reg.head(root, args.name)}))
        return 0
    if args.action == "ls":
        print(_json.dumps(reg.ls(root), indent=1))
        return 0
    if args.action == "rollback":
        if not args.name:
            raise SystemExit("registry rollback: --name is required")
        version = reg.rollback(root, args.name, to=args.to)
        print(_json.dumps({"name": args.name, "head": version}))
        return 0
    if args.action == "gc":
        # no --name sweeps every registered model
        names = [args.name] if args.name else \
            [row["name"] for row in reg.ls(root)]
        out = []
        for name in names:
            removed = reg.gc(root, name, keep=args.keep)
            out.append({"name": name, "removed": removed,
                        "versions": reg.versions(root, name)})
        print(_json.dumps(out if args.name is None else out[0]))
        return 0
    raise SystemExit(f"registry: unknown action {args.action!r}")


def cmd_ingest(args) -> int:
    """`shifu ingest` — durable streaming row-log tooling (the ingest
    twin of `shifu ckpt`): `ingest ls` prints a JSON inventory of one
    log — partitions with sealed/open segment counts, total sealed
    rows, and every consumer's committed offset plus its lag in rows.
    Pure file operations — no device is touched."""
    import json as _json

    from shifu_tpu.data.ingest import RowLog

    if args.action == "ls":
        print(_json.dumps(RowLog(args.log).inventory(), indent=1))
        return 0
    raise SystemExit(f"ingest: unknown action {args.action!r}")


def cmd_watch(args) -> int:
    """`shifu watch` — the long-running model health loop: rolling
    PSI/KS drift over data arriving at the training dataPath, SLO
    guardrail evaluation with alerting, everything persisted to the
    metrics store (and span-traced, so `shifu top` shows the loop
    live). Full mode additionally closes ROADMAP item 1's loop: every
    breach schedules a warm-start retrain in a challenger workspace,
    an eval guardrail vs the incumbent, an atomic registry promotion
    and — when --registry/--model-name bind it to a published model —
    instant rollback on a failed swap. `--monitor-only` keeps the old
    alert-only behavior."""
    from shifu_tpu.obs.health import watch as watch_mod
    ctx = _ctx(args)
    ingest_log = None
    if args.ingest:
        from shifu_tpu.data.ingest import RowLog
        ingest_log = RowLog(args.ingest)
    refresh = None
    if not args.monitor_only:
        from shifu_tpu.obs.health.refresh import RefreshController
        refresh = RefreshController(
            ctx, registry_root=args.registry, model_name=args.model_name,
            eval_name=args.eval_set, ingest_log=ingest_log)
    if args.registry and args.model_name:
        # a canary run a SIGKILL interrupted left its state file in a
        # non-terminal phase — resolve it (rollback to the recorded
        # baseline) before this watch can breach into a new refresh
        from shifu_tpu.obs.health.canary import CanaryController
        CanaryController.recover(args.registry, args.model_name,
                                 store_root=ctx.path_finder.root)
    return watch_mod.run_monitor(
        ctx,
        interval_s=args.interval_s,
        iterations=args.iterations if args.iterations > 0 else None,
        refresh=refresh, ingest_log=ingest_log)


_SPARK_BARS = "▁▂▃▄▅▆▇█"


def _spark(values) -> str:
    """Unicode sparkline over a value series (empty-safe)."""
    vals = [float(v) for v in values]
    if not vals:
        return "-"
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK_BARS[0] * len(vals)
    scale = (len(_SPARK_BARS) - 1) / (hi - lo)
    return "".join(_SPARK_BARS[int((v - lo) * scale)] for v in vals)


def _canary_lines(st) -> list:
    """Live-promotion status lines from the metrics store: the last
    canary phase transition per model plus the freshest per-arm p99
    and between-arms PSI gauges the fleet flushed. Read-only and
    empty-safe — no arms ever started means no lines."""
    phases = {}
    for ev in st.events(limit=50, names=["canary"]):
        tags = ev.get("tags") or {}
        model = tags.get("model")
        if model:
            phases[model] = dict(tags, ts=ev.get("ts", 0))
    if not phases:
        return []
    p99 = {}   # (model, arm) → last value
    for p in st.read_points(names=["serve.arm_p99_ms"]):
        t = p.get("tags") or {}
        v = p.get("value")
        if isinstance(v, dict):   # rollup
            v = v.get("last")
        if isinstance(v, (int, float)) and t.get("model") \
                and t.get("arm"):
            p99[(t["model"], t["arm"])] = float(v)
    psi = {}
    for p in st.read_points(names=["canary.arm_psi"]):
        t = p.get("tags") or {}
        v = p.get("value")
        if isinstance(v, dict):
            v = v.get("last")
        if isinstance(v, (int, float)) and t.get("model"):
            psi[t["model"]] = float(v)
    lines = ["canary arms:"]
    for model, tags in sorted(phases.items()):
        bits = [f"phase={tags.get('phase', '?')}"]
        for k in ("run", "version", "shadow_pct", "canary_pct"):
            if k in tags:
                bits.append(f"{k}={tags[k]}")
        arm_bits = [f"p99[{arm}]={p99[(m, arm)]:.3f}ms"
                    for (m, arm) in sorted(p99) if m == model]
        bits.extend(arm_bits)
        if model in psi:
            bits.append(f"arm_psi={psi[model]:.4f}")
        lines.append(f"  {model}: " + " ".join(bits))
    return lines


def cmd_health(args) -> int:
    """`shifu health` — current SLO state over the metrics store:
    per-rule status with a sparkline trend of the underlying metric,
    the live-promotion (canary) arm status, plus the recent
    breach/warn event tail. Read-only (works without SHIFU_TPU_METRICS
    set — it inspects history already recorded)."""
    from shifu_tpu.obs.health import slo as slo_mod
    from shifu_tpu.obs.health import store as health_store
    root = args.dir
    state = slo_mod.health_state(root)
    st = health_store.store(root)
    print(f"status: {state['status'].upper()}  ({root})")
    name_w = max([len(s["name"]) for s in state["slos"]] + [4])
    met_w = max([len(s["metric"]) for s in state["slos"]] + [6])
    print(f"{'slo':<{name_w}}  {'state':<6} {'value':>10}  "
          f"{'metric':<{met_w}}  trend")
    for s in state["slos"]:
        series = st.series(s["metric"], limit=args.trend)
        val = "-" if s["value"] is None else f"{s['value']:.4g}"
        print(f"{s['name']:<{name_w}}  {s['state']:<6} {val:>10}  "
              f"{s['metric']:<{met_w}}  "
              f"{_spark([v for _, v in series])}")
    for line in _canary_lines(st):
        print(line)
    events = state["recent_events"]
    if events:
        print("recent events:")
        for ev in events:
            tags = ev.get("tags") or {}
            ts = time.strftime("%m-%d %H:%M:%S",
                               time.localtime(ev.get("ts", 0)))
            detail = " ".join(f"{k}={v}" for k, v in sorted(tags.items()))
            print(f"  {ts}  {ev.get('name', '?'):<16} {detail}")
    return 0 if state["status"] != "breach" else 1


def cmd_export(args) -> int:
    from shifu_tpu.processor import export as p
    return p.run(_ctx(args), export_type=args.type)


def cmd_test(args) -> int:
    """Dry-run the model set through the pipeline DAG scheduler
    (ShifuTestProcessor / DataPurifier): the train-data filter check,
    one node per eval set, and a full-pipeline DAG validation run as
    independent host-only sibling nodes, then I/O health (resilience
    retries) is reported. The per-node outcome lands as the `dag`
    block of this command's steps.jsonl record."""
    from shifu_tpu.data.purifier import DataPurifier
    from shifu_tpu.data.reader import read_raw_table
    from shifu_tpu.pipeline.nodes import STEP_REGISTRY, pipeline_nodes
    from shifu_tpu.pipeline.scheduler import Node, run_dag
    from shifu_tpu.resilience import retry_stats
    ctx = _ctx(args)
    mc = ctx.model_config
    root = ctx.path_finder.root

    def check_filter():
        df = read_raw_table(mc, max_rows=args.n)
        keep = DataPurifier(mc.dataSet.filterExpressions).apply(df)
        log.info("filter %r keeps %d / %d sampled records",
                 mc.dataSet.filterExpressions, int(keep.sum()), len(df))

    def check_eval(ec):
        def fn():
            df = read_raw_table(mc, ds=ec.dataSet, max_rows=args.n)
            keep = DataPurifier(ec.dataSet.filterExpressions).apply(df)
            log.info("eval %s: filter %r keeps %d / %d sampled records",
                     ec.name, ec.dataSet.filterExpressions,
                     int(keep.sum()), len(df))
        return fn

    def check_plan():
        # the full pipeline for this model set, validated (unique
        # names, known deps, acyclic) without running anything
        plan = pipeline_nodes(root, eval_sets=[e.name for e in mc.evals])
        log.info("pipeline DAG: %d nodes over %d registered steps "
                 "validate clean", len(plan), len(STEP_REGISTRY))

    def check_config():
        log.info("config: model set %s, algorithm %s, %d eval set(s)",
                 mc.model_set_name, mc.train.algorithm.value,
                 len(mc.evals))

    nodes = [Node("test.config", check_config, (), device=False)]
    nodes.append(Node("test.filter", check_filter, ("test.config",),
                      device=False))
    for ec in mc.evals:
        nodes.append(Node(f"test.eval.{ec.name}", check_eval(ec),
                          ("test.config",), device=False))
    nodes.append(Node("test.plan", check_plan, ("test.config",),
                      device=False))
    run_dag(nodes, root=root, label="test")
    retries = retry_stats()
    if retries:
        for site, d in sorted(retries.items()):
            log.warning("resilience: %s retried %d time(s), last error: "
                        "%s", site, d["attempts"], d["lastError"])
    else:
        log.info("resilience: no I/O retries")
    return 0


def cmd_encode(args) -> int:
    from shifu_tpu.processor import encode as p
    return p.run(_ctx(args))


def cmd_convert(args) -> int:
    """`shifu convert` — model spec ↔ open zip bundle
    (IndependentTreeModelUtils zip↔binary converter)."""
    from shifu_tpu.models.spec import bundle_to_spec, spec_to_bundle
    src, dst = args.src, args.out
    if src.endswith(".zip"):
        out = bundle_to_spec(src, dst)
    else:
        out = spec_to_bundle(src, dst if dst.endswith(".zip")
                             else dst + ".zip")
    log.info("convert: %s → %s", src, out)
    return 0


def cmd_combo(args) -> int:
    from shifu_tpu.processor import combo as p
    ctx = _ctx(args)
    if args.new:
        return p.new(ctx, args.new)
    if args.init:
        return p.init(ctx)
    if args.run:
        return p.run(ctx, resume=args.resume)
    if args.eval:
        return p.evaluate(ctx)
    raise SystemExit("combo: pass one of -new ALGS / -init / -run / -eval")


def cmd_save(args) -> int:
    from shifu_tpu.processor import manage as p
    return p.save(_ctx(args), args.name)


def cmd_switch(args) -> int:
    from shifu_tpu.processor import manage as p
    return p.switch(_ctx(args), args.name)


def cmd_show(args) -> int:
    from shifu_tpu.processor import manage as p
    return p.show(_ctx(args))


def cmd_ckpt(args) -> int:
    """Checkpoint inventory + topology: one JSON record per bag with
    the latest restorable step and the sharding sidecar's provenance
    (the mesh that wrote it, its logical→physical rules, how many
    leaves were device-sharded) — answers "what topology wrote this,
    and can the current fleet restore it?" without touching devices
    (elastic restores re-resolve the sidecar onto whatever mesh the
    restarted fleet actually has)."""
    import json
    from shifu_tpu.processor.base import ProcessorContext
    from shifu_tpu.train import checkpoint as ckpt_mod
    ctx = ProcessorContext.load(args.dir, need_columns=False)
    n_bags = max(ctx.model_config.train.baggingNum, 1)
    records = []
    for bag in range(n_bags):
        d = ctx.path_finder.checkpoint_path(bag)
        step = ckpt_mod.latest_step(d)
        if step is None:
            continue
        rec = {"bag": bag, "dir": d, "latestStep": step}
        meta = ckpt_mod.load_sharding_meta(d, step)
        if meta is None:
            rec["sharding"] = None   # pre-sidecar or all-host state:
            # restores replicated on any mesh
        else:
            rec["sharding"] = {
                "mesh": meta.get("mesh"),
                "rules": meta.get("rules"),
                "shardedLeaves": sum(1 for v in meta.get("leaves",
                                                         {}).values() if v),
                "deviceLeaves": len(meta.get("leaves", {}))}
        records.append(rec)
    print(json.dumps({"checkpoints": records}, indent=1))
    return 0


def _top_render(root: str) -> str:
    """One frame of `shifu top`: the last steps.jsonl records (step,
    rc, wall, trace block when present) plus any live span files from
    a trace run still in flight."""
    import glob as _glob
    lines = []
    steps_path = os.path.join(root, "tmp", "metrics", "steps.jsonl")
    recs = []
    try:
        with open(steps_path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue
    except OSError as e:
        from shifu_tpu.resilience import absorbed
        absorbed("cli.steps-read", e)
    recs = recs[-10:]
    if not recs:
        lines.append(f"no step records yet ({steps_path})")
    else:
        lines.append(f"{'step':<12} {'rc':>3} {'wall_s':>9} "
                     f"{'spans':>6} {'drop':>5}  top self-time")
        for rec in recs:
            tr = rec.get("trace") or {}
            top = ", ".join(
                f"{t['name']}={t['self_s']:.3f}s"
                for t in tr.get("top_self", [])) or "-"
            lines.append(
                f"{str(rec.get('step', '?')):<12} "
                f"{str(rec.get('rc', '-')):>3} "
                f"{float(rec.get('wallSeconds', 0.0)):>9.2f} "
                f"{str(tr.get('span_count', '-')):>6} "
                f"{str(tr.get('dropped_spans', '-')):>5}  {top}")
    live = []
    for d in sorted(_glob.glob(os.path.join(root, "tmp", "trace", "*"))):
        if not os.path.isdir(d):
            continue
        rid = os.path.basename(d)
        merged = os.path.join(root, "tmp", "trace",
                              rid + ".trace.json")
        if os.path.exists(merged):
            continue   # finished run, already merged
        n = len(_glob.glob(os.path.join(d, "spans.*.jsonl")))
        live.append(f"  {rid}: {n} span file(s), not yet merged")
    if live:
        lines.append("live trace runs:")
        lines.extend(live)
    # health/drift tail from the persistent metrics store (absorbed —
    # a corrupt store must not break the monitor)
    try:
        from shifu_tpu.obs.health import store as health_store
        _st = health_store.store(root)
        lines.extend(_canary_lines(_st))
        events = _st.events(
            limit=5, names=["drift", "breach", "warn", "recovered",
                            "refresh", "canary", "fleet_drift"])
        if events:
            lines.append("health/drift events:")
            for ev in events:
                tags = ev.get("tags") or {}
                ts = time.strftime("%H:%M:%S",
                                   time.localtime(ev.get("ts", 0)))
                detail = " ".join(f"{k}={v}"
                                  for k, v in sorted(tags.items()))
                lines.append(f"  {ts}  {ev.get('name', '?'):<16} {detail}")
    except Exception as e:  # noqa: BLE001 — monitoring must not fail top
        from shifu_tpu.resilience import absorbed
        absorbed("cli.status-events", e)
    return "\n".join(lines)


def cmd_top(args) -> int:
    """`shifu top` — live step/trace monitor over steps.jsonl and the
    trace workspace. Single-shot by default (scripts, tests); --watch
    redraws every --interval seconds until interrupted."""
    root = args.dir
    if not args.watch:
        print(_top_render(root))
        return 0
    try:
        while True:
            # ANSI clear + home, same contract as top(1)
            sys.stdout.write("\x1b[2J\x1b[H")
            print(time.strftime("%H:%M:%S"), "shifu top —", root)
            print(_top_render(root))
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    return 0


def cmd_trace(args) -> int:
    """`shifu trace ls` — pair merged span traces (tmp/trace/) with
    maybe_profile device traces (tmp/profile/) by shared run_id."""
    from shifu_tpu.obs import trace as obs_trace
    if args.action != "ls":
        raise SystemExit(f"trace: unknown action {args.action!r}")
    rows = obs_trace.trace_ls(args.dir)
    if not rows:
        print("no trace artifacts under tmp/trace or tmp/profile")
        return 0
    rid_w = max(len(r["run_id"]) for r in rows)
    print(f"{'run_id':<{rid_w}}  {'spans':>5}  trace / profile")
    for r in rows:
        paths = [p for p in (r["trace"], r["profile"]) if p]
        print(f"{r['run_id']:<{rid_w}}  {r['span_files']:>5}  "
              + (" + ".join(paths) or "-"))
    return 0


def cmd_version(args) -> int:
    import shifu_tpu
    print(f"shifu-tpu {shifu_tpu.__version__}")
    return 0


def cmd_knobs(args) -> int:
    """Print the SHIFU_TPU_* knob registry: every tunable the codebase
    reads, with type, documented default, current value and doc (the
    static analyzer guarantees the list is complete — an undeclared
    read is a lint failure)."""
    from shifu_tpu.config.environment import knobs_markdown, knobs_rows
    try:
        if getattr(args, "markdown", False):
            print(knobs_markdown(), end="")
            return 0
        rows = knobs_rows()
        name_w = max(len(r["name"]) for r in rows)
        type_w = max(len(r["type"]) for r in rows)
        dflt_w = max(max(len(r["default"]) for r in rows), len("default"))
        cur_w = max(max(len(r["current"]) for r in rows), len("current"))
        print(f"{'knob':<{name_w}}  {'type':<{type_w}}  "
              f"{'default':<{dflt_w}}  {'current':<{cur_w}}  doc")
        for r in rows:
            cur = r["current"] or "-"
            dflt = r["default"] or "-"
            print(f"{r['name']:<{name_w}}  {r['type']:<{type_w}}  "
                  f"{dflt:<{dflt_w}}  {cur:<{cur_w}}  {r['doc']}")
    except BrokenPipeError:
        # downstream pager/head closed the pipe; redirect stdout to
        # devnull so interpreter shutdown doesn't re-raise on flush
        devnull = os.open(os.devnull, os.O_WRONLY)
        os.dup2(devnull, sys.stdout.fileno())
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="shifu_tpu",
        description="TPU-native config-driven ML pipeline (Shifu-compatible "
                    "ModelConfig.json/ColumnConfig.json)")
    ap.add_argument("-D", dest="defines", action="append", default=[],
                    metavar="key=value",
                    help="environment overrides (ShifuCLI -D)")
    ap.add_argument("--dir", default=".", help="model-set directory")
    ap.add_argument("--profile", action="store_true",
                    help="capture a jax.profiler trace for this command "
                         "under tmp/profile/ (open in TensorBoard)")
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("new", help="create a model set")
    p.add_argument("name")
    p.set_defaults(fn=cmd_new)
    sub.add_parser("init", help="build ColumnConfig from header") \
        .set_defaults(fn=cmd_init)
    p = sub.add_parser("stats", help="column stats + binning")
    p.add_argument("-correlation", "--correlation", action="store_true")
    p.add_argument("-psi", "--psi", action="store_true")
    p.add_argument("-rebin", "--rebin", action="store_true",
                   help="merge existing bins for higher-IV coarse binning")
    p.add_argument("-vars", "--vars", default=None,
                   help="comma-separated columns to rebin")
    p.add_argument("-n", type=int, default=-1,
                   help="expected max bin number after rebin")
    p.add_argument("-ivr", type=float, default=1.0,
                   help="IV keep ratio while shrinking bins")
    p.add_argument("-bic", type=int, default=0,
                   help="minimum instance count per bin")
    p.add_argument("-seg", type=int, default=None,
                   help="compute stats for ONE segment expression "
                        "(1-based index) into a tmp partial — a DAG "
                        "sibling of the base stats step")
    p.add_argument("-seg-merge", "--seg-merge", action="store_true",
                   help="merge base + per-segment partials into "
                        "ColumnConfig.json")
    p.add_argument("-base-only", "--base-only", action="store_true",
                   help="skip segment expansion (the DAG runs segments "
                        "as sibling -seg steps)")
    p.set_defaults(fn=cmd_stats)
    for alias in ("norm", "normalize"):
        sub.add_parser(alias, help="normalize data").set_defaults(fn=cmd_norm)
    for alias in ("varsel", "varselect"):
        p = sub.add_parser(alias, help="variable selection")
        p.add_argument("-r", "--recursive", type=int, default=0)
        p.add_argument("-reset", "--reset", action="store_true",
                       help="reset all variables to finalSelect=false")
        p.add_argument("-list", "--list", action="store_true",
                       help="print currently selected variables")
        p.add_argument("-f", "--file", default=None, metavar="FILE",
                       help="select exactly the variables named in FILE")
        p.set_defaults(fn=cmd_varselect)
    sub.add_parser("train", help="train models").set_defaults(fn=cmd_train)
    sub.add_parser("posttrain", help="post-train analysis") \
        .set_defaults(fn=cmd_posttrain)
    p = sub.add_parser("eval", help="evaluate models")
    p.add_argument("-run", "--run", default=None, metavar="EVAL_NAME")
    p.add_argument("-list", "--list", action="store_true",
                   help="list configured eval sets")
    p.add_argument("-new", "--new", default=None, metavar="EVAL_NAME",
                   help="create a new eval set")
    p.add_argument("-delete", "--delete", default=None,
                   metavar="EVAL_NAME", help="delete an eval set")
    p.add_argument("-score", "--score", nargs="?", const=None,
                   default=False, metavar="EVAL_NAME",
                   help="scoring only (EvalScore.csv, no metrics)")
    p.add_argument("-confmat", "--confmat", nargs="?", const=None,
                   default=False, metavar="EVAL_NAME",
                   help="confusion matrix from an existing score file")
    p.add_argument("-perf", "--perf", nargs="?", const=None,
                   default=False, metavar="EVAL_NAME",
                   help="performance curves from an existing score file")
    p.add_argument("-norm", "--norm", action="store_true",
                   help="export normalized eval data instead of scoring")
    p.add_argument("-audit", "--audit", action="store_true",
                   help="score and write an audit sample with raw "
                        "variable values (eval -audit)")
    p.add_argument("-n", "--n", type=int, default=100,
                   help="audit record count (eval -audit -n N)")
    p.set_defaults(fn=cmd_eval)
    p = sub.add_parser("serve", help="low-latency scorer service")
    p.add_argument("--port", type=int, default=None,
                   help="HTTP port (default SHIFU_TPU_SERVE_PORT; "
                        "0 = ephemeral)")
    p.add_argument("--no-http", action="store_true",
                   help="in-process service only, no listener")
    p.add_argument("--duration-s", type=float, default=0.0,
                   help="exit after this many seconds (0 = run until "
                        "SIGTERM/SIGINT)")
    p.add_argument("--registry", default=None, metavar="DIR",
                   help="serve a model fleet from this registry root "
                        "(POST /score/<model>) instead of the "
                        "workspace model set")
    p.add_argument("--models", default=None, metavar="NAME,NAME",
                   help="fleet mode: host only these registry models "
                        "(default: every published model)")
    p.set_defaults(fn=cmd_serve)
    p = sub.add_parser("registry",
                       help="versioned model registry: "
                            "publish/ls/rollback/gc")
    p.add_argument("action",
                   choices=["publish", "ls", "rollback", "gc"])
    p.add_argument("--registry", default=None, metavar="DIR",
                   help="registry root (default <workspace>/registry)")
    p.add_argument("--name", default=None,
                   help="registered model name (publish/rollback/gc)")
    p.add_argument("--models", default=None, metavar="DIR",
                   help="publish: model-spec dir (default the "
                        "workspace's trained model set)")
    p.add_argument("--priority", default="high",
                   choices=["high", "low"],
                   help="publish: admission class for fleet serving")
    p.add_argument("--max-delay-ms", type=float, default=None,
                   help="publish: pin this model's micro-batch "
                        "admission deadline")
    p.add_argument("--to", default=None, metavar="vNNN",
                   help="rollback: target version (default: the one "
                        "before HEAD)")
    p.add_argument("--keep", type=int, default=None,
                   help="gc: versions to keep (default "
                        "SHIFU_TPU_REGISTRY_KEEP)")
    p.set_defaults(fn=cmd_registry)
    p = sub.add_parser("watch",
                       help="long-running model health monitor "
                            "(rolling drift + SLO guardrails)")
    p.add_argument("--monitor-only", action="store_true",
                   help="drift/SLO monitoring without the "
                        "drift-triggered retrain loop")
    p.add_argument("--registry", default=None,
                   help="registry root to promote refreshed models "
                        "into (with --model-name)")
    p.add_argument("--model-name", default=None,
                   help="registry model name bound to this model set")
    p.add_argument("--eval-set", default=None,
                   help="eval set for the refresh guardrail (default: "
                        "first configured)")
    p.add_argument("--interval-s", type=float, default=None,
                   help="tick period (default "
                        "SHIFU_TPU_WATCH_INTERVAL_S)")
    p.add_argument("--iterations", type=int, default=0,
                   help="stop after N ticks (0 = run until "
                        "SIGTERM/SIGINT)")
    p.add_argument("--ingest", default=None, metavar="LOG",
                   help="consume drift windows from this durable row "
                        "log (data/ingest.py) with exactly-once "
                        "offset commits instead of the deprecated "
                        "dataPath tail")
    p.set_defaults(fn=cmd_watch)
    p = sub.add_parser("ingest",
                       help="streaming row-log tooling: `ingest ls` "
                            "prints partitions, segments and "
                            "per-consumer offsets/lag as JSON")
    p.add_argument("action", choices=["ls"])
    p.add_argument("--log", required=True, metavar="DIR",
                   help="row-log root (local path or scheme:// URL)")
    p.set_defaults(fn=cmd_ingest)
    p = sub.add_parser("health",
                       help="SLO health over the metrics store: "
                            "status, trends, recent breaches")
    p.add_argument("--trend", type=int, default=30,
                   help="points per sparkline trend")
    p.set_defaults(fn=cmd_health)
    p = sub.add_parser("export", help="export model/stats")
    p.add_argument("-t", "--type", default="columnstats",
                   choices=["columnstats", "correlation", "woemapping",
                            "pmml", "tf", "bagging", "baggingpmml",
                            "woe", "ume", "baggingume", "normume"])
    p.set_defaults(fn=cmd_export)
    p = sub.add_parser("test", help="dry-run filter expressions")
    p.add_argument("-n", type=int, default=100)
    p.set_defaults(fn=cmd_test)
    sub.add_parser("encode", help="tree-leaf-path encode the dataset") \
        .set_defaults(fn=cmd_encode)
    p = sub.add_parser("convert",
                       help="model spec ↔ open zip bundle")
    p.add_argument("src", help="a model spec file or a .zip bundle")
    p.add_argument("out", help="output path (.zip for bundles)")
    p.set_defaults(fn=cmd_convert)

    p = sub.add_parser("combo", help="assembled multi-algorithm models")
    p.add_argument("-new", "--new", default=None, metavar="ALG1,ALG2,...",
                   help="create ComboTrain.json (last alg = assemble model)")
    p.add_argument("-init", "--init", action="store_true",
                   help="scaffold sub-model workspaces")
    p.add_argument("-run", "--run", action="store_true",
                   help="train sub-models + assemble model")
    p.add_argument("-eval", "--eval", action="store_true",
                   help="evaluate the assembled model")
    p.add_argument("-resume", "--resume", action="store_true",
                   help="skip already-trained sub-models")
    p.set_defaults(fn=cmd_combo)

    p = sub.add_parser("save", help="snapshot the model set")
    p.add_argument("name", nargs="?", default=None)
    p.set_defaults(fn=cmd_save)
    p = sub.add_parser("switch", help="restore a model-set snapshot")
    p.add_argument("name")
    p.set_defaults(fn=cmd_switch)
    sub.add_parser("show", help="list model-set snapshots") \
        .set_defaults(fn=cmd_show)
    p = sub.add_parser("knobs",
                       help="list every SHIFU_TPU_* knob (type/default/"
                            "current/doc)")
    p.add_argument("--markdown", action="store_true",
                   help="emit the markdown table (same as python -m "
                        "shifu_tpu.analysis --knobs-md)")
    p.set_defaults(fn=cmd_knobs)
    p = sub.add_parser("top",
                       help="live step/trace monitor (steps.jsonl + "
                            "in-flight span files)")
    p.add_argument("--watch", action="store_true",
                   help="redraw continuously until interrupted")
    p.add_argument("--interval", type=float, default=2.0,
                   help="redraw period in seconds (with --watch)")
    p.set_defaults(fn=cmd_top)
    p = sub.add_parser("trace",
                       help="trace artifacts: `trace ls` pairs span "
                            "traces with device traces by run_id")
    p.add_argument("action", choices=["ls"])
    p.set_defaults(fn=cmd_trace)
    sub.add_parser("ckpt",
                   help="checkpoint inventory: latest step + the mesh "
                        "topology that wrote it (sharding sidecar)") \
        .set_defaults(fn=cmd_ckpt)
    sub.add_parser("version").set_defaults(fn=cmd_version)
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    # global-defaults tier first ($SHIFU_HOME/conf/shifuconfig chain,
    # util/Environment.java:95-111) ...
    from shifu_tpu.config.environment import load_shifuconfig
    load_shifuconfig()
    # ... then -D overrides → environment (ShifuCLI.cleanArgs:468-492)
    for kv in args.defines:
        if "=" in kv:
            k, v = kv.split("=", 1)
            os.environ[k.strip()] = v.strip()
    # multi-host runtime comes up for every DEVICE-USING command
    # (stats/norm/eval shard over the same global mesh as train) — a
    # no-op single-process. Pure file-ops commands (new/save/switch/
    # show/convert/test/version) must not block on the coordinator
    # barrier just to copy files.
    if args.command in ("init", "stats", "norm", "normalize", "varsel",
                        "varselect", "train", "posttrain", "eval",
                        "export", "encode", "combo", "serve", "watch"):
        from shifu_tpu.parallel import dist
        dist.initialize()
        # one place turns on the persistent compile cache (and the
        # compile counters) for every device command, not just train
        # and serve; a scheduler parent (`run`, `combo`'s launcher) only
        # sets jax config here — no backend is created
        from shifu_tpu.profiling import enable_compile_cache
        enable_compile_cache()
    t0 = time.time()
    # every command emits one structured metrics record (and a
    # jax.profiler trace under --profile) — SURVEY §5's replacement for
    # master iteration logs / Hadoop counters / TailThread
    from shifu_tpu.obs.trace import trace_run
    from shifu_tpu.profiling import maybe_profile, step_metrics
    root = getattr(args, "dir", ".") or "."
    from shifu_tpu import resilience
    try:
        # trace_run sits INSIDE step_metrics (its exit attaches the
        # span summary to the step record before the record is written)
        # and OUTSIDE maybe_profile (so the device trace is named after
        # the live trace run's id — `shifu trace ls` pairs them)
        with step_metrics(root, args.command) as rec, \
                trace_run(root, args.command), \
                maybe_profile(root, args.command,
                              getattr(args, "profile", False)):
            if args.command in ("stats", "norm", "normalize", "varsel",
                                "varselect", "train", "eval", "serve"):
                # these do their device work in THIS process: take the
                # devices through the lease seam here, once, so the
                # step record carries backend/deviceKind whichever
                # route the command then takes (a fused kernel on the
                # default device never builds a mesh). Never for a
                # launcher (`run`, `combo`): it must not hold the chip.
                from shifu_tpu.parallel import mesh
                mesh.leased_devices()
            rc = args.fn(args)
            rec["rc"] = int(rc or 0)
    except resilience.Preempted as e:
        # checkpointed preemption shutdown: distinct rc so a
        # supervisor (systemd, a shell loop, k8s) knows to rerun with
        # SHIFU_TPU_RESUME=1 — the run resumes at the saved step
        log.warning("preempted: %s — exiting rc=%d; rerun with "
                    "SHIFU_TPU_RESUME=1 to resume", e,
                    resilience.PREEMPT_RC)
        # multi-host: peers exit first, the coordinator (process 0)
        # last — its death tears down the jax coordination service and
        # SIGABRTs any peer still inside a collective
        resilience.preempt_exit_sync()
        return resilience.PREEMPT_RC
    except (FileNotFoundError, ValueError, NotImplementedError) as e:
        log.error("%s", e)
        return 1
    log.info("command %s finished (rc=%s) in %.2fs", args.command, rc,
             time.time() - t0)
    return int(rc or 0)


if __name__ == "__main__":
    sys.exit(main())
