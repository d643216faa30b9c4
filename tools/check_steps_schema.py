#!/usr/bin/env python
"""Schema-drift gate: README-documented steps.jsonl stage fields must
exist among the keys the code actually emits.

README describes the per-stage timing keys carried in each
``tmp/metrics/steps.jsonl`` record's ``inputPipeline`` block
(`host_parse_s`, `ckpt_stall_s`, `compile_s`, ...). The only writers
of that block are ``pipeline.add_stage_time`` / ``add_stage_count``,
so the emitted vocabulary is statically enumerable: this script
AST-walks ``shifu_tpu/`` collecting every string-literal stage name
passed to those calls (plus the string defaults of ``stage=``
parameters, which name the key when callers rely on the default),
extracts the backticked stage tokens README claims, and exits 1 when
documented ⊄ emitted — a renamed or deleted stage key must not leave
the README describing fields that no longer appear in the logs.

Token heuristic: backticked lowercase identifiers ending in ``_s``,
``_hits`` or ``_misses`` are treated as stage fields; ``*per_s`` /
``*_frac`` tokens are rates and shares, not steps.jsonl stages, and
are skipped.

The ``roofline`` block (processor/train.py attaches one to the train
step's record) is pinned the same way: its schema is the single
``profiling.ROOFLINE_FIELDS`` tuple (AST-read, no imports), every
field must be documented in README, and a live log's block must
carry exactly those keys.

The fleet summary block is pinned likewise: ``stats()["fleet"]`` from
serve/fleet.py is ``profiling.FLEET_FIELDS``, every field must be
README-documented, and the builder must reference the tuple.

The ``dag`` block (every command routed through the pipeline DAG
scheduler) is pinned the same way: per-node records are
``profiling.DAG_FIELDS``, the summary is ``profiling.DAG_SUMMARY_FIELDS``,
every member must be README-documented, and the scheduler must build
its records from the tuple. Members of the pinned tuples are excluded
from the stage-field heuristic — `queue_s`/`wall_s`/... are dag-block
keys, not ``inputPipeline`` stages.

The ``trace`` block (attached to every step run with
``SHIFU_TPU_TRACE=1``) is pinned likewise: its schema is
``profiling.TRACE_FIELDS``, every member must be README-documented,
and obs/trace.py must build the block from the tuple.

The job record (what a closed ``train.job`` span leaves in
``obs.trace.job_records()``, and the ``first_job`` block of the step
that ran the process's first job) is pinned likewise: its schema is
``profiling.JOB_FIELDS`` with ``profiling.BUILD_FIELDS`` for its
``builds``, every member must be README-documented, and obs/trace.py
must build the record from the tuples. Members that are also stage
keys (`trace_s`, `lower_s`, `compile_s`) stay under the stage check.

The health plane is pinned likewise: every metrics.jsonl point is
``profiling.METRIC_FIELDS`` (built by obs/health/store.py), every SLO
record is ``profiling.HEALTH_FIELDS`` (built by obs/health/slo.py),
every member must be README-documented, and both modules must
reference their tuple.

Optionally pass a real steps.jsonl to ALSO verify against a live log
(every documented field must appear in at least one record's
``inputPipeline`` block across the file, and any record carrying a
``roofline`` block must carry exactly the ROOFLINE_FIELDS keys):

    python tools/check_steps_schema.py [path/to/steps.jsonl]
"""

from __future__ import annotations

import ast
import functools
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "shifu_tpu")
README = os.path.join(REPO, "README.md")

_TOKEN = re.compile(r"`([a-z][a-z0-9_]*(?:_s|_hits|_misses))`")
_WRITERS = ("add_stage_time", "add_stage_count")


def documented_fields() -> set:
    with open(README, encoding="utf-8") as f:
        text = f.read()
    # members of the pinned block schemas (roofline/fleet/dag/...) are
    # documented as those blocks' keys, not inputPipeline stages
    pinned = set(roofline_fields()) | set(fleet_fields()) | \
        set(dag_fields()) | set(dag_summary_fields()) | \
        set(trace_fields()) | set(metric_fields()) | \
        set(health_fields()) | (set(job_fields()) - emitted_fields())
    return {tok for tok in _TOKEN.findall(text)
            if "per_s" not in tok and not tok.endswith("_frac")
            and tok not in pinned}


@functools.lru_cache(maxsize=None)
def emitted_fields() -> set:
    out = set()
    for dirpath, dirs, files in os.walk(PKG):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    fn = node.func
                    fname = fn.attr if isinstance(fn, ast.Attribute) \
                        else getattr(fn, "id", None)
                    if fname in _WRITERS and node.args and \
                            isinstance(node.args[0], ast.Constant) and \
                            isinstance(node.args[0].value, str):
                        out.add(node.args[0].value)
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    # `stage="host_assemble_s"` style defaults name the
                    # emitted key when callers rely on the default
                    a = node.args
                    params = a.posonlyargs + a.args + a.kwonlyargs
                    defaults = ([None] * (len(a.posonlyargs + a.args)
                                          - len(a.defaults))
                                + list(a.defaults) + list(a.kw_defaults))
                    for p, d in zip(params, defaults):
                        if p.arg == "stage" and \
                                isinstance(d, ast.Constant) and \
                                isinstance(d.value, str):
                            out.add(d.value)
    return out


def _profiling_tuple(name: str) -> tuple:
    """A module-level tuple constant from profiling.py, read from the
    AST so this gate keeps working without importing jax-adjacent
    modules."""
    path = os.path.join(PKG, "profiling.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == name
                for t in node.targets):
            return tuple(ast.literal_eval(node.value))
    raise SystemExit(f"profiling.py no longer defines {name}")


def roofline_fields() -> tuple:
    return _profiling_tuple("ROOFLINE_FIELDS")


def fleet_fields() -> tuple:
    return _profiling_tuple("FLEET_FIELDS")


def dag_fields() -> tuple:
    return _profiling_tuple("DAG_FIELDS")


def dag_summary_fields() -> tuple:
    return _profiling_tuple("DAG_SUMMARY_FIELDS")


def trace_fields() -> tuple:
    return _profiling_tuple("TRACE_FIELDS")


def job_fields() -> tuple:
    return _profiling_tuple("JOB_FIELDS") + _profiling_tuple("BUILD_FIELDS")


def metric_fields() -> tuple:
    return _profiling_tuple("METRIC_FIELDS")


def health_fields() -> tuple:
    return _profiling_tuple("HEALTH_FIELDS")


def check_roofline_docs() -> int:
    """Every ROOFLINE_FIELDS member must be backtick-documented in
    README — a field added to the block without docs, or renamed out
    from under them, fails here."""
    fields = roofline_fields()
    with open(README, encoding="utf-8") as f:
        documented = set(re.findall(r"`([a-z][a-z0-9_]*)`", f.read()))
    missing = sorted(set(fields) - documented)
    if missing:
        print("roofline schema drift: ROOFLINE_FIELDS member(s) never "
              f"documented in README: {missing}", file=sys.stderr)
        return 1
    print(f"roofline block: all {len(fields)} ROOFLINE_FIELDS "
          "documented in README")
    return 0


def check_fleet_docs() -> int:
    """Every FLEET_FIELDS member (the ``stats()["fleet"]`` block) must
    be backtick-documented in README's Model fleet section, and
    serve/fleet.py must build its dict from the tuple — the literal
    check asserts it references `FLEET_FIELDS` so the block cannot
    silently drift from the pinned schema."""
    fields = fleet_fields()
    with open(README, encoding="utf-8") as f:
        documented = set(re.findall(r"`([a-z][a-z0-9_]*)`", f.read()))
    missing = sorted(set(fields) - documented)
    if missing:
        print("fleet schema drift: FLEET_FIELDS member(s) never "
              f"documented in README: {missing}", file=sys.stderr)
        return 1
    with open(os.path.join(PKG, "serve", "fleet.py"),
              encoding="utf-8") as f:
        if "FLEET_FIELDS" not in f.read():
            print("shifu_tpu/serve/fleet.py no longer builds the fleet "
                  "block from profiling.FLEET_FIELDS", file=sys.stderr)
            return 1
    print(f"model fleet: all {len(fields)} FLEET_FIELDS documented in "
          "README and pinned in serve/fleet.py")
    return 0


def check_dag_docs() -> int:
    """Every DAG_FIELDS / DAG_SUMMARY_FIELDS member (the steps.jsonl
    ``dag`` block the scheduler attaches) must be backtick-documented
    in README's Pipeline DAG section, and the scheduler must build its
    per-node records from the tuple — the literal check asserts
    scheduler.py references `profiling.DAG_FIELDS` so the block cannot
    silently drift from the pinned schema."""
    fields = dag_fields() + dag_summary_fields()
    with open(README, encoding="utf-8") as f:
        documented = set(re.findall(r"`([a-z][a-z0-9_]*)`", f.read()))
    missing = sorted(set(fields) - documented)
    if missing:
        print("dag schema drift: DAG_FIELDS/DAG_SUMMARY_FIELDS "
              f"member(s) never documented in README: {missing}",
              file=sys.stderr)
        return 1
    sched = os.path.join(PKG, "pipeline", "scheduler.py")
    with open(sched, encoding="utf-8") as f:
        uses = "DAG_FIELDS" in f.read()
    if not uses:
        print("pipeline/scheduler.py no longer builds the dag block "
              "from profiling.DAG_FIELDS", file=sys.stderr)
        return 1
    print(f"pipeline dag: all {len(fields)} DAG_FIELDS + "
          "DAG_SUMMARY_FIELDS documented in README and pinned in "
          "pipeline/scheduler.py")
    return 0


def check_trace_docs() -> int:
    """Every TRACE_FIELDS member (the steps.jsonl ``trace`` block the
    span tracer attaches) must be backtick-documented in README's
    Observability section, and obs/trace.py must build the block from
    the tuple — the literal check asserts trace.py references
    `TRACE_FIELDS` so the block cannot silently drift from the pinned
    schema."""
    fields = trace_fields()
    with open(README, encoding="utf-8") as f:
        documented = set(re.findall(r"`([a-z][a-z0-9_]*)`", f.read()))
    missing = sorted(set(fields) - documented)
    if missing:
        print("trace schema drift: TRACE_FIELDS member(s) never "
              f"documented in README: {missing}", file=sys.stderr)
        return 1
    tracer = os.path.join(PKG, "obs", "trace.py")
    with open(tracer, encoding="utf-8") as f:
        uses = "TRACE_FIELDS" in f.read()
    if not uses:
        print("obs/trace.py no longer builds the trace block from "
              "profiling.TRACE_FIELDS", file=sys.stderr)
        return 1
    print(f"trace plane: all {len(fields)} TRACE_FIELDS documented in "
          "README and pinned in obs/trace.py")
    return 0


def check_job_docs() -> int:
    """Every JOB_FIELDS / BUILD_FIELDS member (the job record a closed
    `train.job` span leaves, and the steps.jsonl ``first_job`` block)
    must be backtick-documented in README's Observability section, and
    obs/trace.py must build the record from the tuples."""
    fields = job_fields()
    with open(README, encoding="utf-8") as f:
        documented = set(re.findall(r"`([a-z][a-z0-9_]*)`", f.read()))
    missing = sorted(set(fields) - documented)
    if missing:
        print("job record schema drift: JOB_FIELDS/BUILD_FIELDS "
              f"member(s) never documented in README: {missing}",
              file=sys.stderr)
        return 1
    with open(os.path.join(PKG, "obs", "trace.py"),
              encoding="utf-8") as f:
        text = f.read()
    for tup in ("JOB_FIELDS", "BUILD_FIELDS"):
        if tup not in text:
            print("obs/trace.py no longer builds the job record from "
                  f"profiling.{tup}", file=sys.stderr)
            return 1
    print(f"job records: all {len(fields)} JOB_FIELDS + BUILD_FIELDS "
          "documented in README and pinned in obs/trace.py")
    return 0


def check_health_docs() -> int:
    """Every METRIC_FIELDS member (the metrics.jsonl point schema) and
    HEALTH_FIELDS member (the SLO evaluator's record schema) must be
    backtick-documented in README's Model health section, and the
    emitting modules must build their records from the tuples — the
    literal checks assert obs/health/store.py references METRIC_FIELDS
    and obs/health/slo.py references HEALTH_FIELDS so neither record
    can silently drift from its pinned schema."""
    fields = metric_fields() + health_fields()
    with open(README, encoding="utf-8") as f:
        documented = set(re.findall(r"`([a-z][a-z0-9_]*)`", f.read()))
    missing = sorted(set(fields) - documented)
    if missing:
        print("health schema drift: METRIC_FIELDS/HEALTH_FIELDS "
              f"member(s) never documented in README: {missing}",
              file=sys.stderr)
        return 1
    for rel, tup in (("obs/health/store.py", "METRIC_FIELDS"),
                     ("obs/health/slo.py", "HEALTH_FIELDS")):
        path = os.path.join(PKG, *rel.split("/"))
        with open(path, encoding="utf-8") as f:
            if tup not in f.read():
                print(f"shifu_tpu/{rel} no longer builds its records "
                      f"from profiling.{tup}", file=sys.stderr)
                return 1
    print(f"health plane: all {len(fields)} METRIC_FIELDS + "
          "HEALTH_FIELDS documented in README and pinned in "
          "obs/health/store.py + obs/health/slo.py")
    return 0


def log_fields(path: str) -> set:
    out = set()
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            out |= set(rec.get("inputPipeline", {}))
    return out


def check_roofline_log(path: str) -> list:
    """Records carrying a ``roofline`` block must carry EXACTLY the
    ROOFLINE_FIELDS keys; returns the deviations (line no + diff)."""
    want = set(roofline_fields())
    bad = []
    with open(path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            roof = rec.get("roofline")
            if not isinstance(roof, dict):
                continue
            got = set(roof)
            if got != want:
                bad.append(f"line {lineno}: missing="
                           f"{sorted(want - got)} extra={sorted(got - want)}")
    return bad


def main(argv) -> int:
    doc, emit = documented_fields(), emitted_fields()
    missing = sorted(doc - emit)
    if missing:
        print("steps.jsonl schema drift: README documents stage fields "
              "the code never emits:", file=sys.stderr)
        for tok in missing:
            print(f"  {tok}", file=sys.stderr)
        print(f"emitted vocabulary: {sorted(emit)}", file=sys.stderr)
        return 1
    print(f"steps.jsonl schema: {len(doc)} documented stage fields, "
          f"all within the {len(emit)}-key emitted vocabulary")
    if check_roofline_docs():
        return 1
    if check_fleet_docs():
        return 1
    if check_dag_docs():
        return 1
    if check_trace_docs():
        return 1
    if check_job_docs():
        return 1
    if check_health_docs():
        return 1
    if argv:
        seen = log_fields(argv[0])
        absent = sorted(doc - seen)
        if absent:
            print(f"live log {argv[0]} never carried documented "
                  f"field(s): {absent}", file=sys.stderr)
            return 1
        print(f"live log {argv[0]}: all documented fields observed")
        bad = check_roofline_log(argv[0])
        if bad:
            print(f"live log {argv[0]}: roofline block(s) deviate from "
                  f"ROOFLINE_FIELDS: {bad}", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
