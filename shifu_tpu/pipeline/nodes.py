"""DAG node registry + builders over the processor steps.

`STEP_REGISTRY` is the single source of truth for the pipeline's
dependency structure: one entry per `step_guard` manifest name (family
entries like ``eval`` cover the per-instance ``eval.<name>`` guards),
plus the unguarded ``init`` root whose completion marker is
ColumnConfig.json itself. The `unregistered-dag-step` lint rule checks
both directions — every `step_guard` call site must name a registry
entry, and every manifest-bearing entry must be reachable from a call
site — so the registry cannot drift from the processors.

Node bodies are CLI subprocesses (``python -m shifu_tpu --dir <root>
<cmd>``): a step per process keeps abort scope, stage timers and retry
counters exactly as isolated as a sequential CLI run, so scheduling
concurrently cannot change what any step computes. Multi-model /
grid-search fan-outs train siblings in clone workspaces under
``tmp/dag_models/<name>`` that share the parent's normalized data (by
symlink) and its persistent XLA compile cache (PR 5) — the first
sibling to compile a program populates the cache for the rest.

Placement: fan-out siblings declare a device demand — an equal split
of the pool (`_sibling_demand`) — so the scheduler's slice allocator
leases them disjoint chips and they train simultaneously instead of
timesharing. The node body accepts the scheduler's ``lease_env``
keyword and merges it into the subprocess environment, which is the
entire placement hand-off: the child's `parallel.mesh` builds every
mesh over its slice.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import subprocess
import sys
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

from shifu_tpu.config.environment import knob_bool, knob_int
from shifu_tpu.pipeline.scheduler import Node

log = logging.getLogger("shifu_tpu")


class StepSpec(NamedTuple):
    """Registry entry for one pipeline step.

    ``manifest``: the step brackets itself with `step_guard` and owns
    ``tmp/manifests/<name>.json``. ``family``: the guard name is
    per-instance (``<name>.<instance>``, e.g. ``eval.Eval1``).
    ``device``: contends for a device-slice lease (timeshared mode:
    the SHIFU_TPU_DAG_WORKERS admission slots); host-only steps bypass
    both and never queue behind a trainer. ``devices`` is the step's
    device demand — None means "all" (the whole pool, exclusive);
    fan-out builders override it per sibling with an equal split so
    siblings run concurrently on disjoint slices."""

    deps: Tuple[str, ...]
    device: bool
    manifest: bool
    family: bool = False
    doc: str = ""
    devices: Optional[int] = None


# dependency structure of the processor pipeline, in terms of the
# step_guard manifest names (the README "Pipeline DAG" table renders
# exactly this registry)
STEP_REGISTRY: Dict[str, StepSpec] = {
    "init":      StepSpec((), False, False, False,
                          "raw header → ColumnConfig.json"),
    "stats":     StepSpec(("init",), True, True, False,
                          "column stats, binning, KS/IV"),
    "stats.seg": StepSpec(("stats",), True, True, True,
                          "one segment expression's stats partial"),
    "stats.segmerge": StepSpec(("stats.seg",), False, True, False,
                               "merge base + segment partials"),
    "norm":      StepSpec(("stats",), True, True, False,
                          "normalized + cleaned training data"),
    "varselect": StepSpec(("norm",), True, True, False,
                          "sensitivity-based feature selection"),
    "train":     StepSpec(("norm",), True, True, False,
                          "model training (NN/GBT/WDL/…)"),
    "posttrain": StepSpec(("train",), False, True, False,
                          "bin-avg scores + feature importance"),
    "eval":      StepSpec(("train",), True, True, True,
                          "per-eval-set scoring + metrics"),
    "export":    StepSpec(("train",), False, True, True,
                          "pmml/columnstats/encoder export"),
}


def _run_cli(root: str, cmd: Sequence[str], node: str,
             env_extra: Optional[Dict[str, str]] = None) -> None:
    """Run one pipeline step as a CLI subprocess; stdout/stderr land in
    ``tmp/dag_logs/<node>.log`` so concurrent steps don't interleave.
    Raises RuntimeError carrying the log tail on a non-zero exit."""
    log_dir = os.path.join(root, "tmp", "dag_logs")
    os.makedirs(log_dir, exist_ok=True)
    log_path = os.path.join(log_dir, f"{node.replace('/', '_')}.log")
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    argv = [sys.executable, "-m", "shifu_tpu", "--dir", root, *cmd]
    with open(log_path, "w") as lf:  # lint: disable=non-atomic-write -- live-tailed node log; must exist mid-run
        rc = subprocess.call(argv, stdout=lf, stderr=subprocess.STDOUT,
                             env=env)
    if rc != 0:
        try:
            with open(log_path, errors="replace") as lf:
                tail = "".join(lf.readlines()[-15:])
        except OSError:
            tail = "<log unavailable>"
        raise RuntimeError(
            f"DAG node {node}: `shifu {' '.join(cmd)}` exited {rc} "
            f"(log: {log_path})\n{tail}")


def _manifest_done(root: str, step: str) -> Callable[[], bool]:
    """Per-node RESUME test: the step's manifest matches the inputs a
    fresh run would fingerprint and its outputs exist (the same test
    `step_guard` applies, evaluated without loading the processor)."""
    def check() -> bool:
        from shifu_tpu.processor.base import (ProcessorContext,
                                              manifest_complete)
        return manifest_complete(
            ProcessorContext.load(root, need_columns=False), step)
    return check


def _column_config_done(root: str) -> Callable[[], bool]:
    def check() -> bool:
        from shifu_tpu.config.model_config import ModelConfig
        from shifu_tpu.config.path_finder import PathFinder
        mc = ModelConfig.load(root)
        return os.path.exists(PathFinder(mc, root=root).column_config_path())
    return check


def _resume_enabled(resume: Optional[bool]) -> bool:
    return knob_bool("SHIFU_TPU_RESUME") if resume is None else bool(resume)


def _merge_env(base: Optional[Dict[str, str]],
               lease: Optional[Dict[str, str]]) -> Optional[Dict[str, str]]:
    if not lease:
        return base
    out = dict(base or {})
    out.update(lease)
    return out


def _sibling_demand(n_siblings: int) -> Optional[int]:
    """Per-sibling device demand for a fan-out: an equal split of the
    pool, at least one chip each. None (= demand the whole pool) when
    there is a single sibling or the inventory is unknown or single-
    device — the scheduler then serializes or timeshares exactly as
    before. SHIFU_TPU_DAG_DEVICES avoids the runtime probe (preferred
    on hardware)."""
    if n_siblings <= 1:
        return None
    total = knob_int("SHIFU_TPU_DAG_DEVICES")
    if not total:
        try:
            from shifu_tpu.parallel import mesh as mesh_mod
            total = mesh_mod.device_inventory()
        except Exception:  # noqa: BLE001 — no inventory → no demand
            return None
    if total and int(total) > 1:
        return max(1, int(total) // n_siblings)
    return None


def _node(root: str, step: str, cmd: Sequence[str], deps: Tuple[str, ...],
          resume: bool, name: Optional[str] = None,
          env_extra: Optional[Dict[str, str]] = None,
          devices: Optional[int] = None) -> Node:
    # longest registered dotted prefix: "eval.Eval1" → "eval",
    # "stats.seg.3" → "stats.seg" (family entries keep their own spec)
    key = step
    while key not in STEP_REGISTRY and "." in key:
        key = key.rsplit(".", 1)[0]
    spec = STEP_REGISTRY[key]
    name = name or step
    if not resume:
        done = None
    elif step == "init":
        done = _column_config_done(root)
    else:
        done = _manifest_done(root, step)
    return Node(name=name,
                fn=lambda lease_env=None: _run_cli(
                    root, cmd, name, _merge_env(env_extra, lease_env)),
                deps=deps, device=spec.device, done_check=done,
                devices=devices if devices is not None else spec.devices)


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def _segment_count(root: str) -> int:
    try:
        from shifu_tpu.config.model_config import ModelConfig
        from shifu_tpu.data import segment
        return len(segment.segment_expressions(ModelConfig.load(root)))
    except Exception:  # noqa: BLE001 - no config yet → no seg fan-out
        return 0


def _stats_nodes(root: str, res: bool) -> Tuple[List[Node], str]:
    """Stats as DAG nodes. Without segment expressions: the single
    inline node. With K expressions: base-only stats, then one
    ``stats.seg.<k>`` SIBLING per expression (each re-reads the frame
    and fills only its block into a tmp partial), then a host-only
    ``stats.segmerge`` that stitches base + partials into
    ColumnConfig.json — identical content to the inline expansion,
    with the per-segment work schedulable concurrently. Returns the
    nodes and the name downstream steps must depend on."""
    n_seg = _segment_count(root)
    if not n_seg:
        return [_node(root, "stats", ["stats"], ("init",), res)], "stats"
    nodes = [_node(root, "stats", ["stats", "-base-only"], ("init",), res)]
    for k in range(1, n_seg + 1):
        nodes.append(_node(root, f"stats.seg.{k}", ["stats", "-seg",
                                                    str(k)],
                           ("stats",), res))
    nodes.append(_node(root, "stats.segmerge", ["stats", "-seg-merge"],
                       tuple(f"stats.seg.{k}"
                             for k in range(1, n_seg + 1)), res))
    return nodes, "stats.segmerge"


def pipeline_nodes(root: str, eval_sets: Sequence[str] = (),
                   algorithms: Sequence[str] = (),
                   posttrain: bool = False,
                   resume: Optional[bool] = None) -> List[Node]:
    """The standard pipeline as a DAG: init → stats → norm → train,
    then every eval set as a sibling node. With ``algorithms`` (e.g.
    ``["NN", "GBT", "WDL"]``) training fans out: the first algorithm
    trains in the model-set workspace, the rest in clone workspaces
    sharing the parent's normalized data (and, like every command, the
    one compile cache `profiling.enable_compile_cache` resolves)."""
    res = _resume_enabled(resume)
    stats_nodes, stats_dep = _stats_nodes(root, res)
    nodes = [
        _node(root, "init", ["init"], (), res),
        *stats_nodes,
        _node(root, "norm", ["norm"], (stats_dep,), res),
    ]
    algorithms = list(algorithms)
    if len(algorithms) > 1:
        share = _sibling_demand(len(algorithms))
        primary, train_name = algorithms[0], f"train.{algorithms[0]}"
        nodes.append(_node(root, "train", ["train"], ("norm",), res,
                           name=train_name, devices=share))
        for alg in algorithms[1:]:
            nodes.append(variant_node(root, f"train.{alg}", ("norm",),
                                      algorithm=alg, resume=res,
                                      devices=share))
    else:
        train_name = "train"
        nodes.append(_node(root, "train", ["train"], ("norm",), res))
    ev_share = _sibling_demand(len(eval_sets))
    for ev in eval_sets:
        nodes.append(_node(root, f"eval.{ev}", ["eval", "-run", ev],
                           (train_name,), res, devices=ev_share))
    if posttrain:
        nodes.append(_node(root, "posttrain", ["posttrain"],
                           (train_name,), res))
    return nodes


def grid_nodes(root: str, grid_params: Sequence[Dict],
               resume: Optional[bool] = None) -> List[Node]:
    """Grid-search/bagging fan-out: one sibling ``train.grid<i>`` node
    per concrete parameter dict (see `train.grid_search.expand`), each
    in its own clone workspace off the shared norm output."""
    res = _resume_enabled(resume)
    stats_nodes, stats_dep = _stats_nodes(root, res)
    nodes = [
        _node(root, "init", ["init"], (), res),
        *stats_nodes,
        _node(root, "norm", ["norm"], (stats_dep,), res),
    ]
    share = _sibling_demand(len(grid_params))
    for i, params in enumerate(grid_params):
        nodes.append(variant_node(root, f"train.grid{i}", ("norm",),
                                  params=params, resume=res,
                                  devices=share))
    return nodes


def variant_node(root: str, name: str, deps: Tuple[str, ...],
                 algorithm: Optional[str] = None,
                 params: Optional[Dict] = None,
                 resume: bool = False,
                 env_extra: Optional[Dict[str, str]] = None,
                 devices: Optional[int] = None) -> Node:
    """A sibling trainer in a clone workspace under
    ``tmp/dag_models/<name>``: same data, same ColumnConfig, different
    algorithm and/or train params. The clone is prepared lazily inside
    the node body — after the parent's norm finished. ``devices`` declares the
    sibling's slice demand (fan-out builders pass the equal split)."""
    clone = variant_dir(root, name)

    def fn(lease_env: Optional[Dict[str, str]] = None) -> None:
        prepare_variant(root, clone, algorithm=algorithm, params=params)
        _run_cli(clone, ["train"], name, _merge_env(env_extra, lease_env))

    done = _manifest_done(clone, "train") if resume else None
    return Node(name=name, fn=fn, deps=deps, device=True,
                done_check=done, devices=devices)


def variant_dir(root: str, name: str) -> str:
    return os.path.join(root, "tmp", "dag_models",
                        name.replace("/", "_"))


def _absolutize(obj, base: str):
    """Every relative local path-valued field (``*Path``/``*File``) in
    a raw ModelConfig dict, resolved against the parent model set — a
    clone lives under tmp/dag_models/ and must keep reading the
    parent's files."""
    from shifu_tpu.data.fs import has_scheme
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            if isinstance(v, str) and v and \
                    (k.endswith("Path") or k.endswith("File")) and \
                    not has_scheme(v) and not os.path.isabs(v):
                out[k] = os.path.join(base, v)
            else:
                out[k] = _absolutize(v, base)
        return out
    if isinstance(obj, list):
        return [_absolutize(v, base) for v in obj]
    return obj


def prepare_variant(root: str, clone: str, algorithm: Optional[str] = None,
                    params: Optional[Dict] = None) -> str:
    """Materialize a clone workspace: parent's ModelConfig with the
    algorithm/params switched (paths absolutized), parent's
    post-stats ColumnConfig copied, normalized + cleaned data shared
    by symlink so the fan-out never re-reads or re-normalizes."""
    os.makedirs(os.path.join(clone, "tmp"), exist_ok=True)
    with open(os.path.join(root, "ModelConfig.json")) as f:
        raw = json.load(f)
    raw = _absolutize(raw, root)
    if algorithm:
        raw["train"]["algorithm"] = algorithm
    if params:
        raw["train"]["params"] = params
    raw.setdefault("basic", {})["name"] = \
        f"{raw.get('basic', {}).get('name', 'model')}:{os.path.basename(clone)}"
    from shifu_tpu.resilience import atomic_write
    with atomic_write(os.path.join(clone, "ModelConfig.json")) as f:
        json.dump(raw, f, indent=2)
    cc_src = os.path.join(root, "ColumnConfig.json")
    if os.path.exists(cc_src):
        shutil.copyfile(cc_src, os.path.join(clone, "ColumnConfig.json"))
    for d in ("NormalizedData", "CleanedData"):
        src = os.path.join(root, "tmp", d)
        dst = os.path.join(clone, "tmp", d)
        if os.path.isdir(src) and not os.path.lexists(dst):
            os.symlink(src, dst)
    return clone
