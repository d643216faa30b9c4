"""`shifu varsel` — variable selection.

Replaces `core/processor/VarSelectModelProcessor.java:124-318`:

- statistical filters (filterBy KS / IV / MIX / PARETO) rank candidate
  columns by the stats phase's metrics and keep the top filterNum;
- SE / ST sensitivity runs the reference's "wipe one column, re-score"
  MapReduce job (`core/varselect/VarSelectMapper.java:54-272`, cached
  forward via CacheBasicFloatNetwork) in `processor/sensitivity.py`:
  the quick train, then one program that takes each column's term out
  of the cached first-layer sums and scores every row again;
- missingRateThreshold and forceSelect/forceRemove are honored like
  `VarSelectModelProcessor.candidates` preprocessing;
- recursive mode (-r) re-runs SE on the surviving set — the reference's
  recursive SE loop and its ITSA variant
  (`core/varselect/itsa/IteSAMaster.java`) collapse into this single
  re-ranked loop;
- filterBy=V runs the genetic/voted wrapper (`core/dvarsel/*`) as one
  vmapped population training (see _filter_by_voted_wrapper);
- filterBy=FI ranks by tree feature importance
  (selectByFeatureImportance); filterBy=SC is the SE variant with a
  different output sort in the reference.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tpu.config.column_config import ColumnConfig
from shifu_tpu.data.pipeline import host_fetch
from shifu_tpu.config.inspector import ModelStep
from shifu_tpu.config.model_config import ModelConfig
from shifu_tpu.models import nn as nn_mod
from shifu_tpu.processor import norm as norm_proc
from shifu_tpu.processor.base import ProcessorContext, step_guard
from shifu_tpu.processor.sensitivity import sensitivity_select

log = logging.getLogger("shifu_tpu")


def analysis_frame(ctx):
    from shifu_tpu.processor.chunking import analysis_frame as af
    return af(ctx, log=log)




def run(ctx: ProcessorContext, recursive: int = 0, seed: int = 12306,
        reset: bool = False, list_only: bool = False,
        select_file: Optional[str] = None) -> int:
    t0 = time.time()
    mc = ctx.model_config
    ctx.validate(ModelStep.VARSELECT)
    ctx.require_columns()
    vs = mc.varSelect

    if reset:
        # `shifu varsel -reset` — all selections back to false
        # (VarSelectModelProcessor.resetAllFinalSelect:479)
        for cc in ctx.column_configs:
            cc.finalSelect = False
        ctx.save_column_configs()
        log.info("varsel -reset: all %d columns finalSelect=false",
                 len(ctx.column_configs))
        return 0
    if list_only:
        # `shifu varsel -list` — print the current selection
        # (VarSelectModelProcessor getIsToList branch)
        sel = [c.columnName for c in ctx.column_configs if c.finalSelect]
        log.info("varsel -list: %d variables selected", len(sel))
        for name in sel:
            print(name)
        return 0
    if select_file:
        # `shifu varsel -f <file>` — reset, then select exactly the
        # names in the file (VarSelectModelProcessor:202-220)
        names = set(mc.column_names_from_file(select_file))
        if not names:
            # a typo'd path or empty file must FAIL the step — scripts
            # chaining `varsel -f && train` would otherwise train on a
            # stale selection with rc 0
            raise ValueError(
                f"varsel -f: {select_file!r} does not exist (relative "
                "paths resolve against the model-set dir) or names no "
                "variables")
        n_sel = 0
        for cc in ctx.column_configs:
            cc.finalSelect = cc.columnName in names
            n_sel += int(cc.finalSelect)
        if n_sel == 0:
            # names that match NO column (case typo, renamed schema)
            # must not silently deselect everything with rc 0
            raise ValueError(
                f"varsel -f: none of the {len(names)} name(s) in "
                f"{select_file!r} match a column; selection unchanged")
        ctx.save_column_configs()
        log.info("varsel -f: %d variables selected based on %s", n_sel,
                 select_file)
        return 0

    # manifest bracketing covers only the SELECTION path — the -reset/
    # -list/-f modes above are explicit user edits, never skippable
    with step_guard(ctx, "varselect",
                    outputs=[ctx.path_finder.column_config_path()]) as go:
        if not go:
            return 0
        candidates = _apply_pre_filters(ctx)
        if not vs.filterEnable:
            for cc in candidates:
                cc.finalSelect = True
            ctx.save_column_configs()
            return 0

        by = vs.filterBy.upper()
        if by in ("KS", "IV", "MIX", "PARETO"):
            _filter_by_stats(ctx, candidates, by)
        elif by in ("SE", "ST", "SC"):
            # SC differs from SE only in output sort order in the
            # reference (VarSelectModelProcessor.java:302-312); ranking
            # here is already by delta
            _filter_by_sensitivity(ctx, candidates,
                                   "ST" if by == "ST" else "SE", seed)
            for _ in range(recursive):
                survivors = [c for c in candidates if c.finalSelect]
                _filter_by_sensitivity(ctx, survivors, by, seed)
        elif by == "V":
            _filter_by_voted_wrapper(ctx, candidates, seed)
        elif by == "FI":
            _filter_by_feature_importance(ctx, candidates, seed)
        else:
            raise ValueError(
                f"varSelect#filterBy {vs.filterBy!r} not supported")

        n_sel = sum(1 for c in ctx.column_configs if c.finalSelect)
        ctx.save_column_configs()
        log.info("varsel[%s]: %d/%d columns selected in %.2fs", by, n_sel,
                 len(candidates), time.time() - t0)
    return 0


def _apply_pre_filters(ctx: ProcessorContext) -> List[ColumnConfig]:
    """forceSelect / forceRemove / missingRateThreshold preprocessing
    (`VarSelectModelProcessor` candidate assembly)."""
    mc = ctx.model_config
    vs = mc.varSelect
    force_sel = {n.split("::")[-1].strip() for n in
                 mc.column_names_from_file(vs.forceSelectColumnNameFile)}
    force_rem = {n.split("::")[-1].strip() for n in
                 mc.column_names_from_file(vs.forceRemoveColumnNameFile)}
    candidates = []
    for cc in ctx.column_configs:
        cc.finalSelect = False
        if not cc.is_candidate:
            continue
        if cc.columnName in force_rem:
            continue
        if vs.forceEnable and cc.columnName in force_sel:
            cc.finalSelect = True
            continue
        miss = cc.columnStats.missingPercentage or 0.0
        if miss > vs.missingRateThreshold:
            continue
        candidates.append(cc)
    return candidates


def _metric_of(cc: ColumnConfig, by: str) -> float:
    ks = cc.columnStats.ks or 0.0
    iv = cc.columnStats.iv or 0.0
    if by == "KS":
        return ks
    if by == "IV":
        return iv
    return ks + iv  # MIX/PARETO combined ranking


def _filter_by_stats(ctx: ProcessorContext, candidates: List[ColumnConfig],
                     by: str) -> None:
    vs = ctx.model_config.varSelect
    ranked = sorted(candidates, key=lambda c: -_metric_of(c, by))
    thr_iv = vs.minIvThreshold
    thr_ks = vs.minKsThreshold
    for i, cc in enumerate(ranked):
        ok = i < vs.filterNum
        if thr_iv is not None and (cc.columnStats.iv or 0.0) < thr_iv:
            ok = False
        if thr_ks is not None and (cc.columnStats.ks or 0.0) < thr_ks:
            ok = False
        cc.finalSelect = cc.finalSelect or ok


def _filter_by_sensitivity(ctx: ProcessorContext,
                           candidates: List[ColumnConfig], by: str,
                           seed: int) -> None:
    """SE: train a quick NN on all candidates, ablate each column, rank
    by score MSE delta (`processor/sensitivity.sensitivity_select`). ST
    ranks by relative delta (delta / score var), approximating the
    reference's sensitivity-type toggle. Here: the candidates' loading
    and normalizing, the dense columns' deltas summed by source column,
    the `se.0` file and the selection."""
    mc = ctx.model_config
    vs = mc.varSelect
    for cc in candidates:
        cc.finalSelect = True  # train on the full candidate set
    ctx.save_column_configs()

    cols = [c for c in candidates]
    dset = norm_proc.load_dataset_for_columns(mc, ctx.column_configs, cols,
                                              df=analysis_frame(ctx))
    # *_INDEX families route categoricals to the embedding-index block,
    # which the sensitivity MLP can't see — normalize with the dense
    # equivalent family so every candidate lands in the dense matrix
    import copy as _copy
    from shifu_tpu.config.model_config import NormType
    sens_mc = mc
    if mc.normalize.normType.is_index:
        dense_equiv = {
            NormType.WOE_INDEX: NormType.WOE,
            NormType.WOE_APPEND_INDEX: NormType.WOE,
            NormType.WOE_ZSCALE_INDEX: NormType.WOE_ZSCALE,
            NormType.WOE_ZSCALE_APPEND_INDEX: NormType.WOE_ZSCALE,
        }.get(mc.normalize.normType, NormType.ZSCALE)
        sens_mc = _copy.copy(mc)
        sens_mc.normalize = _copy.copy(mc.normalize)
        sens_mc.normalize.normType = dense_equiv
    result = norm_proc.normalize_columns(sens_mc, cols, dset)
    sel = sensitivity_select(mc.train, result.dense.astype(np.float32),
                             dset.tags, dset.weights, seed, vs.filterNum, by)

    # map dense output columns back to source columns (onehot/index
    # families expand; sum deltas per source column)
    names = {c.columnName for c in cols}
    per_col: Dict[str, float] = {}
    for name, d in zip(result.dense_names, sel.deltas):
        src = name if name in names else name.rsplit("_", 1)[0]
        per_col[src] = per_col.get(src, 0.0) + float(d)

    from shifu_tpu.resilience import atomic_write
    se_path = ctx.path_finder.se_path(0)
    ctx.path_finder.ensure(se_path)
    ranked = sorted(per_col.items(), key=lambda kv: -kv[1])
    with atomic_write(se_path) as f:
        samp = getattr(ctx, "_analysis_frame", None)
        if samp is not None:
            # the one analysis step still allowed to sample (ablation
            # deltas are stable on a capped sample; correlation/PSI/
            # posttrain stream exactly) — mark it so the ranking is
            # never mistaken for a full-data pass
            f.write(f"# sensitivity computed on a {len(samp)}-row "
                    "uniform sample of a >RAM dataset "
                    "(SHIFU_TPU_ANALYSIS_MAX_ROWS)\n")
        for name, d in ranked:
            f.write(f"{name}\t{d:.8g}\n")

    keep = {name for name, _ in ranked[:vs.filterNum]}
    for cc in candidates:
        cc.finalSelect = cc.columnName in keep


def _dense_candidate_matrix(ctx: ProcessorContext,
                            candidates: List[ColumnConfig]):
    """Normalized dense matrix over ALL candidates (index families
    remapped to their dense equivalents), plus per-source-column dense
    slices — shared by the wrapper and FI filters."""
    mc = ctx.model_config
    for cc in candidates:
        cc.finalSelect = True
    dset = norm_proc.load_dataset_for_columns(mc, ctx.column_configs,
                                              candidates,
                                              df=analysis_frame(ctx))
    import copy as _copy
    from shifu_tpu.config.model_config import NormType
    sens_mc = mc
    if mc.normalize.normType.is_index:
        sens_mc = _copy.copy(mc)
        sens_mc.normalize = _copy.copy(mc.normalize)
        sens_mc.normalize.normType = NormType.ZSCALE
    result = norm_proc.normalize_columns(sens_mc, candidates, dset)
    names = {c.columnName for c in candidates}
    src_of = [n if n in names else n.rsplit("_", 1)[0]
              for n in result.dense_names]
    return result.dense.astype(np.float32), src_of, dset


def _filter_by_voted_wrapper(ctx: ProcessorContext,
                             candidates: List[ColumnConfig],
                             seed: int) -> None:
    """filterBy=V — the genetic/voted wrapper (`core/dvarsel/*`):
    a population of candidate feature subsets ("seeds",
    `wrapper/CandidateGenerator.java`), each validated by training a
    small net on just those features (`ValidationConductor`), evolved
    for several rounds, final selection by vote frequency among the
    fittest seeds.

    TPU formulation: the per-worker candidate trainings become ONE
    vmapped program over the population axis — every seed's masked MLP
    trains simultaneously; evolution (selection / crossover / mutation)
    stays on host between generations.

    Population knobs come from varSelect#params
    (population_live_size / population_multiply_cnt /
    expect_variable_cnt, CandidateGenerator.java:36-63), defaulting to
    a 20-seed, 5-generation run targeting wrapperNum variables.
    """
    import jax.random as jrandom

    mc = ctx.model_config
    vs = mc.varSelect
    params = vs.params or {}
    x, src_of, dset = _dense_candidate_matrix(ctx, candidates)
    y, w = dset.tags, dset.weights
    n_dense = x.shape[1]
    srcs = sorted({s for s in src_of})
    src_ix = {s: i for i, s in enumerate(srcs)}
    n_src = len(srcs)
    # dense-column → source-column expansion matrix (onehot families
    # expand one source into several dense columns)
    expand = np.zeros((n_src, n_dense), np.float32)
    for j, s in enumerate(src_of):
        expand[src_ix[s], j] = 1.0

    expect = int(params.get("expect_variable_cnt", 0) or vs.wrapperNum
                 or max(n_src // 2, 1))
    expect = min(expect, n_src)
    pop_size = int(params.get("population_live_size", 20) or 20)
    generations = int(params.get("population_multiply_cnt", 5) or 5)
    epochs = max(int(mc.train.numTrainEpochs) // 4, 10)

    rng = np.random.default_rng(seed)
    pop = np.zeros((pop_size, n_src), np.float32)
    for i in range(pop_size):
        pop[i, rng.choice(n_src, expect, replace=False)] = 1.0

    tr_mask = rng.random(len(y)) >= 0.2
    xt, yt, wt = x[tr_mask], y[tr_mask], w[tr_mask]
    xv, yv, wv = x[~tr_mask], y[~tr_mask], w[~tr_mask]

    spec = nn_mod.MLPSpec(input_dim=n_dense, hidden_dims=(16,),
                          activations=("tanh",), loss="log")
    import optax
    optimizer = optax.adam(0.05)

    @jax.jit
    def fitness(masks_src):
        """(P, n_src) source masks → (P,) validation error; every seed
        trains its own masked net in one vmapped scan."""
        masks = masks_src @ jnp.asarray(expand)  # (P, n_dense)

        def one(mask, key):
            p0 = nn_mod.init_params(spec, key)
            o0 = optimizer.init(p0)

            def step(carry, _):
                p, o = carry
                g = jax.grad(lambda q: nn_mod.loss_fn(
                    spec, q, jnp.asarray(xt) * mask[None, :],
                    jnp.asarray(yt), jnp.asarray(wt)))(p)
                up, o2 = optimizer.update(g, o, p)
                return (optax.apply_updates(p, up), o2), 0.0

            (p, _), _ = jax.lax.scan(step, (p0, o0), jnp.arange(epochs))
            return nn_mod.mse(spec, p, jnp.asarray(xv) * mask[None, :],
                              jnp.asarray(yv), jnp.asarray(wv))

        keys = jrandom.split(jrandom.PRNGKey(seed), masks.shape[0])
        return jax.vmap(one)(masks, keys)

    for gen in range(generations):
        # the GA is host-driven: selection/crossover need this
        # generation's fitness on host before the next can dispatch
        errs = host_fetch(fitness(jnp.asarray(pop)))
        order = np.argsort(errs)
        n_keep = max(pop_size // 2, 2)
        survivors = pop[order[:n_keep]]
        children = []
        while len(children) < pop_size - n_keep:
            a, b = survivors[rng.integers(n_keep)], \
                survivors[rng.integers(n_keep)]
            union = np.flatnonzero((a + b) > 0)
            pick = rng.choice(union, min(expect, len(union)), replace=False)
            child = np.zeros(n_src, np.float32)
            child[pick] = 1.0
            # mutation: swap one selected column for an unselected one
            if rng.random() < 0.3 and child.sum() > 0 and \
                    (child == 0).sum() > 0:
                off = rng.choice(np.flatnonzero(child > 0))
                on = rng.choice(np.flatnonzero(child == 0))
                child[off], child[on] = 0.0, 1.0
            children.append(child)
        pop = np.concatenate([survivors, np.stack(children)], axis=0)
        log.info("voted wrapper gen %d/%d: best val err %.6f", gen + 1,
                 generations, float(errs[order[0]]))

    # final vote among the fittest half (VarSelMaster vote count)
    errs = np.asarray(fitness(jnp.asarray(pop)))
    order = np.argsort(errs)
    votes = pop[order[:max(pop_size // 2, 2)]].sum(axis=0)
    top = np.argsort(-votes)[:expect]
    keep = {srcs[i] for i in top}
    for cc in candidates:
        cc.finalSelect = cc.columnName in keep


def _filter_by_feature_importance(ctx: ProcessorContext,
                                  candidates: List[ColumnConfig],
                                  seed: int) -> None:
    """filterBy=FI — rank by gain-weighted tree feature importance
    (VarSelectModelProcessor.selectByFeatureImportance:422-429; only
    valid for GBT/RF). With -Dshifu.varsel.reuse.model=true, existing
    trained models are ranked as-is; otherwise a fresh all-candidate
    tree model is trained INTO the model set first — the same
    model-overwriting behavior as the reference's FI path."""
    mc = ctx.model_config
    vs = mc.varSelect
    if not mc.train.algorithm.is_tree:
        raise ValueError("filterBy=FI only works with GBT/RF "
                         "(train#algorithm)")
    if vs.filterNum <= 0:
        raise ValueError("filterBy=FI needs a positive varSelect#filterNum")
    from shifu_tpu.eval.scorer import Scorer
    reuse = os.environ.get("shifu.varsel.reuse.model", "").lower() == "true"
    if not (reuse and _has_tree_models(ctx)):
        for cc in candidates:
            cc.finalSelect = True
        ctx.save_column_configs()
        from shifu_tpu.processor import norm as norm_p
        from shifu_tpu.processor import train_tree
        norm_p.run(ctx)
        train_tree.run_tree(ctx, seed)

    scorer = Scorer.from_dir(ctx.path_finder.models_path())
    kind, meta, params = scorer.models[0]
    names = meta["denseNames"] + meta["indexNames"]
    feats = np.asarray(params["trees"]["feature"]).ravel()
    if "gain" in params["trees"]:
        gains = np.asarray(params["trees"]["gain"], np.float64).ravel()
    else:  # models trained before gain tracking: split counts
        gains = np.ones_like(feats, np.float64)
    fi = np.zeros(len(names))
    valid = feats >= 0
    np.add.at(fi, feats[valid].astype(int), np.maximum(gains[valid], 0.0))
    ranked = sorted(zip(names, fi), key=lambda kv: -kv[1])
    keep = {n for n, _ in ranked[:vs.filterNum]}
    for cc in candidates:
        cc.finalSelect = cc.columnName in keep


def _has_tree_models(ctx: ProcessorContext) -> bool:
    from shifu_tpu.models.spec import list_models
    return bool(list_models(ctx.path_finder.models_path()))
