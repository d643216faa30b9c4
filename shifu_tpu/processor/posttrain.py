"""`shifu posttrain` — bin-average scores + feature importance.

Replaces `core/processor/PostTrainModelProcessor.java` +
`core/posttrain/{PostTrainMapper,FeatureImportanceMapper}` MR jobs:
score the training data with the trained ensemble, average the score
per (column, bin) → `columnBinning.binAvgScore` write-back, and rank
features (tree models: split-gain usage counts; NN/LR: SE ablation
deltas reused from varselect's kernel).

Bin score sums/counts and squared ablation deltas are pure sums, so a
>RAM dataset streams chunk-by-chunk and merges exactly — matching the
reference's full-data PostTrainMapper semantics with no sampling.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tpu.eval.scorer import Scorer
from shifu_tpu.ops import stats as stats_ops
from shifu_tpu.processor import norm as norm_proc
from shifu_tpu.processor.base import ProcessorContext, step_guard

log = logging.getLogger("shifu_tpu")


def run(ctx: ProcessorContext) -> int:
    ctx.require_columns()
    out = os.path.join(ctx.path_finder.root, "featureimportance.csv")
    with step_guard(ctx, "posttrain", outputs=[out]) as go:
        if not go:
            return 0
        return _run(ctx, out)


def _run(ctx: ProcessorContext, out: str) -> int:
    t0 = time.time()
    mc = ctx.model_config
    cols = norm_proc.selected_candidates(ctx.column_configs)
    from shifu_tpu.processor.chunking import analysis_chunk_rows
    chunk_rows = analysis_chunk_rows(ctx)
    if chunk_rows:
        log.info("posttrain: dataset exceeds the resident threshold — "
                 "exact streaming accumulation in %d-row chunks",
                 chunk_rows)
        from shifu_tpu.data.pipeline import prefetch
        from shifu_tpu.data.reader import iter_raw_table
        frames = prefetch(iter_raw_table(mc, chunk_rows=chunk_rows))
    else:
        frames = [None]      # one resident read through the same path

    scorer = Scorer.from_dir(ctx.path_finder.models_path())
    cc_by_num = {c.columnNum: c for c in ctx.column_configs}
    num_tbl = None
    num_ordered = None
    # (col_num → (score sums per bin, counts per bin)) — exact merges
    bin_sums: Dict[int, np.ndarray] = {}
    bin_cnts: Dict[int, np.ndarray] = {}
    fi = _ImportanceAccumulator(scorer)

    for df in frames:
        dset = norm_proc.load_dataset_for_columns(mc, ctx.column_configs,
                                                  cols, df=df)
        result = norm_proc.normalize_columns(mc, cols, dset)
        if dset.cat_codes.shape[1]:
            vlen = np.asarray([len(v) for v in dset.vocabs], np.int32)
            raw_codes = np.where(dset.cat_codes < 0, vlen[None, :],
                                 dset.cat_codes).astype(np.int32)
        else:
            raw_codes = dset.cat_codes
        scores = scorer.score(result.dense,
                              result.index if result.index.size else None,
                              raw_dense=dset.numeric, raw_codes=raw_codes)
        final = scores["final"]

        if dset.numeric.shape[1]:
            if num_tbl is None:
                from shifu_tpu.ops.normalize import build_numeric_table
                num_by = {c.columnNum: c for c in cols if c.is_numerical}
                num_ordered = [num_by[int(n)] for n in dset.num_column_nums
                               if int(n) in num_by]
                num_tbl = build_numeric_table(num_ordered, mc.stats.maxNumBin)
            bi = np.asarray(stats_ops.bin_index_numeric(
                jnp.asarray(dset.numeric), jnp.asarray(num_tbl.cuts)))
            for j, cn in enumerate(dset.num_column_nums):
                cc = cc_by_num[int(cn)]
                k = cc.columnBinning.length or 1
                idx = np.minimum(bi[:, j], k)
                s = np.bincount(idx, weights=final, minlength=k + 1)
                c = np.bincount(idx, minlength=k + 1)
                bin_sums[int(cn)] = bin_sums.get(int(cn), 0) + s
                bin_cnts[int(cn)] = bin_cnts.get(int(cn), 0) + c
        if dset.cat_codes.shape[1]:
            for j, cn in enumerate(dset.cat_column_nums):
                cc = cc_by_num[int(cn)]
                k = len(cc.columnBinning.binCategory or [])
                idx = np.minimum(raw_codes[:, j], k)
                s = np.bincount(idx, weights=final, minlength=k + 1)
                c = np.bincount(idx, minlength=k + 1)
                bin_sums[int(cn)] = bin_sums.get(int(cn), 0) + s
                bin_cnts[int(cn)] = bin_cnts.get(int(cn), 0) + c
        fi.add_chunk(result, dset)

    for cn, sums in bin_sums.items():
        cnts = bin_cnts[cn]
        cc_by_num[cn].columnBinning.binAvgScore = [
            float(s / c) if c > 0 else 0.0 for s, c in zip(sums, cnts)]

    importance = fi.finalize()
    from shifu_tpu.resilience import atomic_write
    with atomic_write(out) as f:
        f.write("column,importance\n")
        for name, v in sorted(importance.items(), key=lambda kv: -kv[1]):
            f.write(f"{name},{v:.8g}\n")

    ctx.save_column_configs()
    log.info("posttrain: binAvgScore + feature importance (%d cols) in %.2fs",
             len(importance), time.time() - t0)
    return 0


class _ImportanceAccumulator:
    """Tree models: gain-weighted split counts
    (`CommonUtils.computeTreeModelFeatureImportance`) — no data needed.
    Dense models: SE ablation squared-delta sums, accumulated per chunk
    and divided by the total row count at the end — identical to the
    resident mean."""

    def __init__(self, scorer: Scorer):
        self.kind, self.meta, self.params = scorer.models[0]
        self.sums: Dict[str, float] = {}
        self.n = 0
        self._spec = self._jparams = None
        if self.kind in ("nn", "lr"):
            from shifu_tpu.models import nn as nn_mod
            sd = dict(self.meta["spec"])
            sd["hidden_dims"] = tuple(sd.get("hidden_dims", ()))
            sd["activations"] = tuple(sd.get("activations", ()))
            self._spec = nn_mod.MLPSpec(**sd)
            self._jparams = jax.tree.map(jnp.asarray, self.params)

    def add_chunk(self, result, dset) -> None:
        if self.kind in ("gbt", "rf"):
            return
        if self.kind in ("nn", "lr"):
            from shifu_tpu.processor import sensitivity
            rows, cols = result.dense.shape
            deltas, _ = sensitivity.shifu_ablate(
                self._spec, self._jparams, jnp.asarray(result.dense), rows,
                sensitivity.ablate_blocks(
                    rows, cols, max(self._spec.layer_dims[1:])))
            # the chunk's mean times its rows: per-column SUMS of squared
            # deltas, mergeable
            for name, d in zip(result.dense_names, np.asarray(deltas)):
                self.sums[name] = self.sums.get(name, 0.0) + float(d) * rows
            self.n += rows
            return
        # wdl/mtl: host-loop column ablation through the generic
        # predictor (dense cols zeroed; index cols set to missing slot)
        from shifu_tpu.eval.scorer import score_matrix
        dense = result.dense
        index = result.index if result.index.size else None
        base = score_matrix(self.kind, self.meta, self.params, dense, index)
        for j, name in enumerate(result.dense_names):
            wiped = dense.copy()
            wiped[:, j] = 0.0
            s = score_matrix(self.kind, self.meta, self.params, wiped, index)
            self.sums[name] = self.sums.get(name, 0.0) \
                + float(np.sum((s - base) ** 2))
        if index is not None:
            vocab_sizes = self.meta.get("indexVocabSizes") or \
                [int(index[:, j].max()) + 1 for j in range(index.shape[1])]
            for j, name in enumerate(result.index_names):
                wiped = index.copy()
                wiped[:, j] = vocab_sizes[j] - 1  # missing slot
                s = score_matrix(self.kind, self.meta, self.params,
                                 dense, wiped)
                self.sums[name] = self.sums.get(name, 0.0) \
                    + float(np.sum((s - base) ** 2))
        self.n += dense.shape[0]

    def finalize(self) -> Dict[str, float]:
        if self.kind in ("gbt", "rf"):
            names = self.meta["denseNames"] + self.meta["indexNames"]
            feats = np.asarray(self.params["trees"]["feature"]).ravel()
            counts = np.bincount(feats[feats >= 0], minlength=len(names))
            total = max(counts.sum(), 1)
            return {n: float(c) / total for n, c in zip(names, counts)}
        n = max(self.n, 1)
        return {name: v / n for name, v in self.sums.items()}
