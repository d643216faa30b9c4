"""Synthetic model-set generator for tests — a separable binary tabular
dataset with numeric + categorical + meta + weight columns, written in
the pipe-delimited layout the reference's tutorial datasets use."""

from __future__ import annotations

import json
import os

import numpy as np

from shifu_tpu.resilience import atomic_write


def make_raw_frame(rng, n_rows: int = 2000, n_num: int = 6, n_cat: int = 2,
                   missing_rate: float = 0.02, n_classes: int = 2):
    """Returns (header, rows, y) where informative numeric columns are
    Gaussians shifted by class and categoricals have class-skewed
    frequencies. n_classes>2 produces tags c0..c{K-1}."""
    if n_classes > 2:
        y = rng.integers(0, n_classes, n_rows)
    else:
        y = (rng.random(n_rows) < 0.35).astype(int)
    cols = {}
    for j in range(n_num):
        shift = (j + 1) * 0.5 if j % 2 == 0 else 0.0  # odd columns are noise
        x = rng.normal(0, 1, n_rows) + shift * y
        cols[f"num_{j}"] = np.round(x, 6).astype(str)
    cats = ["aa", "bb", "cc", "dd"]
    for j in range(n_cat):
        p_pos = np.array([0.5, 0.3, 0.15, 0.05])
        p_neg = np.array([0.1, 0.2, 0.3, 0.4])
        vals = np.where(y == 1,
                        rng.choice(cats, n_rows, p=p_pos),
                        rng.choice(cats, n_rows, p=p_neg))
        cols[f"cat_{j}"] = vals
    # inject missing tokens
    for name in list(cols):
        mask = rng.random(n_rows) < missing_rate
        v = cols[name].copy()
        v[mask] = "?"
        cols[name] = v
    cols["wgt"] = np.round(rng.uniform(0.5, 2.0, n_rows), 4).astype(str)
    cols["rowid"] = np.arange(n_rows).astype(str)
    if n_classes > 2:
        cols["diagnosis"] = np.array([f"c{v}" for v in y])
    else:
        cols["diagnosis"] = np.where(y == 1, "M", "B")
    header = list(cols.keys())
    rows = np.stack([cols[h] for h in header], axis=1)
    return header, rows, y


def write_parquet_part(path, header, rows, row_group_size: int = 0):
    """Typed parquet part file: numeric columns as float64 (missing
    tokens → null), the rest as string (missing → null) — the layout
    NNParquetWorker consumes. Small row groups exercise the chunked
    batch reader."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    missing = {"?", ""}
    cols = {}
    for j, name in enumerate(header):
        v = rows[:, j]
        if name.startswith("num_") or name == "wgt":
            cols[name] = pa.array(
                [None if s in missing else float(s) for s in v],
                type=pa.float64())
        else:
            cols[name] = pa.array([None if s in missing else str(s)
                                   for s in v], type=pa.string())
    pq.write_table(pa.table(cols), path,
                   row_group_size=row_group_size or len(rows))


def make_model_set(tmp_path, rng, n_rows: int = 2000, norm_type: str = "ZSCALE",
                   algorithm: str = "NN", train_params: dict | None = None,
                   n_classes: int = 2, multi_classify: str = "NATIVE",
                   seg_expressions: list | None = None,
                   data_format: str = "text"):
    root = os.path.join(str(tmp_path), "ModelSet")
    data_dir = os.path.join(root, "data")
    eval_dir = os.path.join(root, "evaldata")
    os.makedirs(data_dir, exist_ok=True)
    os.makedirs(eval_dir, exist_ok=True)
    os.makedirs(os.path.join(root, "columns"), exist_ok=True)

    header, rows, _ = make_raw_frame(rng, n_rows, n_classes=n_classes)
    if n_classes > 2:
        pos_tags, neg_tags = ["c0"], [f"c{k}" for k in range(1, n_classes)]
    else:
        pos_tags, neg_tags = ["M"], ["B"]
    split = int(n_rows * 0.8)
    if data_format == "parquet":
        # schema carries the header (no .pig_header / headerPath)
        write_parquet_part(os.path.join(data_dir, "part-00000.parquet"),
                           header, rows[:split], row_group_size=256)
        write_parquet_part(os.path.join(eval_dir, "part-00000.parquet"),
                           header, rows[split:], row_group_size=256)
    else:
        with atomic_write(os.path.join(data_dir, ".pig_header"), "w") as f:
            f.write("|".join(header) + "\n")
        with atomic_write(os.path.join(data_dir, "part-00000"), "w") as f:
            for r in rows[:split]:
                f.write("|".join(r) + "\n")
        with atomic_write(os.path.join(eval_dir, ".pig_header"), "w") as f:
            f.write("|".join(header) + "\n")
        with atomic_write(os.path.join(eval_dir, "part-00000"), "w") as f:
            for r in rows[split:]:
                f.write("|".join(r) + "\n")
    with atomic_write(os.path.join(root, "columns", "meta.column.names"),
                      "w") as f:
        f.write("rowid\n")
    with atomic_write(os.path.join(root, "columns",
                                   "categorical.column.names"), "w") as f:
        f.write("cat_0\ncat_1\n")

    mc = {
        "basic": {"name": "SynthTest", "author": "test", "description": "",
                  "version": "0.1.0", "runMode": "LOCAL", "postTrainOn": False,
                  "customPaths": {}},
        "dataSet": {
            "source": "LOCAL", "dataPath": data_dir, "dataDelimiter": "|",
            "headerPath": ("" if data_format == "parquet"
                           else os.path.join(data_dir, ".pig_header")),
            "headerDelimiter": "|", "filterExpressions": "",
            "weightColumnName": "wgt", "targetColumnName": "diagnosis",
            "posTags": pos_tags, "negTags": neg_tags,
            "missingOrInvalidValues": ["", "*", "#", "?", "null", "~"],
            "metaColumnNameFile": os.path.join(root, "columns", "meta.column.names"),
            "categoricalColumnNameFile": os.path.join(root, "columns",
                                                      "categorical.column.names"),
        },
        "stats": {"maxNumBin": 10, "binningMethod": "EqualPositive",
                  "sampleRate": 1.0, "sampleNegOnly": False,
                  "binningAlgorithm": "SPDTI", "psiColumnName": ""},
        "varSelect": {"forceEnable": False, "forceSelectColumnNameFile": "",
                      "forceRemoveColumnNameFile": "", "filterEnable": True,
                      "filterNum": 200, "filterBy": "KS",
                      "wrapperEnabled": False, "wrapperNum": 50,
                      "wrapperRatio": 0.05, "wrapperBy": "S",
                      "missingRateThreshold": 0.98, "filterBySE": True,
                      "params": None},
        "normalize": {"stdDevCutOff": 4.0, "sampleRate": 1.0,
                      "sampleNegOnly": False, "normType": norm_type},
        "train": {
            "baggingNum": 1, "baggingWithReplacement": False,
            "baggingSampleRate": 1.0, "validSetRate": 0.2,
            "numTrainEpochs": 40, "epochsPerIteration": 1,
            "trainOnDisk": False, "isContinuous": False,
            "workerThreadCount": 4, "algorithm": algorithm,
            "multiClassifyMethod": multi_classify,
            "params": train_params or {
                "NumHiddenLayers": 1, "ActivationFunc": ["tanh"],
                "NumHiddenNodes": [10], "RegularizedConstant": 0.0,
                "LearningRate": 0.1, "Propagation": "ADAM"},
            "customPaths": {}},
        "evals": [{
            "name": "Eval1",
            "dataSet": {
                "source": "LOCAL", "dataPath": eval_dir, "dataDelimiter": "|",
                "headerPath": ("" if data_format == "parquet"
                               else os.path.join(eval_dir, ".pig_header")),
                "headerDelimiter": "|", "filterExpressions": "",
                "weightColumnName": "wgt",
                "targetColumnName": "diagnosis",
                "posTags": pos_tags, "negTags": neg_tags,
                "missingOrInvalidValues": ["", "*", "#", "?", "null", "~"]},
            "performanceBucketNum": 10, "performanceScoreSelector": "mean",
            "scoreMetaColumnNameFile": "", "customPaths": {}}],
    }
    if seg_expressions:
        seg_file = os.path.join(root, "columns", "segments.txt")
        with atomic_write(seg_file, "w") as f:
            f.write("\n".join(seg_expressions) + "\n")
        mc["dataSet"]["segExpressionFile"] = seg_file

    with atomic_write(os.path.join(root, "ModelConfig.json"), "w") as f:
        json.dump(mc, f, indent=2)
    return root


def planted_linear_table(rng, n_rows: int, beta, scale: float = 1.0):
    """(x, y, w) float32: unit Gaussian features under a planted linear
    margin — y = [x·beta·scale + N(0, 1) > 0]; `beta` (F,) for one
    label, (F, T) for T labels a row."""
    beta = np.asarray(beta, np.float32)
    x = rng.normal(0, 1, (n_rows, beta.shape[0])).astype(np.float32)
    margin = x @ beta * np.float32(scale)
    y = (margin + rng.normal(0, 1, margin.shape) > 0).astype(np.float32)
    return x, y, np.ones(n_rows, np.float32)


def planted_binned_table(rng, n_rows: int, n_cols: int, n_bins: int):
    """(bins (R, C) int32 in [0, n_bins - 1), y, w): uniform bin ids
    under a planted linear margin over the ids, half its spread in
    noise, labels split at the margin's median."""
    bins = rng.integers(0, n_bins - 1, (n_rows, n_cols)).astype(np.int32)
    margin = bins.astype(np.float32) @ rng.normal(0, 1, n_cols) \
        / np.sqrt(n_cols)
    noise = rng.normal(0, 1, n_rows) * margin.std() * 0.5
    y = (margin + noise > np.median(margin)).astype(np.float32)
    return bins, y, np.ones(n_rows, np.float32)


def planted_wdl_table(rng, n_rows: int, n_dense: int, n_cat: int,
                      vocab: int):
    """(dense, idx, y, w): the label leans on one dense column and on
    planted effects of the first two categorical columns' ids."""
    dense = rng.normal(0, 1, (n_rows, n_dense)).astype(np.float32)
    idx = rng.integers(0, vocab, (n_rows, n_cat)).astype(np.int32)
    effect = rng.normal(0, 1, vocab).astype(np.float32)
    margin = dense[:, 0] * 0.8 + effect[idx[:, 0]] + effect[idx[:, 1]] * 0.5
    y = (margin + rng.normal(0, 1, n_rows) > 0).astype(np.float32)
    return dense, idx, y, np.ones(n_rows, np.float32)
