"""The least work of one step (one epoch of mini-batch updates) of Wide &
Deep training, from the configuration alone: what the algorithm needs,
whatever implements it.

Operations: the deep tower is an MLP over [dense, embeddings]; a dense
layer d_in -> d_out costs 2*d_in*d_out a row forward and the same again
for each of its two gradients: 3 * 2 * sum(d_in*d_out) a training row (as
`work/mlp.py` counts them). The lookups, the wide sum and the optimizer are
adds and a few multiplies a value and are left out, which only lowers a
share.

Bytes: every epoch reads each row's columns once (13 floats, 26 ids,
label, weight). A batch has to read the embedding row and the wide weight
of every DISTINCT id it holds, and AdaGrad has to read and write the value
and the accumulator of those rows and of no other: 4 passes (value and
accumulator, each read and written) over (E + 1) floats a distinct row. A
row that a batch does not look up need not be touched, so a dense pass over
the whole table is the implementation's choice and is not counted. The
expected number of distinct rows a batch touches follows from the
configuration's own id distribution (`datasets/criteo_synth.py`: a column's
missing slot with probability m, else rank k with probability p_k):
sum over columns of [1 - (1-m)^B] + sum_k [1 - (1 - (1-m) p_k)^B], for B =
batch_rows. The MLP's weights stay on the chip.
"""

import importlib

import numpy as np

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
ID_BYTES = 4
TABLE_PASSES = 4        # value and accumulator, each read and written


def deep_products(config) -> int:
    dims = [config["dense_dim"] + len(config["vocab_sizes"])
            * config["embed_size"], *config["hidden_dims"],
            config["output_dim"]]
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def distinct_rows_per_batch(config) -> float:
    """Expected distinct table rows one batch looks up, all columns."""
    dataset = importlib.import_module(
        "benchmark.datasets." + config["dataset"])
    batch, miss = config["batch_rows"], config["missing_rate"]
    hit = lambda p: -np.expm1(batch * np.log1p(-p))  # noqa: E731
    total = 0.0
    for n in dataset.real_ids(config):
        p = (1.0 - miss) * dataset.rank_probabilities(
            int(n), config["zipf_exponent"])
        total += float(np.sum(hit(p))) + float(hit(miss))
    return total


def row_bytes(config) -> int:
    return DTYPE_BYTES[config["dtype"]] * (config["dense_dim"] + 2) \
        + ID_BYTES * len(config["vocab_sizes"])


def table_bytes_per_batch(config) -> float:
    return TABLE_PASSES * DTYPE_BYTES[config["dtype"]] \
        * (config["embed_size"] + 1) * distinct_rows_per_batch(config)


def step_work(config):
    rows = config["train_rows"]
    batches = -(-rows // config["batch_rows"])
    return {"flops": 3 * 2 * deep_products(config) * rows,
            "bytes": rows * row_bytes(config)
            + batches * table_bytes_per_batch(config)}


def table_step_work(config):
    """The table path's part of a step: the lookups, their gradient's
    accumulation and the two tables' update. No operations are counted
    (adds), so its least time is its bytes over the bandwidth."""
    batches = -(-config["train_rows"] // config["batch_rows"])
    return {"flops": 0.0, "bytes": batches * table_bytes_per_batch(config)}
