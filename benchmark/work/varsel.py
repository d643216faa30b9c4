"""The least work of an SE variable-selection job, from the configuration
alone: what the algorithm needs, whatever implements it.

The quick train: max(num_train_epochs // 2, 10) full-batch epochs over
the rows the validSetRate split trains on, each as `work/mlp.py`
reckons an epoch (3 * 2 * sum(d_in * d_out) operations a row, the
training matrix read once).

The ablation, over R rows, C columns and the first hidden width H1:

- operations: the first layer's sums once, 2 * R * C * H1; then for every
  (column, row) the wiped sums, a multiply and a subtract a unit
  (2 * H1), every activation of the net counted as one operation a unit,
  2 * d_in * d_out a later layer, and the score's change squared and
  added (3). What a transcendental costs more than one operation is left
  out, which only lowers a share;
- bytes: x read once, R * C * 4; the first layer's sums and a block's
  activations need not leave the chip.

The least time of each is the larger of its operations over peak FLOP/s
and its bytes over peak bytes/s (`peaks.json`); the job's is their sum.
"""

from benchmark.work import mlp


def _dims(config):
    return [config["input_dim"], *config["hidden_dims"], config["output_dim"]]


def epochs(config) -> int:
    return max(config["num_train_epochs"] // 2, 10)


def epoch_work(config):
    """One epoch of the quick train."""
    rows = round(config["train_rows"] * (1.0 - config["valid_set_rate"]))
    return mlp.step_work({**config, "train_rows": rows})


def ablate_work(config):
    """One ablation of every column over every row."""
    dims = _dims(config)
    rows, cols, first = config["train_rows"], dims[0], dims[1]
    later = sum(a * b for a, b in zip(dims[1:-1], dims[2:]))
    a_row_column = 2 * first + sum(dims[1:]) + 2 * later + 3
    return {"flops": 2 * rows * cols * first + rows * cols * a_row_column,
            "bytes": rows * cols * mlp.DTYPE_BYTES[config["dtype"]]}


def step_work(config):
    """A step is an epoch of the quick train, and carries its share of
    the ablation: the job's operations and bytes over its epochs. The
    larger of the two totals' times is no more than the sum of each
    part's, so a share of peak read from it is a share of a lower
    bound."""
    epoch, ablate = epoch_work(config), ablate_work(config)
    n = epochs(config)
    return {k: epoch[k] + ablate[k] / n for k in ("flops", "bytes")}
