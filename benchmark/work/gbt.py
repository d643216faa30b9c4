"""The least work of one step (one tree) of level-wise histogram boosting,
and of one call of a histogram kernel, from the configuration alone.

A histogram pass over n rows reads, for each row, one bin index a column
(one byte is what up to 256 bins need) and 12 bytes of row state (gradient,
hessian, node id), and makes one add each into the gradient and the hessian
histogram a column: bytes n*(C + 12), operations 2*n*C. The one-hot
contraction that today's kernel spends on this is its own choice and is
not counted.

A tree of depth D needs the histograms of levels 0..D-1 (the leaves need
totals only). The root reads every row; at each deeper level the smaller
child of every split is enough, the sibling comes by subtraction, so at
most half the rows: 1 + (D-1)/2 passes. One more pass of 12 bytes a row
turns predictions into gradients and adds the tree's leaf values.
"""

ROW_STATE_BYTES = 12


def pass_work(config, rows: int):
    cols = config["input_dim"]
    return {"flops": 2 * rows * cols,
            "bytes": rows * (cols * 1 + ROW_STATE_BYTES)}


def step_work(config):
    rows = config["train_rows"]
    passes = 1 + (config["max_depth"] - 1) / 2
    one = pass_work(config, rows)
    return {"flops": passes * one["flops"],
            "bytes": passes * one["bytes"] + ROW_STATE_BYTES * rows}


def kernel_call_work(config, chips: int):
    """One call of the histogram kernel on one chip: a pass over the rows
    that chip holds."""
    return pass_work(config, config["train_rows"] // chips)
