"""The least work of one step (one full-batch epoch) of MLP training,
from the configuration alone: what the algorithm needs, whatever
implements it.

Operations: a dense layer d_in -> d_out costs 2*d_in*d_out a row forward;
backward costs the same again for the gradient of its input and again for
the gradient of its weights: 3 * 2 * sum(d_in*d_out) a training row. The
first layer's input gradient is not needed, and the validation pass is
work too; both are left as they are, which only lowers a share.

Bytes: the training matrix is far larger than on-chip memory and the
parameters change every epoch, so every epoch reads it at least once:
rows * d_in * bytes of the stated storage dtype. Activations need not
leave the chip.
"""

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def step_work(config):
    dims = [config["input_dim"], *config["hidden_dims"], config["output_dim"]]
    products = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    rows = config["train_rows"]
    return {"flops": 3 * 2 * products * rows,
            "bytes": rows * config["input_dim"] * DTYPE_BYTES[config["dtype"]]}
