"""SE / ST sensitivity variable selection on rows already in memory or
on the device: the quick train, then every column wiped and re-scored.

`shifu varsel` with filterBy SE, ST or SC (`processor/varselect.py`)
loads and normalizes the candidate columns and calls
`sensitivity_select`; a caller whose rows already lie on the device
calls it the same way. The reference's job (`VarSelectMapper`, scoring
through `CacheBasicFloatNetwork`, as recalled) trains a quick NN on
every candidate (`TrainModelProcessor` isForVarSelect: half of
`numTrainEpochs`, at least 10, one bag), then, for every column, sets
the column to its normalized mean, 0, scores every row again and ranks
the columns by the mean squared change of the score.

`shifu_ablate` takes the cached network's form. The first layer's sums
Z = x W1 + b1 are computed once. Wiping column c takes its one term out
of them, Z - x[:, c] (x) W1[c, :], and the rest of the net is
`nn.forward`'s own (`nn.first_layer_sums`, `nn.forward_from_sums`).
The term is rounded as the product inside Z rounded it
(`nn.first_layer_operand_dtype`), so a wiped row's first layer is what
a forward pass over the wiped row computes, but for the order of its
float32 sums. Columns go in blocks of `COL_BLOCK` and rows in blocks
sized from `BLOCK_BYTES`; the largest array a step holds is one block's
(columns, width, rows), so the ablation holds x, Z and a few blocks,
whatever the column count.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import nn as nn_mod
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.parallel import mesh as mesh_mod
from shifu_tpu.train.trainer import TrainResult, train_nn

COL_BLOCK = 128          # columns a block: one lane tile of x's row
BLOCK_BYTES = 64 << 20   # a block's (columns, width, rows) float32
LANES = 128


@dataclass
class Selection:
    train: TrainResult      # the quick train's result
    deltas: np.ndarray      # (C,) mean squared score change a column
    selected: np.ndarray    # (filter_num,) column indices, largest first


def quick_train_conf(train_conf: ModelTrainConf) -> ModelTrainConf:
    """The quick train's settings: half of `numTrainEpochs`, at least
    10, one bag (`TrainModelProcessor.java:1588-1591`)."""
    conf = copy.copy(train_conf)
    conf.numTrainEpochs = max(train_conf.numTrainEpochs // 2, 10)
    conf.baggingNum = 1
    return conf


def ablate_blocks(rows: int, cols: int, widest: int) -> Tuple[int, int]:
    """(columns, rows) a block: COL_BLOCK columns, and rows as many
    128-row tiles as keep a block's (columns, widest layer, rows)
    float32 within BLOCK_BYTES; neither more than there are."""
    cb = min(cols, COL_BLOCK)
    rb = BLOCK_BYTES // (4 * cb * widest) // LANES * LANES
    return cb, min(rows, max(rb, LANES))


def _local_sums(spec, params, x, n_real, row0, blocks):
    """(C,) sums over this device's rows of the squared score change
    when each column is wiped, rows at or past `n_real` (their place in
    the whole table is row0 + local index) left out; and the base
    scores' sum and sum of squares over the same rows."""
    rows, cols = x.shape
    cb, rb = blocks
    f32 = jnp.float32
    zT = nn_mod.first_layer_sums(spec, params, x).T          # (width, R)
    # the term's operands rounded as the product's inside the sums: a
    # float32 rounded by `reduce_precision`, which the compiler keeps
    # where it may drop a cast to bfloat16 and back (excess precision)
    if nn_mod.first_layer_operand_dtype(spec) == jnp.bfloat16:
        rounded = lambda a: jax.lax.reduce_precision(  # noqa: E731
            a.astype(f32), exponent_bits=8, mantissa_bits=7)
    else:
        rounded = lambda a: a.astype(f32)  # noqa: E731
    w1r = rounded(params[0]["w"])
    rest = partial(nn_mod.forward_from_sums, spec, params, rows_last=True)

    def row_blocks(c0):
        wb = jax.lax.dynamic_slice_in_dim(w1r, c0, cb, 0)     # (cb, width)

        def body(i, acc):
            r0 = jnp.minimum(i * rb, rows - rb)
            at = r0 + jnp.arange(rb)
            keep = (at >= i * rb) & (row0 + at < n_real)
            zr = jax.lax.dynamic_slice_in_dim(zT, r0, rb, 1)  # (width, rb)
            xr = jax.lax.dynamic_slice(x, (r0, c0), (rb, cb))
            term = wb[:, :, None] * rounded(xr).T[:, None, :]
            base = rest(zr[None])                   # (1, rb) or (1, K, rb)
            wiped = rest(zr[None] - term)           # (cb, rb) or (cb, K, rb)
            sq = jnp.square(wiped - base).reshape(cb, -1, rb).sum(axis=1)
            sums, s1, s2 = acc
            s = jnp.where(keep, base.reshape(-1, rb)[0], 0.0)
            return (sums + jnp.sum(jnp.where(keep, sq, 0.0), axis=1),
                    s1 + jnp.sum(s), s2 + jnp.sum(s * s))

        zero = jnp.zeros((), f32)
        return jax.lax.fori_loop(0, -(-rows // rb), body,
                                 (jnp.zeros(cb, f32), zero, zero))

    def col_body(j, acc):
        c0 = jnp.minimum(j * cb, cols - cb)
        sums, s1, s2 = row_blocks(c0)
        # the base scores are the same in every column block: kept once
        return (jax.lax.dynamic_update_slice(acc[0], sums, (c0,)), s1, s2)

    zero = jnp.zeros((), f32)
    return jax.lax.fori_loop(0, -(-cols // cb), col_body,
                             (jnp.zeros(cols, f32), zero, zero))


@partial(jax.jit, static_argnames=("spec", "blocks", "mesh"))
def shifu_ablate(spec: nn_mod.MLPSpec, params, x, n_real, blocks,
                 mesh=None):
    """((C,) mean squared score change when column c is wiped to 0, the
    variance of the unwiped scores), both over the first `n_real` rows
    of x (rows after them, a mesh's padding, are left out). Columns and
    rows in `blocks` (`ablate_blocks` of one device's rows); under a
    `mesh` whose data axis is wider than one, each device ablates its
    own rows and the (C,) partial sums are added."""
    with jax.named_scope("ablate"):
        if mesh is None or mesh.shape["data"] == 1:
            sums, s1, s2 = _local_sums(spec, params, x, n_real, 0, blocks)
        else:
            local_rows = x.shape[0] // mesh.shape["data"]

            @partial(jax.shard_map, mesh=mesh,
                     in_specs=(P(), P("data", None)), out_specs=P(),
                     check_vma=False)
            def sharded(p, xs):
                row0 = jax.lax.axis_index("data") * local_rows
                return jax.lax.psum(
                    _local_sums(spec, p, xs, n_real, row0, blocks), "data")

            sums, s1, s2 = sharded(params, x)
        mean = s1 / n_real
        return sums / n_real, s2 / n_real - mean * mean


def sensitivity_select(train_conf: ModelTrainConf, x, y, w, seed: int,
                       filter_num: int, by: str = "SE") -> Selection:
    """The quick train on every column of x, then the ablation of every
    column over every row; the `filter_num` columns of largest delta.
    `by` "ST" divides the deltas by the variance of the unwiped scores.
    x, y, w are host or device arrays (rows, columns), (rows,), (rows,).

    `shifu:train.ablate`, beside the quick train's `shifu:train.job`,
    covers the rows' placement on the mesh, the ablation's dispatch,
    the wait and the one fetch of the deltas."""
    res = train_nn(quick_train_conf(train_conf), x, y, w, seed=seed)
    params = jax.tree.map(jnp.asarray, res.params_per_bag[0])
    mesh = mesh_mod.default_mesh()
    rows, cols = (int(d) for d in x.shape)
    local_rows = -(-rows // mesh.shape["data"])
    blocks = ablate_blocks(local_rows, cols,
                           max(res.spec.layer_dims[1:]))
    hidden = "x".join(str(d) for d in res.spec.hidden_dims)
    with obs_trace.span("train.ablate", family="varsel", rows=rows,
                        columns=cols, col_block=blocks[0], hidden=hidden,
                        filter_num=int(filter_num), by=by):
        jx = mesh_mod.shard_axis(mesh, x, 0)
        # waited on whole before the one copy to the host, so that a
        # trace tells the program's end from the transfer's
        deltas, var = jax.device_get(jax.block_until_ready(shifu_ablate(
            res.spec, params, jx, rows, blocks, mesh)))
    deltas = np.asarray(deltas, np.float64)
    if by == "ST":
        deltas = deltas / (float(var) or 1.0)
    selected = np.argsort(-deltas, kind="stable")[:filter_num]
    return Selection(train=res, deltas=deltas, selected=selected)
