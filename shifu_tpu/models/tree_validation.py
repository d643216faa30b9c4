"""Scoring a boosted ensemble on its validation rows, round by round.

The resident GBT builders (`models/gbdt.py`: `build_gbt` and
`build_gbt_bagged`) decide early stop on the host once a round, from the
validation error of every ensemble they grow. The rows come here once a
job (`place`) and stay where they are put; each round the new trees are
walked over them and added into the raw scores by one program of its own
(`shifu_validate`, scope `validate`, span `train.validate`), whose
executions a device trace names.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tpu.models.gbdt import TreeConfig, _predict_tree, _val_error


def place(val_data, mesh):
    """(bins (C, V), labels, weights) of a job's validation rows
    `val_data` = (bins, labels). A device bin matrix is taken as ALREADY
    transposed, (C, V), and placed: nothing is fetched or transposed; the
    labels are laid by row beside it (`rows.rows_over`) and the weights,
    all one, made there. Host rows (V, C) are transposed and placed by
    row over `mesh`, as a builder places its training rows; the padding
    weighs nothing."""
    from shifu_tpu.parallel import mesh as mesh_mod, rows
    vb, vy = val_data
    if isinstance(vb, jax.Array):
        vy = rows.rows_over(rows.rows_mesh(vb, axis=1), vy)
        return vb, vy, jnp.ones_like(vy)
    n_val = vb.shape[0]
    vbT = mesh_mod.shard_axis(
        mesh, np.ascontiguousarray(np.asarray(vb, np.int32).T), 1)
    vy, vw = mesh_mod.shard_rows(mesh, np.asarray(vy, np.float32),
                                 np.ones(n_val, np.float32))
    return vbT, vy, vw


@partial(jax.jit, static_argnames=("cfg",))
def shifu_validate(cfg: TreeConfig, trees_T, vbinsT, vraw_T, vy, vw):
    """One round of validation for G ensembles: each one's new tree
    (`trees_T`, (G, nodes)) walked over the validation rows
    (`gbdt._predict_tree`), its leaf values added at the learning rate
    into that ensemble's raw scores `vraw_T` (G, V), and each one's
    `gbdt._val_error`. Returns (vraw_T, (G,) errors)."""
    with jax.named_scope("validate"):
        # one tree at a time, on the rows' own (V,) vectors: the
        # temporaries are one tree's whatever the group (a gather walk
        # batched over a group of five compiled to 16 GB at 2^22 rows;
        # one tree's select walk holds 0.4 MB), and no tree axis of one
        # is laid second-minor under the leaf lookup
        score = jax.lax.map(lambda tree: _predict_tree(
            tree, vbinsT, cfg.max_depth, cfg.n_bins), trees_T)
        vraw_T = vraw_T + cfg.learning_rate * score
        return vraw_T, _val_error(vraw_T, vy, vw, cfg.loss)
