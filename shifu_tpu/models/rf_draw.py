"""A random forest's draws, made on the device from the job's seed.

`models/gbdt.build_rf` gives every tree of a forest its own Poisson bag
of the rows and its own subset of the columns. Both are drawn here, by a
rule written down so that anyone can draw them again without this
module (`benchmark/families/rf_reference.py` does): with `key =
jax.random.key(seed)` (threefry2x32) and `kt = jax.random.fold_in(key,
t)` for tree t = 0 .. n_trees - 1 of the forest,

- instance weights (`bags`): `u = jax.random.bits(fold_in(kt, 0),
  (rows,), uint32)`; `iw[r] = #{k: u[r] >= T_k}` with
  `poisson_thresholds(rate)`'s T: Poisson(rate) by inversion, that is
  sampling with replacement; a tree whose `iw` is 0 on every row takes 1
  on every row. Under jax's partitionable threefry (the default) a row's
  bits hang on its index alone, so padding rows, or dividing them over
  chips, changes no real row's draw;
- feature subset (`masks`): `v = jax.random.bits(fold_in(kt, 1),
  (columns,), uint32)`; the tree keeps the k columns of smallest
  `(v[c], c)`.

A tree's draw hangs on its own index t, never on the trees drawn beside
it: `build_rf` draws a lockstep group at a time and the grouping changes
no tree.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


def poisson_thresholds(rate: float) -> Tuple[int, ...]:
    """T_0 < T_1 < ... < T_{K-1}: T_k = floor(F(k) * 2^32), F the
    Poisson(rate) distribution function summed in float64 by the
    recurrence pmf(0) = exp(-rate), pmf(j) = pmf(j-1) * rate / j, up to
    and without the first k whose floor reaches 2^32 - 1. A uniform
    32-bit draw u turns into the Poisson count #{k: u >= T_k}: the
    inverse of F on a grid of 2^-32, compares only, so the device, numpy
    and a reference agree bit for bit."""
    rate = max(float(rate), 1e-6)
    out, pmf, cdf, k = [], math.exp(-rate), 0.0, 0
    while True:
        cdf += pmf
        edge = int(math.floor(cdf * 4294967296.0))
        if edge >= 4294967295:
            return tuple(out)
        out.append(edge)
        k += 1
        pmf *= rate / k


def _stream(key, tree, stream: int):
    return jax.random.fold_in(jax.random.fold_in(key, tree), stream)


@partial(jax.jit, static_argnames=("n_rows", "thresholds", "sharding"))
@jax.named_scope("bag")
def bags(key, tree_ids, n_rows: int, thresholds: Tuple[int, ...],
         sharding=None):
    """(G, n_rows) float32 instance weights of the trees `tree_ids`
    ((G,) int32) of the forest that `key` names, laid as `sharding` says
    (rows over a mesh's data axis) where one is given."""
    def one(t):
        u = jax.random.bits(_stream(key, t, 0), (n_rows,), jnp.uint32)
        iw = jnp.zeros((n_rows,), jnp.int32)
        for edge in thresholds:
            iw = iw + (u >= jnp.uint32(edge)).astype(jnp.int32)
        # a bag that drew no row at all sees every row once
        return jnp.where(jnp.sum(iw) == 0, 1, iw).astype(jnp.float32)

    out = jax.vmap(one)(tree_ids)
    if sharding is not None:
        out = jax.lax.with_sharding_constraint(out, sharding)
    return out


@partial(jax.jit, static_argnames=("n_cols", "k_cols"))
@jax.named_scope("bag")
def masks(key, tree_ids, n_cols: int, k_cols: int):
    """(G, n_cols) float32 feature masks of the trees `tree_ids`,
    `k_cols` ones a tree."""
    def one(t):
        v = jax.random.bits(_stream(key, t, 1), (n_cols,), jnp.uint32)
        col = jnp.arange(n_cols, dtype=jnp.int32)
        before = (v[None, :] < v[:, None]) | (
            (v[None, :] == v[:, None]) & (col[None, :] < col[:, None]))
        return (jnp.sum(before, axis=1) < k_cols).astype(jnp.float32)

    return jax.vmap(one)(tree_ids)
