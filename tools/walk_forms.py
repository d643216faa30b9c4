#!/usr/bin/env python3
"""Time the two forms of a finished tree's walk, level by level.

    python3 tools/walk_forms.py [--rows N] [--columns C] [--slots 128,512,...]
                                [--update-rows N] [--groups 1,4]
                                [--out tmp/walk_forms.json]

`gbdt._walk_trees` looks a row's node up over a level's own slots by
select-and-sum (`_walk_level_select`: S compares a row) or gathers it
from the tree's tables (`_walk_level_gather`), and takes the leaf value
the same two ways (`_leaf_values`); `gbdt._WALK_SELECT_SLOTS` and
`_LEAF_SELECT_SLOTS` are where they switch. For each slot count S this
times one level of each form over rows spread across that level's S
nodes of a random tree, and the leaf-value read from an S-entry table,
and checks that both forms give the same bits (`--slots ""` skips
these). Then one depth-8 tree walked with its leaf values three ways:
on the rows' own (R,) vectors (`_predict_tree`, as the validation
program calls it), as `predict_trees` of a stack of that one tree, whose
`vmap` lays the unit tree axis second-minor, and with every level and
the leaf value gathered. Last, a lockstep group's boosting update over
`--update-rows` rows for each group size in `--groups`: `vmap` of
`_lookup` over the group against a tree at a time
(`_group_leaf_values`), 511 leaf values a tree. Every pair of forms is
compared bit for bit. Wall milliseconds a call, the median of
`--repeats` timings of `--calls` back-to-back calls each; a device
measurement only where jax runs on an accelerator.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from shifu_tpu.models import gbdt  # noqa: E402
from shifu_tpu.parallel import mesh  # noqa: E402
from shifu_tpu.resilience import atomic_write  # noqa: E402

N_BINS = 64


def random_tree(rng, n_nodes, columns, leaf_share=0.1):
    return {"feature": jnp.asarray(rng.integers(-1, columns, n_nodes),
                                   jnp.int32),
            "bin": jnp.asarray(rng.integers(0, N_BINS - 1, n_nodes),
                               jnp.int32),
            "default_left": jnp.asarray(rng.random(n_nodes) < 0.5),
            "is_leaf": jnp.asarray(rng.random(n_nodes) < leaf_share),
            "leaf_value": jnp.asarray(rng.standard_normal(n_nodes),
                                      jnp.float32)}


def same_bits(a, b):
    """Both results equal bit for bit (float32 compared as its bits)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == np.float32:
        a, b = a.view(np.int32), b.view(np.int32)
    return bool(np.array_equal(a, b))


def walk_ms(form, tree, binsT, depth, calls, repeats):
    """One tree walked with its leaf values: `one_tree` on the rows' (R,)
    vectors, `stack_of_one` as `predict_trees` of a stack of one, both
    selecting every level and the leaf value; `gather` gathers them all
    (and leaves the module's crossovers at 0: call it last)."""
    if form == "gather":
        gbdt._WALK_SELECT_SLOTS = gbdt._LEAF_SELECT_SLOTS = 0
    if form == "stack_of_one":
        walk = jax.jit(lambda t, b: gbdt.predict_trees.__wrapped__(
            jax.tree.map(lambda x: x[None], t), b, depth, N_BINS)[0])
    else:
        walk = jax.jit(lambda t, b: gbdt._predict_tree(t, b, depth, N_BINS))
    return timed(walk, (tree, binsT), calls, repeats)


def update_ms(seed, n_trees, rows, calls, repeats):
    """A lockstep group's leaf-value lookup over its rows in both forms:
    511 values a tree, node ids drawn on the device in [-1, 511]."""
    k = jax.random.split(jax.random.key(seed), 2)
    values = jax.random.normal(k[0], (n_trees, 511), jnp.float32)
    node = jax.random.randint(k[1], (n_trees, rows), -1, 512, jnp.int32)
    t_vmap, out_vmap = timed(jax.jit(jax.vmap(gbdt._lookup)),
                             (values, node), calls, repeats)
    t_tree, out_tree = timed(jax.jit(gbdt._group_leaf_values),
                             (values, node), calls, repeats)
    return {"trees": n_trees, "rows": rows, "vmap_ms": t_vmap,
            "per_tree_ms": t_tree, "equal": same_bits(out_vmap, out_tree)}


def timed(fn, args, calls, repeats):
    out = jax.block_until_ready(fn(*args))          # compile and warm up
    laps = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        laps.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(laps), out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=2 ** 22)
    ap.add_argument("--columns", type=int, default=28)
    ap.add_argument("--slots", default="128,512,2048,8192,32768")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--update-rows", type=int, default=2 ** 24)
    ap.add_argument("--groups", default="1,4")
    ap.add_argument("--out", default="tmp/walk_forms.json")
    a = ap.parse_args()
    rng = np.random.default_rng(7)
    r, c = a.rows, a.columns
    bins = rng.integers(0, N_BINS, (c, r)).astype(np.int32)
    binsT = jnp.asarray(bins)
    dev = mesh.leased_devices()[0]
    result = {"device": dev.device_kind, "platform": dev.platform,
              "rows": r, "columns": c, "levels": [], "walk": {},
              "update": []}
    sel = jax.jit(gbdt._walk_level_select, static_argnums=(3, 4))
    gat = jax.jit(gbdt._walk_level_gather, static_argnums=(3,))
    lsel = jax.jit(gbdt._lookup)
    lgat = jax.jit(lambda v, n: v[n])
    for s in (int(x) for x in a.slots.split(",") if x):
        depth = s.bit_length() - 1
        tree = random_tree(rng, 2 ** (depth + 2) - 1, c)
        node = jnp.asarray(rng.integers(2 ** depth - 1, 2 ** (depth + 1) - 1,
                                        r), jnp.int32)
        t_sel, n_sel = timed(sel, (tree, binsT, node, depth, N_BINS),
                             a.calls, a.repeats)
        t_gat, n_gat = timed(gat, (tree, binsT, node, N_BINS),
                             a.calls, a.repeats)
        values = tree["leaf_value"][:s]
        leaf_id = node - (2 ** depth - 1)
        t_lsel, v_sel = timed(lsel, (values, leaf_id), a.calls, a.repeats)
        t_lgat, v_gat = timed(lgat, (values, leaf_id), a.calls, a.repeats)
        row = {"slots": s, "level_select_ms": t_sel,
               "level_gather_ms": t_gat, "leaf_select_ms": t_lsel,
               "leaf_gather_ms": t_lgat,
               "level_equal": same_bits(n_sel, n_gat),
               "leaf_equal": same_bits(v_sel, v_gat)}
        result["levels"].append(row)
        print(json.dumps(row), flush=True)

    depth = 8
    tree = random_tree(rng, 2 ** (depth + 1) - 1, c, leaf_share=0.02)
    t_one, s_one = walk_ms("one_tree", tree, binsT, depth, a.calls,
                           a.repeats)
    t_stack, s_stack = walk_ms("stack_of_one", tree, binsT, depth, a.calls,
                               a.repeats)
    t_gat, s_gat = walk_ms("gather", tree, binsT, depth, a.calls, a.repeats)
    result["walk"] = {"depth": depth, "one_tree_ms": t_one,
                      "stack_of_one_ms": t_stack, "gather_ms": t_gat,
                      "equal": same_bits(s_one, s_stack)
                      and same_bits(s_one, s_gat)}
    print(json.dumps(result["walk"]), flush=True)
    del binsT
    for g in (int(x) for x in a.groups.split(",") if x):
        row = update_ms(g, g, a.update_rows, a.calls, a.repeats)
        result["update"].append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with atomic_write(a.out) as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
