"""Attribute the GBT end-to-end s/tree to its components on the real
backend (VERDICT r4 next #3): times, separately and under identical
11Mx28 shapes, (a) the full scanned boosting rounds, (b) the per-level
histogram kernel alone, (c) the row routing alone, (d) split selection
alone — each synced by a scalar fetch. Appends one JSON line to
tools/profile_gbt.jsonl and optionally captures a jax.profiler trace
(SHIFU_TPU_GBT_TRACE=1 -> tools/gbt_trace/).

Usage: python tools/profile_gbt.py [rows] [trees]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from shifu_tpu.config.environment import knob_bool  # noqa: E402


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 11_000_000
    trees = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    os.environ.setdefault("SHIFU_TPU_GBT_SCAN_GROUP", "5")
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models import gbdt

    backend = jax.default_backend()
    n_bins = 64
    cols = 28
    depth = 6
    key = jax.random.PRNGKey(0)
    kb, kbeta, kn = jax.random.split(key, 3)
    binsT = jax.random.randint(kb, (cols, rows), 0, n_bins - 1,
                               dtype=jnp.int32)
    beta = jax.random.normal(kbeta, (cols,))
    margin = (beta @ binsT.astype(jnp.float32)) / np.sqrt(cols)
    y = (margin > jnp.median(margin)).astype(jnp.float32)
    w = jnp.ones(rows, jnp.float32)
    cfg = gbdt.TreeConfig(max_depth=depth, n_bins=n_bins,
                          learning_rate=0.2, loss="log")
    float(y[:4].sum())      # sync generation

    rec = {"ts": time.time(), "backend": backend, "rows": rows,
           "trees": trees, "depth": depth}

    def timed(name, fn, sync):
        fn()                                    # compile
        sync()
        t0 = time.time()
        fn()
        sync()
        rec[name] = round(time.time() - t0, 3)
        print(f"[profile] {name}: {rec[name]}s", file=sys.stderr,
              flush=True)

    # (a) full build
    out = {}

    def full():
        out["trees"], _ = gbdt.build_gbt(cfg, binsT, y, w, n_trees=trees)

    timed("full_build_s", full, lambda: None)   # build_gbt self-syncs
    rec["s_per_tree"] = round(rec["full_build_s"] / trees, 3)

    # component kernels at each level's realistic slot count. node ids
    # come from the REAL first tree's routing so occupancy is honest.
    tree0 = jax.tree.map(lambda a: jnp.asarray(a[0]), out["trees"])
    grad, hess = gbdt.gbt_gradients(y, jnp.zeros(rows), w, cfg.loss)

    node = jnp.zeros(rows, jnp.int32)
    nodes_per_level = [node]
    for d in range(depth):
        node = gbdt._route_level(cfg, tree0, binsT, node, d)
        nodes_per_level.append(node)

    # (b) histograms: every level's kernel, one jit, realistic slots
    @jax.jit
    def hists_all_levels(b, g, h):
        acc = 0.0
        for d in range(depth + 1):
            n_level = 2 ** d
            gh, hh = gbdt._level_histograms(
                b, nodes_per_level[min(d, depth)], g, h,
                2 ** d - 1, n_level, n_bins)
            acc = acc + gh.sum() + hh.sum()
        return acc

    timed("hist_levels_s",
          lambda: hists_all_levels(binsT, grad, hess),
          lambda: float(hists_all_levels(binsT, grad, hess)))

    # (c) routing: all levels' row advancement
    @jax.jit
    def route_all(b):
        n = jnp.zeros(rows, jnp.int32)
        for d in range(depth):
            n = gbdt._route_level(cfg, tree0, b, n, d)
        return n.sum()

    timed("route_levels_s", lambda: route_all(binsT),
          lambda: float(route_all(binsT)))

    # (d) split selection on depth-6-sized histograms (64 slots)
    g64 = jax.random.normal(key, (64, cols, n_bins))
    h64 = jnp.abs(jax.random.normal(kb, (64, cols, n_bins)))
    fm = jnp.ones(cols, jnp.float32)

    @jax.jit
    def splits(g, h):
        s = gbdt._best_splits((g, h), cfg, fm)
        return s["gain"].sum()

    timed("best_splits64_s", lambda: splits(g64, h64),
          lambda: float(splits(g64, h64)))

    # (e) gradient recompute + leaf lookup (the boosting glue)
    @jax.jit
    def glue(pred):
        g, h = gbdt.gbt_gradients(y, pred, w, cfg.loss)
        contrib = gbdt._lookup(tree0["leaf_value"], nodes_per_level[-1])
        return (pred + cfg.learning_rate * contrib).sum() + g.sum() + h.sum()

    timed("glue_s", lambda: glue(jnp.zeros(rows)),
          lambda: float(glue(jnp.zeros(rows))))

    if knob_bool("SHIFU_TPU_GBT_TRACE"):
        import jax.profiler
        tdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "gbt_trace")
        with jax.profiler.trace(tdir):
            gbdt.build_gbt(cfg, binsT, y, w, n_trees=2)
        rec["trace_dir"] = tdir

    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "profile_gbt.jsonl")
    with open(out_path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
