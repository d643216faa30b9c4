#!/usr/bin/env python3
"""Time the two forms of a lockstep group's histogram pass, kernel alone.

    python3 tools/hist_forms.py [--rows N] [--columns C] [--trees 1,2,4]
                                [--slots 1,2,4,8,16,32,64]
                                [--out tmp/hist_forms.json]

A group of T trees over one bin matrix takes its level's histograms
from `ops/pallas_hist.level_histograms_pallas` in one of two forms:
`vmap` over the trees' (R,) row state, where the `pallas_call` gains a
tree axis and every tree's grid steps build the bin one-hot again; or
the grouped form, the (T, R) row state handed to the kernel whole, where
`trees_per_pass` trees share each row tile's one-hot. For each T and
slot count S this times both over seeded rows spread across S slots and
the dump slot, and gives the sha1 of every histogram and whether the two
forms agree bit for bit. Wall milliseconds a call, operand views
included, the median of `--repeats` timings of `--calls` back-to-back
calls; a device measurement only where jax runs on an accelerator.
"""

import argparse
import functools
import hashlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from shifu_tpu.ops import pallas_hist  # noqa: E402
from shifu_tpu.parallel import mesh  # noqa: E402
from shifu_tpu.resilience import atomic_write  # noqa: E402

N_BINS = 64


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


def sha1(arrays):
    h = hashlib.sha1()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def draw(seed, n_trees, rows, n_slots):
    """Seeded (T, R) slot (in [-1, S]: the dump slot and a row of no
    level among them), gradient and hessian, made on the device."""
    k = jax.random.split(jax.random.key(seed), 3)
    slot = jax.random.randint(k[0], (n_trees, rows), -1, n_slots + 1,
                              jnp.int32)
    grad = jax.random.normal(k[1], (n_trees, rows), jnp.float32)
    hess = jax.random.uniform(k[2], (n_trees, rows), jnp.float32, 0.5, 1.5)
    return slot, grad, hess


@functools.partial(jax.jit, static_argnums=(4, 5))
def each(binsT, slot, grad, hess, n_slots, interpret):
    """The per-tree form: `vmap` of the (R,) call over the trees."""
    return jax.vmap(lambda s, g, h: pallas_hist.level_histograms_pallas(
        binsT, s, g, h, n_slots, N_BINS, interpret=interpret))(slot, grad,
                                                                hess)


@functools.partial(jax.jit, static_argnums=(4, 5))
def grouped(binsT, slot, grad, hess, n_slots, interpret):
    """The grouped form: the (T, R) row state handed to the kernel."""
    return pallas_hist.level_histograms_pallas(
        binsT, slot, grad, hess, n_slots, N_BINS, interpret=interpret)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=2 ** 24)
    ap.add_argument("--columns", type=int, default=28)
    ap.add_argument("--trees", default="1,2,4")
    ap.add_argument("--slots", default="1,2,4,8,16,32,64")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="tmp/hist_forms.json")
    a = ap.parse_args()
    r, c = a.rows, a.columns
    binsT = jax.random.randint(jax.random.key(7), (c, r), 0, N_BINS,
                               jnp.int32)
    dev = mesh.leased_devices()[0]
    interpret = dev.platform != "tpu"     # the rehearsal off the chip
    result = {"device": dev.device_kind, "platform": dev.platform,
              "rows": r, "columns": c, "bins": N_BINS, "passes": []}
    for t in (int(x) for x in a.trees.split(",")):
        for s in (int(x) for x in a.slots.split(",")):
            state = draw(1000 * t + s, t, r, s)
            t_each, out_each = timed(each, (binsT,) + state + (s, interpret),
                                     a.calls, a.repeats)
            t_group, out_group = timed(grouped,
                                       (binsT,) + state + (s, interpret),
                                       a.calls, a.repeats)
            row = {"trees": t, "slots": s,
                   "trees_per_pass": pallas_hist.trees_per_pass(
                       t, s, c, N_BINS),
                   "vmap_ms": t_each, "grouped_ms": t_group,
                   "vmap_sha1": sha1(out_each),
                   "grouped_sha1": sha1(out_group)}
            row["equal"] = row["vmap_sha1"] == row["grouped_sha1"]
            result["passes"].append(row)
            print(json.dumps(row), flush=True)
            del state, out_each, out_group
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with atomic_write(a.out) as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
