"""Ask the chip's compiler, chip not attached.

Interpret-mode tests cannot see what Mosaic refuses: a block that is
not (8, 128)-aligned, a primitive without a TPU lowering, a float iota,
a tile that does not fit VMEM. The TPU compiler is installed here and
compiles for a DESCRIBED `v5e:2x2` device, so every kernel of the main
path is compiled at the shapes `chip_smoke.py` and the ROADMAP's cells
use — about two seconds each, no chip time. A compile that passes is
not a chip run: it says nothing about results or speed.

The topology is described inside a module-scoped fixture (never at
import, in a `skipif`, or in `parametrize` arguments): only one process
may load the TPU library, and under xdist every worker imports every
test file. The compiles run in this process, with the persistent
compile cache off around them (an entry compiled for a described chip
cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _cache_off():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# (columns, rows, bins, level nodes): the smoke's HIGGS shape at 1M and
# 11M rows, the root and the widest level, one 600-column and one
# 256-bin case; the benchmark's gbt-higgs cell at two slots (under a
# sublane tile) and its leaf level, 128 slots on the one column
# `gbdt._leaf_columns` hands it; rows that fill whole 512-row tiles but
# no whole eight-tile grid step (the bins go unpadded and the last
# step's block hangs over their end, the slot / gradient / hessian
# views are padded with slot -1)
HIST_CASES = [(28, 1_000_000, 64, 1), (28, 1_000_000, 64, 32),
              (28, 11_000_000, 64, 64), (600, 1_000_000, 64, 64),
              (28, 1_000_000, 256, 64), (28, 2 ** 24, 64, 2),
              (1, 2 ** 24, 64, 128), (28, 2 ** 22 + 1536, 64, 8)]


@pytest.mark.parametrize("c,r,b,s", HIST_CASES)
def test_hist_kernel_compiles(one_chip, c, r, b, s):
    from shifu_tpu.ops import pallas_hist
    _compile(lambda bt, sl, g, h: pallas_hist.level_histograms_pallas(
        bt, sl, g, h, s, b), one_chip,
        ((c, r), I32), ((r,), I32), ((r,), F32), ((r,), F32))


def test_kernel_instructions_take_the_kernels_names(one_chip):
    """A profiler trace names a device event by its HLO instruction, and
    the chip's compiler names a Mosaic custom call after the innermost
    scope of its `op_name`: the `pallas_call`'s `name=`. The benchmark's
    kernel readers find the histogram kernels by `_level_histograms` in
    that name and take the other custom calls of a tree build for the
    split search (`benchmark/trace_reduce.tree_build_kernels`)."""
    import re
    from shifu_tpu.ops import pallas_hist, pallas_split
    c, r, b, s = 28, 1_000_000, 64, 32

    def level(bt, sl, g, h, m):
        with jax.named_scope("hist"):
            gh, hh = pallas_hist.level_histograms_pallas(bt, sl, g, h, s, b)
        with jax.named_scope("split"):
            return pallas_split.best_splits_pallas(gh, hh, m, 1.0, 5.0)

    text = _compile(level, one_chip, ((c, r), I32), ((r,), I32), ((r,), F32),
                    ((r,), F32), ((s, c), F32))
    calls = {m.group(1): m.group(2) for m in re.finditer(
        r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', text)}
    assert len(calls) == 2, calls
    hist = [n for n in calls if "_level_histograms" in n]
    assert len(hist) == 1 and hist[0].startswith("shifu_level_histograms.")
    assert calls[hist[0]].endswith(
        "hist/jit(_level_histograms_pallas)/shifu_level_histograms/"
        "pallas_call")
    other = [n for n in calls if n not in hist]
    assert other[0].startswith("shifu_best_splits.")
    assert calls[other[0]].endswith("split/shifu_best_splits/pallas_call")


@pytest.mark.parametrize("c,r,b,s", HIST_CASES)
def test_fused_hist_kernel_compiles(one_chip, c, r, b, s):
    from shifu_tpu.ops import pallas_hist
    _compile(lambda v, ct, sl, g, h: pallas_hist.level_histograms_fused(
        v, ct, sl, g, h, s, b), one_chip,
        ((c, r), F32), ((c, b - 1), F32), ((r,), I32), ((r,), F32),
        ((r,), F32))


@pytest.mark.parametrize("s,r", [(1, 2 ** 22), (64, 2 ** 22),
                                 (8, 2 ** 22 + 1536)])
def test_hist_kernel_compiles_under_vmap_over_trees(one_chip, s, r):
    """The form the lockstep forest's pass took before the grouped one,
    and `tools/hist_forms.py`'s comparison: a vmap of the kernel over
    three trees' row state, the bin matrix shared. The `pallas_call`
    gains a grid axis and the stacked G/H operand is built per tree
    inside the kernel: Mosaic takes it at the root's one slot, at a full
    pass of the array's width, and at rows that fill no whole eight-tile
    grid step."""
    from shifu_tpu.ops import pallas_hist
    c, b, trees = 28, 64, 3
    _compile(jax.vmap(
        lambda sl, g, h, bt: pallas_hist.level_histograms_pallas(
            bt, sl, g, h, s, b), in_axes=(0, 0, 0, None)), one_chip,
        ((trees, r), I32), ((trees, r), F32), ((trees, r), F32),
        ((c, r), I32))


# (trees, slots, rows, kernel): the forest cells' groups at 2^24 rows,
# four trees a pass up to 16 slots, two at 32 (two passes of a group of
# four, one of a group of two), one at 64; rows that fill no whole
# eight-tile grid step; the fused values-and-cuts kernel
GROUPED_CASES = [(4, 8, 2 ** 24, "bins"), (4, 16, 2 ** 24, "bins"),
                 (4, 32, 2 ** 24, "bins"), (2, 32, 2 ** 24, "bins"),
                 (4, 64, 2 ** 24, "bins"), (4, 1, 2 ** 22 + 1536, "bins"),
                 (4, 8, 2 ** 24, "fused")]


@pytest.mark.parametrize("trees,s,r,kernel", GROUPED_CASES)
def test_grouped_hist_kernel_compiles_at_the_forest_cells_shapes(
        one_chip, trees, s, r, kernel):
    """The lockstep forest's pass (`gbdt._forest_level_histograms`): the
    group's (T, rows) row state handed to the kernel whole, the trees of
    a pass stacked on one (2·Tp·S8, TR) operand against each row tile's
    one bin one-hot, the passes a grid axis. Mosaic takes it inside the
    kernel's `vmem_limit_bytes` at the 28 x 2^24 table's (Tp, S8) of
    (4, 8), (4, 16) and (2, 32), and a level of a group stays ONE kernel
    call (`hist_kernel_roofline` counts one event a group-level)."""
    from shifu_tpu.ops import pallas_hist
    c, b = 28, 64
    rows = (((trees, r), I32), ((trees, r), F32), ((trees, r), F32))
    if kernel == "fused":
        text = _compile(lambda v, ct, sl, g, h:
                        pallas_hist.level_histograms_fused(
                            v, ct, sl, g, h, s, b), one_chip,
                        ((c, r), F32), ((c, b - 1), F32), *rows)
    else:
        text = _compile(lambda bt, sl, g, h:
                        pallas_hist.level_histograms_pallas(
                            bt, sl, g, h, s, b), one_chip,
                        ((c, r), I32), *rows)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1


# (columns, rows, max depth, bins, trees): the benchmark's gbt-higgs cell,
# a 3-tree lockstep forest under vmap, and a wide table
@pytest.mark.parametrize("c,r,depth,b,trees", [
    (28, 2 ** 24, 8, 64, 1), (28, 2 ** 22, 8, 64, 3),
    (1000, 2 ** 20, 6, 1024, 1)])
def test_route_level_streams_without_gather(one_chip, c, r, depth, b, trees):
    """The widest level a build routes (depth - 1: 2^(depth-1) nodes,
    a static slice of the tree's arrays) and the leaf-value lookup: the
    chip's compiler is given no gather, and no (slots, rows) or
    (columns, rows) intermediate reaches HBM: the program's temporaries
    stay a few (rows,) vectors. A per-row gather ran at 40-100 M rows/s
    on the v5e and was 80% of a tree (PERF.md, PR 25)."""
    import re
    from shifu_tpu.models import gbdt
    cfg = gbdt.TreeConfig(max_depth=depth, n_bins=b)

    def one(tree, binsT, node):
        node, half = gbdt._route_level(cfg, tree, binsT, node, depth - 1,
                                       tree["side"])
        return node, half, gbdt._lookup(tree["leaf_value"], node)

    def level(tree, binsT, node):
        if trees == 1:
            return one(tree, binsT, node)
        return jax.vmap(lambda t, n: one(t, binsT, n))(tree, node)

    lead = () if trees == 1 else (trees,)
    n = cfg.n_nodes

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tree = {"feature": shape(lead + (n,), I32), "bin": shape(lead + (n,), I32),
            "default_left": shape(lead + (n,), jnp.bool_),
            "leaf_value": shape(lead + (n,), F32),
            "side": shape(lead + (2 ** (depth - 1),), I32)}
    compiled = jax.jit(level).lower(
        tree, shape((c, r), I32), shape(lead + (r,), I32)).compile()
    assert not re.search(r"= \S+ gather\(", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * 4 * trees * r


# (nodes, columns, bins): a level of the smoke's trees, the root, a
# 20-tree lockstep forest level (20·32), 600 columns, 256 bins
@pytest.mark.parametrize("n,c,b", [(32, 28, 64), (1, 28, 64),
                                   (640, 28, 64), (64, 600, 64),
                                   (64, 128, 256), (32, 28, 32)])
def test_split_kernel_compiles(one_chip, n, c, b):
    from shifu_tpu.ops import pallas_split
    _compile(lambda g, h, m: pallas_split.best_splits_pallas(
        g, h, m, 1.0, 5.0), one_chip,
        ((n, c, b), F32), ((n, c, b), F32), ((n, c), F32))


@pytest.mark.parametrize("rows,c,h", [(1, 28, 64), (512, 28, 64),
                                      (8192, 28, 64), (64, 600, 512)])
def test_score_kernel_compiles(one_chip, rows, c, h):
    from shifu_tpu.ops import pallas_score
    _compile(lambda x, m, sd, w, b: pallas_score.fused_first_layer(
        x, m, sd, 4.0, w, b, mode="pallas"), one_chip,
        ((rows, c), F32), ((c,), F32), ((c,), F32), ((c, h), F32),
        ((h,), F32))


# (trees, depth, row bucket): the SHIFU_TPU_SERVE_BUCKETS ladder ends
@pytest.mark.parametrize("t,depth,rows", [(100, 6, 512), (20, 6, 1),
                                          (20, 6, 64), (500, 8, 8),
                                          (100, 8, 512)])
def test_trees_kernel_compiles(one_chip, t, depth, rows):
    from shifu_tpu.ops import pallas_trees
    n_pad = -(-(2 ** (depth + 1) - 1) // 8) * 8
    _compile(lambda nd, v, ct: pallas_trees.predict_ensemble(
        nd, v, ct, n_trees=t, kind="gbt", loss="log", learning_rate=0.1,
        max_depth=depth, n_bins=64), one_chip,
        ((8, t * n_pad), F32), ((28, rows), F32), ((28, 63), F32))


def test_gbt_level_step_compiles_on_four_chip_mesh(topo):
    """The data-parallel GBT level step of `gbdt._level_histograms` —
    `shard_map` around the histogram `pallas_call`, then `psum` — as
    ONE program over a 4-device mesh: the kernel must be partitionable
    and the reduction an all-reduce, not a gather of the row-sharded
    bins. (`jax.default_backend()` is the CPU here, so gbdt's own
    dispatch would pick interpret mode; the step is rebuilt around the
    kernel's entry point instead.)"""
    from shifu_tpu.ops import pallas_hist
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    rows = NamedSharding(mesh, P("data"))
    c, r, b, s = 28, 1_000_000, 64, 32

    def local(bt, sl, g, h):
        gh, hh = pallas_hist.level_histograms_pallas(bt, sl, g, h, s, b)
        return jax.lax.psum(gh, "data"), jax.lax.psum(hh, "data")

    step = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, "data"), P("data"), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False)
    text = jax.jit(step).lower(
        jax.ShapeDtypeStruct((c, r), I32,
                             sharding=NamedSharding(mesh, P(None, "data"))),
        jax.ShapeDtypeStruct((r,), I32, sharding=rows),
        jax.ShapeDtypeStruct((r,), F32, sharding=rows),
        jax.ShapeDtypeStruct((r,), F32, sharding=rows)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    assert "all-gather" not in text


def test_whole_gbt_round_compiles_on_four_chip_mesh(topo, monkeypatch):
    """One whole boosting round (`gbdt._gbt_round`: histograms, split
    search, routing) as the product builds it for a 4-device data mesh.
    The first four-chip run died here: the split kernel sat outside any
    `shard_map`, and a Mosaic kernel cannot be partitioned
    automatically. `jax.default_backend()` is steered to "tpu" so the
    product's own dispatch takes its chip branch (compiled kernels)."""
    from shifu_tpu.models import gbdt
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))

    def shape(dims, dtype, *spec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    r = 900_000
    cfg = gbdt.TreeConfig(max_depth=6, n_bins=64, loss="log",
                          learning_rate=0.2, min_instances_per_node=5)
    text = gbdt._gbt_round.lower(
        cfg, shape((28, r), I32, None, "data"), shape((r,), F32, "data"),
        shape((r,), F32, "data"), shape((r,), F32, "data"),
        shape((28,), F32), mesh=mesh, subtract=None).compile().as_text()
    assert text.count("tpu_custom_call") >= 2      # histogram AND split
    assert "all-reduce" in text and "all-gather" not in text


def test_gbt_higgs_x4_rounds_compile_sharded_with_one_all_reduce_a_level(
        topo, monkeypatch):
    """The benchmark's `gbt-higgs-x4.train` call as `build_gbt` makes it:
    two rounds of depth 8 over 2^27 rows divided over the four chips, at
    the real size. Nine all-reduces (one a level, the leaf level's too)
    and no other collective: an all-gather would be a row-sized array
    left replicated. A chip holds its quarter of the rows and of the row
    state: 4.70 GB of arguments (the 28 columns lie in 32 sublanes),
    under 2.5 GB of temporaries."""
    from shifu_tpu.models import gbdt
    from tests.test_gbt_mesh import _collectives
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))

    def shape(dims, dtype, *spec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    r, depth = 2 ** 27, 8
    cfg = gbdt.TreeConfig(max_depth=depth, n_bins=64, loss="log",
                          learning_rate=0.1, min_instances_per_node=1)
    compiled = gbdt._gbt_rounds.lower(
        cfg, shape((28, r), I32, None, "data"), shape((r,), F32, "data"),
        shape((r,), F32, "data"), shape((r,), F32, "data"),
        shape((28,), F32), 2, mesh=mesh, subtract=True).compile()
    text = compiled.as_text()
    assert _collectives(text) == {
        "all-reduce": depth + 1, "all-gather": 0, "reduce-scatter": 0,
        "all-to-all": 0, "collective-permute": 0}
    assert text.count("tpu_custom_call") >= 2 * depth + 1
    memory = compiled.memory_analysis()
    # bins (28 columns in 32 sublanes: the (8, 128) tile), y, w, pred
    quarter = (32 * 4 + 3 * 4) * (r // 4)
    assert memory.argument_size_in_bytes < 1.01 * quarter
    assert memory.temp_size_in_bytes < 2.5e9


def test_gbt_higgs_rounds_write_nothing_row_sized_for_the_kernel(
        one_chip, monkeypatch):
    """The benchmark's `gbt-higgs.train` call as `build_gbt` makes it on
    one chip: two rounds of depth 8 over 28 x 2^24. Slot, gradient and
    hessian reach the nine histogram calls of a tree as (32768, 512)
    views of the (rows,) vectors: the program holds no (8, rows) block,
    and nothing that feeds a histogram kernel (through views and copies)
    is a row-sized `dynamic-update-slice` or `pad`. The packed [slot,
    grad, hess] operand was 17 + 9 of them a tree, 40 ms of 451
    (PERF.md, PR 27 / PR 36)."""
    import re
    from shifu_tpu.models import gbdt
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    r, depth = 2 ** 24, 8

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = gbdt.TreeConfig(max_depth=depth, n_bins=64, loss="log",
                          learning_rate=0.1, min_instances_per_node=1)
    text = gbdt._gbt_rounds.lower(
        cfg, shape((28, r), I32), shape((r,), F32), shape((r,), F32),
        shape((r,), F32), shape((28,), F32), 2, mesh=None,
        subtract=True).compile().as_text()
    assert f"f32[8,{r}]" not in text
    made = {m.group(1): (m.group(2), m.group(3), m.group(4))
            for m in re.finditer(
                r"\n\s*(?:ROOT )?%([\w.-]+) = (\S+) ([\w-]+)\(([^\n]*)", text)}
    kernels = [n for n, (_, op, rest) in made.items()
               if op == "custom-call" and "tpu_custom_call" in rest
               and "_level_histograms" in n]
    assert len(kernels) == depth + 1, kernels
    through = {"bitcast", "reshape", "copy", "copy-start", "copy-done",
               "get-tuple-element"}
    seen, todo = set(), list(kernels)
    while todo:
        rest = made[todo.pop()][2]
        for name in re.findall(r"%([\w.-]+)", rest.split("),")[0]):
            if name in seen or name not in made:
                continue
            seen.add(name)
            result, feeder, _ = made[name]
            row_sized = int(np.prod([int(d) for d in re.findall(
                r"\d+", result.split("{")[0].split("[", 1)[-1])] or [0])) >= r
            assert not (row_sized and feeder in ("dynamic-update-slice",
                                                 "pad")), (name, result)
            if feeder in through:
                todo.append(name)
    assert any(made[n][0].startswith("f32[32768,512]") for n in seen)


def test_wdl_table_step_compiles_and_fits(one_chip):
    """The lookup of a batch in a lane-packed embedding table of the
    benchmark's `wdl-criteo` size, its gradient's scatter-add and a dense
    AdaGrad pass: the chip's compiler takes it, keeps the table one
    128-lane row a packed row (no padded copy: a (rows, 4, 32) view would
    be tiled to eight times its size), and its temporaries stay a few
    table sizes."""
    from shifu_tpu.models import wdl
    rows, e, batch, cols = 8_440_680, 32, 16_384, 26
    packed = (-(-rows // (wdl.LANES // e)), wdl.LANES)

    def step(table, acc, ids, proj):
        def loss(t):
            return jnp.sum(wdl.lookup(t, ids, e) * proj)
        g = jax.grad(loss)(table)
        acc = acc + g * g
        return table - 0.01 * g * jax.lax.rsqrt(acc + 1e-7), acc

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (packed, F32), (packed, F32), ((batch, cols), I32),
        ((batch, cols, e), F32))]
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert "f32[%d,%d]{1,0" % packed in text, "the table is not row-major"
    table_bytes = packed[0] * packed[1] * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * table_bytes


# (hidden widths, activations, loss, output activation, bags): the
# benchmark's nn-higgs net, two narrow layers, the widest the kernel
# serves, three bags under vmap, a width that is no sublane multiple
MLP_CASES = [((64,), ("tanh",), "squared", "sigmoid", 1),
             ((16, 8), ("relu", "relu"), "log", "sigmoid", 1),
             ((128, 128), ("sigmoid", "leakyrelu"), "absolute", "linear", 1),
             ((64,), ("tanh",), "squared", "sigmoid", 3),
             ((50,), ("tanh",), "log", "tanh", 1)]


@pytest.mark.parametrize("hidden,acts,loss,out_act,bags", MLP_CASES)
def test_mlp_kernel_compiles(one_chip, hidden, acts, loss, out_act, bags):
    """`ops/pallas_mlp.py`: loss and gradient of a narrow MLP over 10^6
    rows laid out by `lay_rows`, as `train_bags_carry` differentiates
    it under its vmap over bags: the (F8, rows) matrix shared, the
    parameters and the weights a bag each."""
    from shifu_tpu.models import nn as nn_mod
    from shifu_tpu.ops import pallas_mlp
    spec = nn_mod.MLPSpec(28, hidden, acts, loss=loss,
                          output_activation=out_act)
    assert pallas_mlp.serves(spec)
    rp = -(-1_000_000 // pallas_mlp.ROW_TILE) * pallas_mlp.ROW_TILE
    chunks = (rp // pallas_mlp.CHUNK, pallas_mlp.CHUNK)
    params = jax.eval_shape(lambda: jax.vmap(
        lambda k: nn_mod.init_params(spec, k))(
            jax.random.split(jax.random.PRNGKey(0), bags)))

    def step(params, xT, y, w):
        return jax.vmap(lambda p, ww: jax.value_and_grad(
            lambda q: pallas_mlp.loss(spec, q, xT, y, ww))(p))(params, w)

    shapes = [(a.shape, a.dtype) for a in jax.tree.leaves(params)]
    flat = jax.tree.structure(params)
    _compile(lambda xT, y, w, *leaves: step(
        jax.tree.unflatten(flat, leaves), xT, y, w), one_chip,
        ((32, rp), F32), (chunks, F32), ((bags,) + chunks, F32), *shapes)


def test_mlp_kernel_compiles_at_its_widest_input_and_not_past_it(one_chip):
    """`pallas_mlp.MAX_INPUT`: a grid step reads a (F8, ROW_TILE) block
    of the inputs, double-buffered, F8 x 128 KiB of VMEM. At MAX_INPUT
    inputs the kernel compiles; at the next sublane tile the compiler
    refuses it for VMEM, which is why `serves` stops there."""
    from shifu_tpu.models import nn as nn_mod
    from shifu_tpu.ops import pallas_mlp
    rp = -(-400_000 // pallas_mlp.ROW_TILE) * pallas_mlp.ROW_TILE
    chunks = (rp // pallas_mlp.CHUNK, pallas_mlp.CHUNK)
    for inputs, fits in ((pallas_mlp.MAX_INPUT, True),
                         (pallas_mlp.MAX_INPUT + 8, False)):
        spec = nn_mod.MLPSpec(inputs, (50,), ("tanh",))
        params = jax.eval_shape(
            lambda: nn_mod.init_params(spec, jax.random.PRNGKey(0)))
        flat = jax.tree.structure(params)

        def step(xT, y, w, *leaves):
            return jax.value_and_grad(lambda q: pallas_mlp.loss(
                spec, q, xT, y, w))(jax.tree.unflatten(flat, leaves))

        shapes = [((-(-inputs // 8) * 8, rp), F32), (chunks, F32),
                  (chunks, F32)] + [(a.shape, a.dtype)
                                    for a in jax.tree.leaves(params)]
        if fits:
            _compile(step, one_chip, *shapes)
        else:
            with pytest.raises(Exception, match="(?i)vmem"):
                _compile(step, one_chip, *shapes)


def test_se_epsilon_ablation_compiles_inside_its_bytes(one_chip,
                                                     monkeypatch):
    """`processor/sensitivity.shifu_ablate` at the benchmark's
    `se-epsilon`: 400,000 rows x 2,000 columns under a 50-unit tanh
    layer on a one-chip TPU. The first layer's sums read x in place (no
    operand copy of it, which would be 1.6 GB), and the temporaries are
    at most the sums (80 MB) and a block's work: nothing of (columns,
    rows, ...), which would be 3.2 GB a hidden unit. Every op carries
    the scope `ablate`."""
    from shifu_tpu.models import nn as nn_mod
    from shifu_tpu.processor import sensitivity
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, cols = 400_000, 2_000
    spec = nn_mod.MLPSpec(cols, (50,), ("tanh",))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: nn_mod.init_params(spec,
                                                  jax.random.PRNGKey(0))))
    x = jax.ShapeDtypeStruct((rows, cols), F32, sharding=one_chip)
    compiled = sensitivity.shifu_ablate.lower(
        spec, params, x, rows,
        sensitivity.ablate_blocks(rows, cols, 50)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (
        4 * 50 * rows + 2 * sensitivity.BLOCK_BYTES)
    assert "ablate" in compiled.as_text()


def test_nn_higgs_epoch_program_holds_the_kernel_and_no_activation(
        one_chip, monkeypatch):
    """The benchmark's `nn-higgs.train` job as `train_nn` builds it on a
    one-chip TPU: 100 full-batch epochs of 28-64-1 over 10.5 M rows. The
    epoch's loss and gradient are ONE Mosaic call, named after the
    kernel inside the scope `forward_loss` (what a profiler trace and
    `benchmark/layer_metrics/mlp_kernel_share.py` find it by), and no
    (rows, 64) activation lives in HBM: XLA's own program keeps 3.52 GB
    of temporaries there (PERF.md section 4), this one under 0.6 GB."""
    import re
    from shifu_tpu.models import nn as nn_mod
    from shifu_tpu.ops import pallas_mlp
    from shifu_tpu.train import trainer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = nn_mod.MLPSpec(28, (64,), ("tanh",))
    rows, val_rows, bags = 10_500_000, 500_000, 1

    def shape(dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    optimizer = trainer.optimizer_from_params({"Propagation": "ADAM",
                                               "LearningRate": 0.05})
    keys = jax.random.split(jax.random.PRNGKey(0), bags)
    carry = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: trainer.init_train_carry(
            optimizer, jax.vmap(lambda k: nn_mod.init_params(spec, k))(keys),
            keys)))
    loss_fn, metric_fn = trainer.nn_objectives(spec, True)
    rp = -(-rows // pallas_mlp.ROW_TILE) * pallas_mlp.ROW_TILE
    chunks = (rp // pallas_mlp.CHUNK, pallas_mlp.CHUNK)
    compiled = trainer.train_bags_carry.lower(
        loss_fn, metric_fn, optimizer, 100, 0, 0.0, carry,
        (shape((32, rp)), shape(chunks)), shape((bags,) + chunks),
        (shape((val_rows, 28)), shape((val_rows,))), shape((val_rows,)),
        None).compile()
    text = compiled.as_text()
    calls = {m.group(1): m.group(2) for m in re.finditer(
        r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', text)}
    assert len(calls) == 1, calls
    (name, op_name), = calls.items()
    assert "shifu_mlp_loss_grad" in name
    assert "/forward_loss/" in op_name and op_name.endswith("/pallas_call")
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.6e9
    # xT in 32 sublanes, y, w, the validation rows: no second copy
    assert memory.argument_size_in_bytes < 1.01 * 4 * (
        34 * rp + 30 * val_rows)


def test_rf_higgs_group_compiles_with_one_bin_matrix_inside_its_bytes(
        one_chip, monkeypatch):
    """The benchmark's `rf-higgs.train` lockstep group as `build_rf`
    makes it on a 16 GB chip: four depth-10 trees over 2^24 rows
    (`gbdt._rf_grow`), and the draw beside it (`rf_draw.bags`). Mosaic
    takes the histogram kernel under `vmap` at every level's slots, 256
    and the leaf level's 512 on one column among them: 21 calls, a
    histogram pass and a split search a level and the leaf pass. The bin
    matrix is an unbatched operand of the batched `pallas_call` and stays
    ONE copy (no (4, 28|32, rows) array outside a fusion; the arguments
    are the table, the group's instance weights and nothing else). The
    program's bytes stay under what `gbdt.rf_group_bytes` reckons, from
    which `build_rf` sizes its groups and which says 8 trees would need
    17.9 GB. Since PR 36 that is an over-estimate: slot, gradient and
    hessian reach the kernel as views, no packed (4, 8, rows) operand
    is held twice, and the compiler finds 4,758,324,224 B held
    (2,550,138,880 of arguments + 2,208,185,344 of temporaries: 36.9 B
    a row a tree beside the table) for the 10,066,329,600 reckoned;
    eight trees would hold 7,218,682,880 B. The reckoning stays until
    the configuration's `lockstep_group_trees` can move with it
    (ROADMAP R3 (c))."""
    import re
    from shifu_tpu.models import gbdt, rf_draw
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    r, c, depth, group = 2 ** 24, 28, 10, 4

    def shape(dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = gbdt.TreeConfig(max_depth=depth, n_bins=64)
    compiled = gbdt._rf_grow.lower(
        cfg, shape((c, r), I32), shape((r,)), shape((r,)),
        shape((group, r)), shape((group, c)), mesh=None,
        subtract=True).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 * depth + 1
    entry = text[text.index("ENTRY"):]
    assert not re.search(rf"= s32\[{group},(28|32),{r}\]", entry), \
        "the bin matrix was copied a tree"
    memory = compiled.memory_analysis()
    table = (32 * 4 + 2 * 4) * r
    assert memory.argument_size_in_bytes < 1.01 * (table + group * 4 * r)
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    reckoned = gbdt.rf_group_bytes(group, r, c)
    assert held < 1.02 * reckoned, (held, reckoned)
    assert gbdt.rf_group_bytes(8, r, c) > 16_909_336_064

    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    drawn = rf_draw.bags.lower(key, shape((group,), I32), r,
                               rf_draw.poisson_thresholds(1.0)).compile()
    assert drawn.memory_analysis().temp_size_in_bytes < 4 * r


@pytest.mark.parametrize("group", [4, 1])
def test_gbt_higgs_bagged_round_and_validation_compile_inside_their_bytes(
        one_chip, monkeypatch, group):
    """The benchmark's `gbt-higgs-bagged.train` job as `build_gbt_bagged`
    grows it on a 16 GB chip: five bags of depth-8 log-loss trees over
    2^24 rows in groups of 4 and 1 (`gbdt._rf_group_trees`), a round of
    a group (`gbdt._gbt_bagged_round`: 17 kernel calls, a histogram pass
    and a split search a level and the leaf pass) and the group's
    validation program over 2^22 rows
    (`tree_validation.shifu_validate`). The round's and the validation
    program's bytes, with what the job holds of the other group (its
    predictions and weights, its raw scores), stay under what
    `gbdt.rf_group_bytes` reckons for the group, from which the builder
    sizes its groups: a round of four holds 2.82 GB of arguments and
    1.97 GB of temporaries for 11.39 GB reckoned. Both programs look a
    tree's 511 leaf values up on its own (rows,) vector: no 511-slot
    reduction carries a unit or group axis ahead of the rows, which the
    compiler lays second-minor (a (4, 128) or (1, 128) tile: the vector
    unit at half or an eighth of its rows)."""
    import re
    from shifu_tpu.models import gbdt, tree_validation
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    r, c, v, depth, bags = 2 ** 24, 28, 2 ** 22, 8, 5

    def shape(dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = gbdt.TreeConfig(max_depth=depth, n_bins=64, learning_rate=0.1,
                          loss="log")
    compiled = gbdt._gbt_bagged_round.lower(
        cfg, shape((c, r), I32), shape((r,)), shape((r,)),
        shape((group, r)), shape((group, r)), shape((group, c)), mesh=None,
        subtract=True).compile()
    round_text = compiled.as_text()
    assert round_text.count("tpu_custom_call") == 2 * depth + 1
    round_memory = compiled.memory_analysis()
    trees = jax.tree.map(lambda a: shape((group,) + a.shape, a.dtype),
                         jax.eval_shape(lambda: gbdt._empty_tree(cfg)))
    validated = tree_validation.shifu_validate.lower(
        cfg, trees, shape((c, v), I32), shape((group, v)), shape((v,)),
        shape((v,))).compile()
    val_text = validated.as_text()
    assert "shifu_validate" in val_text
    # a depth-8 walk takes the select form at every level and for its
    # 511 leaf values: nothing is gathered per row
    assert gbdt.walk_levels(depth) == {"walk_select_levels": depth,
                                       "walk_gather_levels": 0}
    assert not re.search(r"= \S+ gather\(", val_text), "the walk gathers"
    for text, rows in ((round_text, r), (val_text, v)):
        assert not re.search(rf"\[\d+,511,{rows}\]", text), \
            "a leaf lookup runs under a tree axis"
        assert f"[511,{rows}]" in text, "no 511-slot leaf lookup"
    val_memory = validated.memory_analysis()
    others = bags - group if group < bags else 0
    held = (round_memory.argument_size_in_bytes
            + round_memory.temp_size_in_bytes
            + val_memory.argument_size_in_bytes
            + val_memory.temp_size_in_bytes + others * (8 * r + 4 * v))
    reckoned = gbdt.rf_group_bytes(group, r, c, n_bags=bags,
                                   val_rows=v)
    assert held < reckoned, (held, reckoned)
    # the walk goes a tree at a time: its temporaries are one tree's,
    # whatever the group (the gather walk batched over trees held
    # 2.25 GB at 4 and 16.2 GB at 5: a group that is no power of two)
    assert val_memory.temp_size_in_bytes < 0.1e9
