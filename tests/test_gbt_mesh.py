"""The resident tree builders on a data mesh, on four of the rig's
virtual CPU devices: a device input carries its own mesh, the row state
is sharded by statement, a level is one all-reduce and nothing else
crosses the chips.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from shifu_tpu.models import gbdt
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.parallel import mesh as mesh_mod

CHIPS = 4
R, C, B = 4000, 6, 16
CFG = gbdt.TreeConfig(max_depth=4, n_bins=B, loss="log")


@pytest.fixture(scope="module")
def mesh():
    return mesh_mod.make_mesh(n_data=CHIPS, devices=jax.devices()[:CHIPS])


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(30)
    bins = rng.integers(0, B - 1, (R, C)).astype(np.int32)
    bins[rng.random((R, C)) < 0.01] = B - 1                 # missing
    y = (rng.random(R) < 0.25 + 0.4 * (bins[:, 0] > 7)
         + 0.2 * (bins[:, 3] < 4)).astype(np.float32)
    return np.ascontiguousarray(bins.T), y, np.ones(R, np.float32)


def _placed(mesh, rows):
    binsT, y, w = rows
    by_row = NamedSharding(mesh, P("data"))
    return (jax.device_put(binsT, NamedSharding(mesh, P(None, "data"))),
            jax.device_put(y, by_row), jax.device_put(w, by_row))


def _on_one_device(rows):
    return tuple(jax.device_put(a, jax.devices()[0]) for a in rows)


def test_sharded_device_inputs_build_the_one_device_trees(mesh, rows):
    """The same trees from rows over four chips as from rows on one:
    every split (feature, bin, default direction, leaf or not) equal;
    gains and leaf values to the float32 sum-order tolerance: a bin's
    sum over 4,000 rows is four partial sums added, not one, which moves
    it by a few ulps (1e-6 relative), and a gain is a difference of
    squares of such sums, so 1e-4 relative with 1e-5 of room at zero."""
    t4, _ = gbdt.build_gbt(CFG, *_placed(mesh, rows), n_trees=3)
    t1, _ = gbdt.build_gbt(CFG, *_on_one_device(rows), n_trees=3)
    for k in ("feature", "bin", "default_left", "is_leaf"):
        np.testing.assert_array_equal(t4[k], t1[k], err_msg=k)
    for k in ("gain", "leaf_value"):
        np.testing.assert_allclose(t4[k], t1[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)
    assert (t1["feature"] >= 0).sum() >= 20      # the trees do split


def test_the_chips_local_histograms_add_up_to_the_unsharded_one(mesh, rows):
    binsT, y, _ = rows
    rng = np.random.default_rng(31)
    node = rng.integers(0, 5, R).astype(np.int32)      # 4 is out of level
    grad = rng.normal(size=R).astype(np.float32)
    hess = rng.random(R).astype(np.float32)
    whole = gbdt._level_histograms(jnp.asarray(binsT), jnp.asarray(node),
                                   jnp.asarray(grad), jnp.asarray(hess),
                                   0, 4, B)
    local = [gbdt._level_histograms(
        jnp.asarray(binsT[:, s]), jnp.asarray(node[s]), jnp.asarray(grad[s]),
        jnp.asarray(hess[s]), 0, 4, B)
        for s in (slice(i * R // CHIPS, (i + 1) * R // CHIPS)
                  for i in range(CHIPS))]
    placed = _placed(mesh, (binsT, grad, hess))
    reduced = jax.jit(lambda b, n, g, h: gbdt._level_histograms(
        b, n, g, h, 0, 4, B, mesh=mesh))(
        placed[0], jax.device_put(node, placed[1].sharding), *placed[1:])
    for k in range(2):                                  # G, then H
        summed = sum(np.asarray(part[k], np.float64) for part in local)
        np.testing.assert_allclose(summed, whole[k], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(reduced[k], summed, rtol=1e-5, atol=1e-5)


def _collectives(text):
    return {kind: len(re.findall(rf"= \S+ {kind}(?:-start)?\(", text))
            for kind in ("all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute")}


@pytest.mark.parametrize("subtract", [True, False])
def test_round_program_holds_one_all_reduce_a_level_and_nothing_else(
        mesh, rows, subtract):
    """The compiled rounds (a scan: its body is there once) reduce once a
    level, leaf level included, and exchange nothing else: no all-gather
    of anything, which is what a row-sized array left replicated would
    cost every round."""
    jb, jy, jw = _placed(mesh, rows)
    text = gbdt._gbt_rounds.lower(
        CFG, jb, jy, jw, gbdt._zeros_by_row((R,), mesh), jnp.ones(C), 3,
        mesh=mesh, subtract=subtract).compile().as_text()
    assert _collectives(text) == {
        "all-reduce": CFG.max_depth + 1, "all-gather": 0,
        "reduce-scatter": 0, "all-to-all": 0, "collective-permute": 0}
    scopes = {s for name in re.findall(r'op_name="([^"]*)"', text)
              for s in obs_trace.device_scopes(name)}
    assert "allreduce" in scopes
    # `psum_bytes` (the job span's attribute) is what those all-reduces
    # are handed: a growth loop that reduces other shapes fails here
    reduced = re.findall(r"= f32\[([\d,]+)\]\S* all-reduce(?:-start)?\(", text)
    assert len(reduced) == CFG.max_depth + 1
    assert sum(4 * int(np.prod([int(d) for d in dims.split(",")]))
               for dims in reduced) == gbdt.psum_bytes(CFG, C, mesh, subtract)


def test_row_state_and_trees_leave_the_program_as_stated(mesh, rows):
    jb, jy, jw = _placed(mesh, rows)
    trees, pred = gbdt._gbt_rounds(
        CFG, jb, jy, jw, gbdt._zeros_by_row((R,), mesh), jnp.ones(C), 2,
        mesh=mesh, subtract=True)
    assert pred.sharding.is_equivalent_to(jy.sharding, 1)
    for leaf in jax.tree.leaves(trees):
        assert leaf.sharding.is_fully_replicated


def test_bagged_rounds_reduce_once_a_level_too(mesh, rows):
    jb, jy, jw = _placed(mesh, rows)
    w_T = jax.device_put(np.ones((2, R), np.float32),
                         NamedSharding(mesh, P(None, "data")))
    text = gbdt._gbt_bagged_rounds.lower(
        CFG, jb, jy, w_T, gbdt._zeros_by_row((2, R), mesh),
        jnp.ones((2, C)), 2, mesh=mesh, subtract=True).compile().as_text()
    found = _collectives(text)
    assert found["all-reduce"] == CFG.max_depth + 1
    assert sum(found.values()) == found["all-reduce"]


@pytest.mark.parametrize("layout", ["replicated", "columns", "model_axis",
                                    "undivided"])
def test_a_device_input_laid_out_otherwise_raises(mesh, rows, layout):
    binsT, y, w = rows
    if layout == "replicated":
        bad = jax.device_put(binsT, NamedSharding(mesh, P()))
    elif layout == "columns":
        bad = jax.device_put(binsT[:4], NamedSharding(mesh, P("data", None)))
    elif layout == "model_axis":
        other = mesh_mod.make_mesh(n_data=1, n_model=CHIPS,
                                   devices=jax.devices()[:CHIPS])
        bad = jax.device_put(binsT, NamedSharding(other, P(None, "model")))
    else:       # rows the axis does not divide cannot be divided over it
        bad = jax.device_put(binsT[:, :R - 2], NamedSharding(mesh, P()))
    n = bad.shape[1]
    with pytest.raises(ValueError, match=r"rows \(axis 1, a multiple of the "
                       r"axis size\) divided over the 'data' axis"):
        gbdt.build_gbt(CFG, bad, y[:n], w[:n], n_trees=1)


def test_per_row_inputs_beside_device_bins(mesh, rows):
    """Host labels and weights are placed by row beside sharded bins; a
    device array that lies otherwise over the chips raises."""
    jb, jy, jw = _placed(mesh, rows)
    from_host, _ = gbdt.build_gbt(CFG, jb, rows[1], rows[2], n_trees=1)
    placed, _ = gbdt.build_gbt(CFG, jb, jy, jw, n_trees=1)
    for k in placed:
        np.testing.assert_array_equal(from_host[k], placed[k])
    with pytest.raises(ValueError, match="divided over the 'data' axis"):
        gbdt.build_gbt(CFG, jb, jax.device_put(
            rows[1], NamedSharding(mesh, P())), jw, n_trees=1)


def test_a_one_device_input_builds_where_it_lies(rows):
    """One chip is the special case of the same lines: no histogram mesh,
    the one-device program, whatever other devices the host shows."""
    mesh, hist_mesh = gbdt._build_meshes(_on_one_device(rows)[0])
    assert hist_mesh is None and mesh.devices.size == 1
    trees, _ = gbdt.build_gbt(CFG, *_on_one_device(rows), n_trees=1)
    assert trees["feature"].shape == (1, CFG.n_nodes)


@pytest.mark.parametrize("depth,cols,subtract,n_trees,expected", [
    # G and H, float32: (1+1+2+4 slots x 6 columns + 8 slots x 1) x 16 bins
    (4, 6, True, 1, 2 * 4 * (8 * 6 + 8) * 16),
    (4, 6, False, 1, 2 * 4 * (15 * 6 + 16) * 16),
    (4, 6, True, 3, 3 * 2 * 4 * (8 * 6 + 8) * 16),
    # the benchmark's gbt-higgs-x4: 128 slots x 28 columns + 128 x 1
    (8, 28, True, 1, 2 * 4 * (128 * 28 + 128) * 64),
])
def test_psum_bytes_by_hand(mesh, depth, cols, subtract, n_trees, expected):
    cfg = gbdt.TreeConfig(max_depth=depth, n_bins=16 if depth == 4 else 64)
    assert gbdt.psum_bytes(cfg, cols, mesh, subtract, n_trees) == expected
    assert gbdt.psum_bytes(cfg, cols, None, subtract, n_trees) == 0


def _job_stats(tmp_path, job):
    from tests.test_train_spans import _profiled_spans
    return [stats for events in _profiled_spans(tmp_path, job).values()
            for name, _, _, stats in events if name == "shifu:train.job"]


@pytest.mark.parametrize("placed,chips", [(True, CHIPS), (False, 1)],
                         ids=["four_chips", "one_device"])
def test_job_span_carries_chips_and_psum_bytes(tmp_path, mesh, rows, placed,
                                               chips):
    data = _placed(mesh, rows) if placed else _on_one_device(rows)
    jobs = _job_stats(tmp_path,
                      lambda: gbdt.build_gbt(CFG, *data, n_trees=2))
    assert len(jobs) == 1
    assert int(jobs[0]["chips"]) == chips
    assert int(jobs[0]["psum_bytes"]) == gbdt.psum_bytes(
        CFG, C, mesh if placed else None, True)
    assert (int(jobs[0]["psum_bytes"]) > 0) == placed
