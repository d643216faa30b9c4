"""SE / ST sensitivity variable selection (`processor/sensitivity.py`;
tier-1, CPU, small sizes, seeded random weights).

(a) `shifu_ablate` against the plain wipe-and-reforward: every column set
    to 0 in x and `nn.forward` run again, for LR with no hidden layer, one
    tanh layer, two layers of different activations and a three-class
    softmax head, at jax's default
    precision and at `highest`, over a column count that is no multiple
    of the block and rows that are no multiple of theirs;
(b) under the 8-device test mesh with padding rows, the same numbers;
(c) `sensitivity_select`: ST is SE over the unwiped scores' variance, and
    the selection is the plain reference's top `filter_num`;
(d) the compiled ablation's temporary bytes do not grow with C^2;
(e) `shifu:train.ablate` beside the quick train's `shifu:train.job`,
    with its attributes, and the device scope `ablate`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import nn as nn_mod
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.parallel import mesh as mesh_mod
from shifu_tpu.processor import sensitivity
from tests.test_train_spans import _op_names, _profiled_spans, _scopes_of

SPECS = {
    "lr": nn_mod.MLPSpec(37, (), ()),
    "one-tanh": nn_mod.MLPSpec(37, (20,), ("tanh",)),
    "tanh-relu": nn_mod.MLPSpec(37, (20, 9), ("tanh", "relu")),
    # a multi-class head: a delta sums the squared change over outputs
    "softmax-3": nn_mod.MLPSpec(37, (20,), ("tanh",), output_dim=3,
                                output_activation="softmax"),
}
# 37 columns in blocks of 16 (16, 16 and a last block shifted back over
# 11 columns already done), 1,000 rows in blocks of 256
BLOCKS = (16, 256)


def _table(spec, rows=1_000, seed=2):
    kx, kp = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (rows, spec.input_dim))
    params = nn_mod.init_params(spec, kp)
    # biases off zero, so that a wiped row's every layer moves
    return x, jax.tree.map(lambda p: p + 0.05, params)


def _plain(spec, params, x, n_real):
    """Every column set to 0 in x and the whole forward pass run again."""
    base = nn_mod.forward(spec, params, x)[:n_real].reshape(n_real, -1)
    out = []
    for c in range(x.shape[1]):
        wiped = nn_mod.forward(spec, params, x.at[:, c].set(0.0))[:n_real]
        change = jnp.square(wiped.reshape(n_real, -1) - base)
        out.append(float(jnp.mean(jnp.sum(change, axis=1))))
    return np.asarray(out), float(np.var(np.asarray(base[:, 0], np.float64)))


# The cached form takes a column's term out of the float32 first-layer
# sums where the re-forward sums the other 36 terms afresh: the two
# differ by the rounding of a 37-term float32 sum, a few 1e-7 of |Z|,
# which moves a delta by about 2 |ds| 1e-7; at both precisions a CPU
# multiplies float32 operands exactly, so one tolerance holds for both:
# 1e-5 of the largest delta, and 1e-5 relative for the variance.
RTOL = 1e-5


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("case", sorted(SPECS))
def test_ablation_matches_the_plain_wipe_and_reforward(case, precision):
    spec = SPECS[case]
    x, params = _table(spec)
    with jax.default_matmul_precision(precision):
        got, var = sensitivity.shifu_ablate(spec, params, x, 900, BLOCKS)
        want, want_var = _plain(spec, params, x, 900)
    assert got.shape == (37,)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=RTOL * want.max())
    np.testing.assert_allclose(float(var), want_var, rtol=RTOL)
    # rows 900..999 are left out: with them the deltas differ
    assert not np.allclose(_plain(spec, params, x, 1_000)[0], want,
                           rtol=1e-3)


def test_the_mesh_pads_rows_and_adds_the_devices_partial_sums():
    """1,001 rows over the 8-device test mesh: seven zero rows pad the
    last device's share, and every device ablates its own rows."""
    spec = SPECS["tanh-relu"]
    x, params = _table(spec, rows=1_001, seed=5)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:8]), ("data",))
    xs = mesh_mod.shard_axis(mesh, x, 0)
    assert xs.shape[0] == 1_008
    blocks = sensitivity.ablate_blocks(126, 37, 20)
    got, var = sensitivity.shifu_ablate(spec, params, xs, 1_001, blocks,
                                        mesh)
    want, want_var = _plain(spec, params, x, 1_001)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=RTOL * want.max())
    np.testing.assert_allclose(float(var), want_var, rtol=RTOL)


def test_blocks_come_from_bytes():
    cb, rb = sensitivity.ablate_blocks(400_000, 2_000, 50)
    assert cb == sensitivity.COL_BLOCK and rb % 128 == 0
    assert 4 * cb * 50 * rb <= sensitivity.BLOCK_BYTES
    assert sensitivity.ablate_blocks(100, 7, 50) == (7, 100)


def _conf(epochs=24, hidden=(12,)):
    tc = ModelTrainConf()
    tc.numTrainEpochs = epochs
    tc.params = {"NumHiddenLayers": len(hidden),
                 "NumHiddenNodes": list(hidden),
                 "ActivationFunc": ["tanh"] * len(hidden),
                 "LearningRate": 0.05, "Propagation": "ADAM"}
    return tc


def _planted(n=3_000, c=24, seed=4):
    """Columns 0..7 carry the label with weights 2, 1.6, ... 0.6."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, c)).astype(np.float32)
    beta = np.zeros(c, np.float32)
    beta[:8] = np.linspace(2.0, 0.6, 8)
    y = (rng.random(n) < 1 / (1 + np.exp(-x @ beta))).astype(np.float32)
    return x, y, np.ones(n, np.float32)


def test_st_is_se_over_the_score_variance_and_selection_is_the_top():
    x, y, w = _planted()
    se = sensitivity.sensitivity_select(_conf(), x, y, w, seed=3,
                                        filter_num=6, by="SE")
    st = sensitivity.sensitivity_select(_conf(), x, y, w, seed=3,
                                        filter_num=6, by="ST")
    # the quick train: half of numTrainEpochs, one bag
    assert se.train.train_errors.shape == (1, 12)
    params = jax.tree.map(jnp.asarray, se.train.params_per_bag[0])
    want, var = _plain(se.train.spec, params, jnp.asarray(x), len(y))
    np.testing.assert_allclose(se.deltas, want, rtol=0,
                               atol=RTOL * want.max())
    np.testing.assert_allclose(st.deltas, se.deltas / var, rtol=RTOL)
    top = np.argsort(-want, kind="stable")[:6]
    np.testing.assert_array_equal(se.selected, top)
    np.testing.assert_array_equal(st.selected, top)
    assert set(top) <= set(range(8)), "the planted columns lead"


def test_compiled_temporaries_grow_with_columns_not_their_square():
    """x is an argument; what the program keeps besides it is the first
    layer's sums and a block's work, and the deltas: a 4x wider table
    must not need 16x the temporaries."""
    spec_of = lambda c: nn_mod.MLPSpec(c, (50,), ("tanh",))  # noqa: E731
    temp = {}
    for cols in (256, 1_024):
        spec = spec_of(cols)
        params = jax.eval_shape(
            lambda: nn_mod.init_params(spec, jax.random.PRNGKey(0)))
        x = jax.ShapeDtypeStruct((4_096, cols), jnp.float32)
        blocks = sensitivity.ablate_blocks(4_096, cols, 50)
        compiled = sensitivity.shifu_ablate.lower(
            spec, params, x, 4_096, blocks).compile()
        temp[cols] = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp[1_024] <= 4 * 1.1 * temp[256], temp


def test_ablate_span_and_scope(tmp_path, monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_MESH_DEVICES", "1")
    x, y, w = _planted(n=600, c=10)
    by_line = _profiled_spans(tmp_path, lambda: sensitivity.sensitivity_select(
        _conf(epochs=20), x, y, w, seed=1, filter_num=4, by="ST"))
    (evs,) = [evs for evs in by_line.values()
              if any(n == "shifu:train.ablate" for n, *_ in evs)]
    jobs = [e for e in evs if e[0] == "shifu:train.job"]
    ablates = [e for e in evs if e[0] == "shifu:train.ablate"]
    assert len(jobs) == 1 and len(ablates) == 1
    (_, j0, j1, _), (_, a0, a1, stats) = jobs[0], ablates[0]
    assert j1 <= a0, "after the quick train, beside its job"
    assert stats["family"] == "varsel" and int(stats["rows"]) == 600
    assert int(stats["columns"]) == 10 and int(stats["col_block"]) == 10
    assert str(stats["hidden"]) == "12" and int(stats["filter_num"]) == 4
    assert stats["by"] == "ST"
    assert obs_trace.span_registered("train.ablate")

    spec = nn_mod.MLPSpec(10, (12,), ("tanh",))
    params = nn_mod.init_params(spec, jax.random.PRNGKey(0))
    text = sensitivity.shifu_ablate.lower(
        spec, params, jnp.asarray(x), 600, (10, 600)).compile().as_text()
    assert "ablate" in obs_trace.DEVICE_SCOPES
    assert "ablate" in _scopes_of(_op_names(text))
