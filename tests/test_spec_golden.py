"""Golden model-spec files: the npz container format
(`shifu_tpu/models/spec.py`) is the framework's cross-runtime model
binary — the analog of the reference's `.nn`/`.gbt` specs, which are
guarded by checked-in golden models scored in tests
(`core/dtrain/{NNModelEvalAndScore,TreeModelEvalAndScore,
IndependentTreeModel}Test.java`, SURVEY §4.5). These goldens pin:
(a) today's loader reads specs written by past rounds byte-for-byte,
(b) the portable (numpy-only) scorer reproduces the pinned scores.

Regenerate only on an INTENTIONAL format change (bump FORMAT_VERSION):
    python tests/test_spec_golden.py regen
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "tests", "golden")

KINDS = ("nn", "gbt", "rf", "wdl", "bagging")


def _probe_inputs(kind, rng):
    dense = rng.normal(0, 1, (20, 6)).astype(np.float32)
    index = rng.integers(0, 4, (20, 2)).astype(np.int32)
    # tree probes must SPAN the cut table (0.5..6.5) so every bin —
    # and hence real routing through mid/high splits — is exercised
    raw_dense = rng.uniform(0.0, 7.0, (20, 6)).astype(np.float32)
    raw_codes = rng.integers(0, 5, (20, 2)).astype(np.int32)
    return dense, index, raw_dense, raw_codes


def _build_spec(kind, rng):
    """A small deterministic model of each kind, built directly from
    the model modules (no pipeline — goldens pin the container, not
    training)."""
    import jax

    if kind in ("nn", "bagging"):
        from shifu_tpu.models import nn as nn_mod
        spec = nn_mod.MLPSpec(input_dim=6, hidden_dims=(5,),
                              activations=("tanh",))
        meta = {"spec": spec.to_dict() if hasattr(spec, "to_dict")
                else spec.__dict__, "inputNames": [f"x{i}" for i in
                                                   range(6)]}
        params = jax.tree.map(np.asarray,
                              nn_mod.init_params(spec,
                                                 jax.random.PRNGKey(3)))
        if kind == "nn":
            return "nn", meta, params
        members = [{"kind": "nn", "meta": meta}, {"kind": "nn",
                                                  "meta": meta}]
        p2 = jax.tree.map(lambda a: a * 0.5, params)
        return "bagging", {"members": members, "assemble": "mean"}, \
            {"m0": params, "m1": p2}
    if kind in ("gbt", "rf"):
        import jax.numpy as jnp
        from shifu_tpu.models import gbdt
        cfg = gbdt.TreeConfig(max_depth=3, n_bins=8, learning_rate=0.3,
                              loss="log" if kind == "gbt" else "squared")
        bins = rng.integers(0, 7, (500, 6)).astype(np.int32)
        y = (bins[:, 0] + bins[:, 1] > 6).astype(np.float32)
        w = np.ones(500, np.float32)
        binsT = jnp.asarray(bins.T)
        fm = jnp.ones(6, jnp.float32)
        if kind == "gbt":
            trees, _ = gbdt.build_gbt(cfg, binsT, jnp.asarray(y),
                                      jnp.asarray(w), n_trees=3)
        else:
            gT = jnp.asarray(np.stack([y * w, y * w]))
            hT = jnp.asarray(np.stack([w, w]))
            trees = {k: np.asarray(v) for k, v in gbdt.build_forest(
                cfg, binsT, gT, hT, jnp.ones((2, 6), jnp.float32)).items()}
        # the tree-spec layout the trainers persist (train_tree.py:160):
        # params = {"trees": ..., "tables": {"num_cuts", "cat_map"}}
        num_cuts = np.linspace(0.5, 6.5, cfg.n_bins - 2)[:, None] \
            .repeat(6, 1).astype(np.float32)
        tables = gbdt.make_bin_tables(num_cuts, [], cfg.n_bins)
        meta = {"kind": kind,
                "treeConfig": {"max_depth": cfg.max_depth,
                               "n_bins": cfg.n_bins,
                               "learning_rate": cfg.learning_rate,
                               "loss": cfg.loss},
                "denseNames": [f"x{i}" for i in range(6)],
                "indexNames": []}
        return kind, meta, {"trees": {k: np.asarray(v)
                                      for k, v in trees.items()},
                            "tables": tables}
    if kind == "wdl":
        import jax
        from shifu_tpu.models import wdl
        spec = wdl.WDLSpec(dense_dim=6, n_cat=2, vocab_sizes=(5, 5),
                           embed_size=3, hidden_dims=(4,),
                           activations=("relu",))
        params = wdl.file_params(spec, jax.tree.map(
            np.asarray, wdl.init_params(spec, jax.random.PRNGKey(5))))
        meta = {"spec": spec.__dict__,
                "denseNames": [f"x{i}" for i in range(6)],
                "indexNames": ["c0", "c1"]}
        return "wdl", meta, params
    raise ValueError(kind)


def _score(kind, meta, params, rng):
    from shifu_tpu.portable import score_model
    dense, index, raw_dense, raw_codes = _probe_inputs(kind, rng)
    if kind in ("gbt", "rf"):
        # tree portable scorer bins the raw floats through the spec's
        # cut table itself
        return score_model(kind, meta, params, raw_dense=raw_dense,
                           raw_codes=None)
    if kind == "wdl":
        return score_model(kind, meta, params, dense=dense, index=index)
    return score_model(kind, meta, params, dense=dense)


@pytest.mark.parametrize("kind", KINDS)
def test_spec_golden_loads_and_scores(kind):
    from shifu_tpu.models.spec import load_model
    path = os.path.join(GOLDEN, f"{kind}.spec")
    assert os.path.exists(path), \
        "golden missing — run: python tests/test_spec_golden.py regen"
    k, meta, params = load_model(path)
    assert k == kind
    side = json.load(open(os.path.join(GOLDEN, f"{kind}.spec.json")))
    rng = np.random.default_rng(1234)
    got = _score(kind, meta, params, rng)
    np.testing.assert_allclose(got, np.asarray(side["scores"]),
                               rtol=1e-5, atol=1e-6)


def regen():
    from shifu_tpu.models.spec import save_model
    os.makedirs(GOLDEN, exist_ok=True)
    for kind in KINDS:
        rng = np.random.default_rng(42)
        k, meta, params = _build_spec(kind, rng)
        path = os.path.join(GOLDEN, f"{kind}.spec")
        save_model(path, k, meta, params)
        rng = np.random.default_rng(1234)
        scores = _score(k, meta, params, rng)
        with open(path + ".json", "w") as f:
            json.dump({"scores": np.asarray(scores).tolist()}, f)
        print(f"golden spec {kind}: {os.path.getsize(path)} bytes")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "regen":
        regen()
