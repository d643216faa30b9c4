"""Shape-bucket padding + AOT compilation for the serving plane.

Every distinct row count a scorer sees compiles its own XLA
executable.  Serving traffic (and the ragged final chunk of a batch
eval) would otherwise compile an unbounded set of shapes; instead all
scoring here rounds the row count up a small geometric ladder
(``SHIFU_TPU_SERVE_BUCKETS``, default ``1,8,64,512``) so steady state
touches a fixed, pre-warmable set of shapes and never recompiles.

Padding semantics — the padded rows REPEAT THE LAST REAL ROW rather
than zero-fill.  That choice is load-bearing for bit parity:
`convert_tree_score`'s MAXMIN strategy rescales by the batch-global
min/max, so a padded row with a novel score would change every real
row's converted score.  A duplicated row can never move a min or a
max, and every per-row model is row-independent, so WITHIN a bucket
the amount of padding is bit-invisible: any two calls that land on
the same bucket run the same executable and score identical rows
identically.  Compared to an UNPADDED call at a different shape, XLA's
shape-dependent scheduling (gemm tiling, per-device shard sizes) can
move float results by ~1 ulp — which is why batch eval routes through
this same helper: serving and eval then score at the same bucket
shapes and stay bit-identical to each other.

AOT warm-up has two gears:

* `warm_scores` drives a dummy padded batch per bucket through the
  REAL scoring entrypoint (``Scorer.score`` → ``score_matrix``), which
  populates exactly the jit/executable caches steady-state requests
  will hit — including the PR-6 fused Pallas path when routed.
* `aot_compile` additionally pre-lowers+compiles the NN-family forward
  per model × bucket via ``jit(...).lower().compile()``.  With the
  PR-5 persistent compile cache enabled the lowered HLO hashes into
  the on-disk cache, so a second process start pays a cache read
  instead of a compile; the compiled executable is also checked
  against the interpretive path on the warm-up batch, making the AOT
  artifact a self-test rather than dead weight.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from shifu_tpu.config import environment as env

DEFAULT_LADDER = (1, 8, 64, 512)


def bucket_ladder() -> Tuple[int, ...]:
    """Parse SHIFU_TPU_SERVE_BUCKETS → ascending unique positive ints;
    malformed entries fall back to the default ladder (warn-and-run,
    matching the knob registry's philosophy)."""
    raw = env.knob_str("SHIFU_TPU_SERVE_BUCKETS")
    try:
        vals = sorted({int(tok) for tok in raw.split(",") if tok.strip()})
        if not vals or vals[0] <= 0:
            raise ValueError(raw)
        return tuple(vals)
    except ValueError:
        return DEFAULT_LADDER


def bucket_for(n: int, ladder: Optional[Tuple[int, ...]] = None) -> int:
    """Smallest bucket ≥ n; past the top rung, keep doubling the top
    bucket (bounded distinct shapes for any request size)."""
    if n <= 0:
        raise ValueError(f"cannot bucket {n} rows")
    ladder = ladder or bucket_ladder()
    for b in ladder:
        if n <= b:
            return b
    b = ladder[-1]
    while b < n:
        b *= 2
    return b


def pad_rows(block: np.ndarray, bucket: int) -> np.ndarray:
    """Pad axis 0 to `bucket` rows by repeating the last row (see
    module docstring for why not zeros)."""
    n = block.shape[0]
    if n == bucket:
        return block
    if n > bucket:
        raise ValueError(f"{n} rows exceed bucket {bucket}")
    reps = np.repeat(block[-1:], bucket - n, axis=0)
    return np.concatenate([np.asarray(block), reps], axis=0)


def pad_blocks(blocks: Dict[str, Optional[np.ndarray]],
               bucket: int) -> Dict[str, Optional[np.ndarray]]:
    return {k: (pad_rows(v, bucket) if v is not None else None)
            for k, v in blocks.items()}


def _slice_tree(out: Any, n: int) -> Any:
    """Slice the pad back off every array leaf of a score result
    (dict for Scorer.score, tuple for score_multiclass)."""
    if isinstance(out, dict):
        return {k: _slice_tree(v, n) for k, v in out.items()}
    if isinstance(out, (tuple, list)):
        return type(out)(_slice_tree(v, n) for v in out)
    a = np.asarray(out)
    return a[:n] if a.ndim >= 1 else a


def padded_call(score_fn: Callable[..., Any], n: int,
                blocks: Dict[str, Optional[np.ndarray]],
                ladder: Optional[Tuple[int, ...]] = None,
                **kw: Any) -> Any:
    """Pad every row block up to `n`'s bucket, score through `score_fn`
    (row blocks as keyword args, plus passthrough kwargs like `norm`),
    and slice the result back to `n` rows."""
    bucket = bucket_for(n, ladder)
    out = score_fn(**pad_blocks(blocks, bucket), **kw)
    return _slice_tree(out, n)


def eval_pad_enabled() -> bool:
    return env.knob_bool("SHIFU_TPU_EVAL_PAD_BUCKETS")


def warm_scores(score_fn: Callable[[Dict[str, Optional[np.ndarray]]], Any],
                proto: Dict[str, Optional[np.ndarray]],
                ladder: Tuple[int, ...]) -> int:
    """Drive one real scoring call per bucket using rows tiled from the
    prototype blocks, so every executable steady state needs is built
    (or read from the persistent compile cache) up front. `score_fn`
    takes the padded row blocks and MUST be the very function steady
    traffic goes through (the service's placement + `scorer.score`):
    a warm-up that feeds host arrays where traffic feeds pre-placed
    device arrays leaves the pad/reshard programs of the device path
    to compile under the first real requests. Returns the number of
    buckets warmed."""
    for bucket in ladder:
        score_fn(pad_blocks(proto, bucket))
    return len(ladder)


def _tree_fused_blocks(meta: Dict[str, Any], params: Any,
                       raw_dense: Optional[np.ndarray],
                       raw_codes: Optional[np.ndarray]) -> Tuple[
                           np.ndarray, Any, Any, Dict[str, Any]]:
    """Derive the fused tree-kernel inputs for one GBT/RF model from
    its params + a raw request block pair: (packed node block,
    FusedBins valuesT, cuts, static kwargs for predict_ensemble)."""
    import jax

    from shifu_tpu.models import gbdt
    from shifu_tpu.ops import pallas_trees

    cfg_meta = meta["treeConfig"]
    n_bins = int(cfg_meta["n_bins"])
    tables = {"num_cuts": np.asarray(params["tables"]["num_cuts"]),
              "cat_map": np.asarray(params["tables"]["cat_map"])}
    fb = gbdt.make_fused_inputs(tables, raw_dense, raw_codes, n_bins)
    trees_np = jax.tree.map(np.asarray, params["trees"])
    packed, _ = pallas_trees.pack_ensemble(trees_np)
    statics = {"n_trees": int(trees_np["feature"].shape[0]),
               "loss": str(cfg_meta.get("loss", "squared")),
               "learning_rate": float(cfg_meta["learning_rate"]),
               "max_depth": int(cfg_meta["max_depth"]),
               "n_bins": n_bins}
    return packed, fb.valuesT, fb.cuts, statics


def aot_compile(scorer: Any, proto: Dict[str, Optional[np.ndarray]],
                ladder: Tuple[int, ...]) -> Tuple[
                    Dict[Tuple[int, int], Any], Dict[int, Any]]:
    """`jit(...).lower().compile()` per model × bucket.

    Returns ``(executables, device_params)``:
    ``executables[(model_index, bucket)]`` is a compiled executable
    whose params are RUNTIME ARGUMENTS, not baked closure constants —
    ``exe(params, x)`` for NN-family models, ``exe(nodes, valuesT,
    cuts)`` for tree models (the `ops/pallas_trees.predict_ensemble`
    kernel over the packed node block + FusedBins-style raw inputs) —
    and ``device_params[model_index]`` is the incumbent's pytree
    already placed on device.  Because an executable only fixes tree
    structure/shapes/dtypes, a model refresh can place new same-shaped
    params into the resident executables without touching XLA
    (`serve.service.ScorerService.swap_params`); shape or dtype
    changes fail the structural check there and fall back to a full
    evict/re-warm.  NN-family models need a ``dense`` proto block,
    tree models ``raw_dense`` (and ``raw_codes`` when categorical) —
    models whose blocks are absent, and kinds with no persistent
    executable (external SavedModels), are skipped; `warm_scores`
    covers them.  The lowered computations hash into the persistent
    XLA compile cache when `profiling.enable_compile_cache` is
    active, so the next process start of the same service compiles
    nothing.
    """
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models import nn as nn_mod

    out: Dict[Tuple[int, int], Any] = {}
    dev_params: Dict[int, Any] = {}
    for i, (kind, meta, params) in enumerate(scorer.models):
        if kind in ("nn", "lr") and proto.get("dense") is not None:
            input_dim = int(np.asarray(proto["dense"]).shape[1])
            sd = dict(meta["spec"])
            sd["hidden_dims"] = tuple(sd.get("hidden_dims", ()))
            sd["activations"] = tuple(sd.get("activations", ()))
            spec = nn_mod.MLPSpec(**sd)
            d_params = jax.tree.map(jnp.asarray, params)
            dev_params[i] = d_params

            def fwd(p, x, _spec=spec):
                return nn_mod.forward(_spec, p, x)

            # once-per-model AOT compile at service start — the loop IS
            # the compile site, not a hot path
            jitted = jax.jit(fwd)  # lint: disable=jit-in-loop -- AOT warmup compiles each model once at startup
            p_struct = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                d_params)
            for bucket in ladder:
                shape = jax.ShapeDtypeStruct((bucket, input_dim),
                                             jnp.float32)
                out[(i, bucket)] = jitted.lower(p_struct, shape).compile()
        elif kind in ("gbt", "rf") and proto.get("raw_dense") is not None:
            from shifu_tpu.ops import pallas_trees
            packed, valuesT, cuts, statics = _tree_fused_blocks(
                meta, params, np.asarray(proto["raw_dense"]),
                (None if proto.get("raw_codes") is None
                 else np.asarray(proto["raw_codes"])))
            dev_params[i] = jax.tree.map(jnp.asarray, params)
            interpret = jax.default_backend() != "tpu"

            def tfwd(nodes, vT, ct, _kind=kind, _st=statics,
                     _ip=interpret):
                return pallas_trees.predict_ensemble(
                    nodes, vT, ct, kind=_kind, interpret=_ip, **_st)

            jitted = jax.jit(tfwd)  # lint: disable=jit-in-loop -- AOT warmup compiles each model once at startup
            n_struct = jax.ShapeDtypeStruct(packed.shape, jnp.float32)
            c_struct = jax.ShapeDtypeStruct(np.asarray(cuts).shape,
                                            jnp.float32)
            n_cols = np.asarray(valuesT).shape[0]
            for bucket in ladder:
                v_struct = jax.ShapeDtypeStruct((n_cols, bucket),
                                                jnp.float32)
                out[(i, bucket)] = jitted.lower(
                    n_struct, v_struct, c_struct).compile()
    return out, dev_params


def aot_selfcheck(executables: Dict[Tuple[int, int], Any],
                  params_by_model: Dict[int, Any], scorer: Any,
                  proto: Dict[str, Optional[np.ndarray]]) -> None:
    """Assert each AOT executable agrees with the interpretive scoring
    path on the warm-up batch — the compiled artifact doubles as a
    parity probe for the compile layer.  ``params_by_model`` may carry
    CANDIDATE params (the refresh swap's parity gate runs challenger
    params through the resident executables before they go live) — the
    interpretive reference is recomputed with the same params, so the
    check is exactly 'resident executable == what a cold re-warm of
    these params would score'."""
    import jax

    from shifu_tpu.eval.scorer import score_matrix

    for (i, bucket), exe in executables.items():
        kind, meta, _ = scorer.models[i]
        params = params_by_model[i]
        if kind in ("gbt", "rf"):
            from shifu_tpu.models import gbdt
            import jax.numpy as jnp
            rd = pad_rows(np.asarray(proto["raw_dense"], np.float32),
                          bucket)
            rc = None if proto.get("raw_codes") is None else pad_rows(
                np.asarray(proto["raw_codes"]), bucket)
            np_params = jax.tree.map(np.asarray, params)
            packed, valuesT, cuts, _ = _tree_fused_blocks(
                meta, np_params, rd, rc)
            got = np.asarray(exe(jnp.asarray(packed),
                                 jnp.asarray(valuesT),
                                 jnp.asarray(cuts))).reshape(-1)
            # reference: the interpretive bin_dataset + walk route
            want = np.asarray(gbdt.predict(
                meta, np_params, rd, rc, route="xla")).reshape(-1)
        else:
            dense = pad_rows(np.asarray(proto["dense"], np.float32),
                             bucket)
            got = np.asarray(exe(params, dense)).reshape(-1)
            want = np.asarray(
                score_matrix(kind, meta, params, dense)).reshape(-1)
        if not np.allclose(got, want, rtol=1e-5, atol=1e-6):
            raise AssertionError(
                f"AOT executable for model{i} bucket {bucket} deviates "
                "from the interpretive score path")
