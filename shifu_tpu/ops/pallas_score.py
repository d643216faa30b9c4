"""Pallas TPU kernel: fused z-score normalize + first-layer matmul.

The NN scoring path (eval/scorer.score_matrix) normalizes the raw
numeric block to a full (N, C) z-scored matrix in HBM and then
immediately contracts it with the first layer's (C, H) weights — the
normalized matrix is written once and read once. This kernel fuses the
two: each (TN, TC) raw tile is NaN-filled, clamped and scaled
in-register (exact `ops/normalize.zscore` semantics, including the
std ≤ 1e-5 → 0 rule) and fed straight into the MXU contraction with
the matching (TC, H) weight tile, accumulating the (TN, H) first-layer
pre-activation across column tiles. The z-scored matrix never exists
in HBM, halving the scoring path's bytes-moved for wide inputs.

Per-column normalize parameters ride in ONE packed (8, C) f32 block
(sublanes: mean, safe-std, lo, hi) — four separate (C,) vectors would
each sublane-pad 8×.

Routing: SHIFU_TPU_SCORE_FUSED = auto (Pallas on TPU, XLA elsewhere) |
pallas | xla. `interpret=True` runs the kernel on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from shifu_tpu.config.environment import knob_str

__all__ = ["score_fused_mode", "fused_first_layer", "score_nn"]


def score_fused_mode() -> str:
    """Fused scoring route: "pallas" | "xla"; "auto" resolves by
    backend (Pallas on TPU, XLA fallback elsewhere)."""
    mode = knob_str("SHIFU_TPU_SCORE_FUSED").lower()
    if mode in ("pallas", "xla"):
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _pack_norm(mean, std, cutoff: float, n_cols: int, pad_c: int):
    """(8, C+pad) block [mean, safe-std, lo, hi]. Columns with
    std < STD_EPS get lo = hi = mean so the clamp pins the value to the
    mean and the kernel's (v - mean)/safe_std lands on EXACTLY 0 — the
    `Normalizer.computeZScore` tiny-std rule without a separate mask.
    Pad columns are all-zero: z = (clip(0,0,0) - 0)/1 = 0."""
    from shifu_tpu.ops.normalize import STD_EPS
    ok = std >= STD_EPS
    std_safe = jnp.where(ok, std, 1.0)
    lo = jnp.where(ok, mean - cutoff * std, mean)
    hi = jnp.where(ok, mean + cutoff * std, mean)
    packed = jnp.zeros((8, n_cols + pad_c), jnp.float32)
    packed = packed.at[0, :n_cols].set(mean.astype(jnp.float32))
    packed = packed.at[1, :n_cols].set(std_safe.astype(jnp.float32))
    packed = packed.at[1, n_cols:].set(1.0)
    packed = packed.at[2, :n_cols].set(lo.astype(jnp.float32))
    packed = packed.at[3, :n_cols].set(hi.astype(jnp.float32))
    return packed


def _score_kernel(x_ref, np_ref, w_ref, out_ref, *, precision):
    # grid = (row_tiles, col_tiles): the COLUMN (reduction) dimension is
    # innermost so each output block's revisits are consecutive grid
    # steps — required for the += accumulation pattern on TPU
    j = pl.program_id(1)
    v = x_ref[:, :]                             # (TN, TC) raw values
    mean = np_ref[0:1, :]
    std_safe = np_ref[1:2, :]
    lo = np_ref[2:3, :]
    hi = np_ref[3:4, :]
    v = jnp.where(jnp.isnan(v), mean, v)        # missing → mean → z 0
    v = jnp.clip(v, lo, hi)                     # mean ± cutoff·std clamp
    z = (v - mean) / std_safe
    part = jax.lax.dot_general(
        z, w_ref[:, :], (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        out_ref[:, :] = part

    @pl.when(j > 0)
    def _accum():
        out_ref[:, :] += part


@functools.partial(jax.jit, static_argnames=("cutoff", "row_tile",
                                             "col_tile", "interpret"))
def _fused_first_layer_pallas(values, mean, std, w, cutoff: float,
                              row_tile: int, col_tile: int,
                              interpret: bool):
    n, c = values.shape
    h = w.shape[1]
    row_tile = min(row_tile, max(8, n))
    col_tile = min(col_tile, max(1, c))
    pad_n = (-n) % row_tile
    pad_c = (-c) % col_tile
    pad_h = (-h) % 128                          # lane-align the output
    x = jnp.pad(values.astype(jnp.float32), ((0, pad_n), (0, pad_c)))
    # zero pad weight rows/cols contribute nothing to the contraction
    wp = jnp.pad(w.astype(jnp.float32), ((0, pad_c), (0, pad_h)))
    packed = _pack_norm(mean, std, cutoff, c, pad_c)
    np_, cp = x.shape
    hp = h + pad_h
    grid = (np_ // row_tile, cp // col_tile)    # cols innermost

    out = pl.pallas_call(
        functools.partial(_score_kernel,
                          precision=jax.lax.Precision.DEFAULT),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),
            pl.BlockSpec((8, col_tile), lambda i, j: (0, j)),
            pl.BlockSpec((col_tile, hp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, hp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, hp), jnp.float32),
        interpret=interpret,
        name="shifu_first_layer",
    )(x, packed, wp)
    return out[:n, :h]


def fused_first_layer(values, mean, std, cutoff: float, w, b,
                      mode: str = "", row_tile: int = 512,
                      col_tile: int = 128, interpret: bool = False):
    """(N, C) RAW values (NaN = missing) → (N, H) first-layer
    pre-activation `zscore(values) @ w + b`, without materializing the
    z-scored matrix. `mode` overrides SHIFU_TPU_SCORE_FUSED; the XLA
    route is the lax reference the parity tests check against."""
    mode = mode or score_fused_mode()
    if mode == "xla":
        from shifu_tpu.ops.normalize import zscore
        z = zscore(jnp.asarray(values, jnp.float32), mean, std, cutoff)
        return jax.lax.dot_general(
            z, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + b
    out = _fused_first_layer_pallas(values, mean, std, w, float(cutoff),
                                    row_tile, col_tile, interpret)
    return out + b


def score_nn(spec, params, values, mean, std, cutoff: float,
             mode: str = "", interpret: bool = False):
    """Full MLP forward over RAW inputs with the normalize + layer-0
    matmul fused (scoring only: no dropout, f32 throughout — mirrors
    models/nn.forward's layer loop from layer 1 on)."""
    from shifu_tpu.models import nn as nn_mod
    h = fused_first_layer(values, mean, std, cutoff,
                          params[0]["w"], params[0]["b"],
                          mode=mode, interpret=interpret)
    if len(params) == 1:
        out = h
    else:
        h = nn_mod.activation(spec.activations[0])(h)
        for i, layer in enumerate(params[1:-1], start=1):
            h = nn_mod.mm_f32(h, layer["w"]) + layer["b"]
            h = nn_mod.activation(spec.activations[i])(h)
        out = nn_mod.mm_f32(h, params[-1]["w"]) + params[-1]["b"]
    if spec.output_activation == "softmax":
        return jax.nn.softmax(out, axis=-1)
    out = nn_mod.activation(spec.output_activation)(out)
    return out[..., 0] if spec.output_dim == 1 else out
