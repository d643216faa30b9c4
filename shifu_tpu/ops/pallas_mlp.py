"""Pallas TPU kernel: loss and gradient of a narrow MLP, one call an epoch.

XLA runs a full-batch epoch of a 28-64-1 net as four passes over the
(rows, 64) activation (layer 0 forward and backward, the 64 -> 1
product forward and backward), each near the memory's speed: it will
not fuse a matrix product into two consumers, so the activation goes
out to HBM and comes back, 2.7 GB a pass where the matrix itself is
1.2 GB. Here a row tile's forward pass, loss and backward pass are one
grid step and its activations live and die in VMEM: an epoch reads the
matrix once and writes a few KB of gradients.

Rows lie on the LANE axis (as `binsT` in `ops/pallas_hist.py`):

- `xT`: (F8, R) float32, the F features padded to a sublane multiple;
- `y`, `w`: (R / CHUNK, CHUNK) float32: chunk c of the rows is sublane
  row c, so a (1, R) operand's eightfold sublane padding never exists
  and a grid step's (CHUNKS, CHUNK) block is its ROW_TILE rows.

`lay_rows` makes the three once a job (weight-0 rows pad the last
tile). A grid step walks its chunks; for a chunk every hidden layer is
`act(W^T h + b)`, a `dot_general` at DEFAULT precision on float32
operands (one bfloat16 MXU pass, float32 sums: what jax does by default
on a TPU and what `_hist_body` does); the product onto the single output
unit is a float32 multiply and sublane reduce on the vector unit,
forward and backward, and rounds nothing (XLA computes it so). Then the
output activation, the row's loss times its weight, and straight back:
`dout`, `w_last (x) dout`, each layer's `act'`, the weight gradient as
the NT contraction over the chunk's rows, the input gradient of layers
above the first.

Sums over rows: a weight gradient leaves the MXU summed over a chunk; a
bias gradient, the last layer's weight gradient and the loss are folded
to 128 lanes by vreg adds (no cross-lane work in the kernel). All are
accumulated over the row tiles of a PARTIAL block (`TILES_PER_PARTIAL`
grid steps revisit one output block) and XLA adds the partial blocks
and the lanes after the call: one running float32 block over every tile
of 10^7 rows drifts (PERF.md, PR 30's review round on `_hist_body`).

`loss` is the entry: a `jax.custom_vjp` whose forward pass returns the
weighted mean loss and keeps the gradients as residuals, so
`jax.value_and_grad` around it costs the one kernel call. L1/L2 terms
stay XLA's (`nn.penalty`). Under `jax.vmap` over bags the parameters
and `w` carry the bag axis and `xT`, `y` do not: the `pallas_call`
gains a grid axis and the matrix is not copied.

`serves(spec)` says which nets the kernel implements; who takes the
path is `train/trainer.py`'s to decide. `interpret=True` runs the same
kernel on the CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from shifu_tpu.models import nn as nn_mod

__all__ = ["serves", "on_chip", "lay_rows", "loss"]

# my chip runs, PR 32, 28-64-1 at 10.5 M rows, ms a call by (CHUNK,
# CHUNKS): (128, 8) 7.5, (512, 8) 5.3, (1024, 8) 3.7, (256, 8) 4.2,
# (256, 16) 3.3, (256, 32) 2.8, (256, 64) 2.6, (128, 64) 3.6: a chunk's
# (64, 256) activation is 16 vregs and stays in registers, and a grid
# step's fixed cost is paid 641 times an epoch
CHUNK = 256             # rows whose activations are alive at once
CHUNKS = 64             # chunks a grid step: a (64, CHUNK) block of y, w
ROW_TILE = CHUNK * CHUNKS
TILES_PER_PARTIAL = 8   # grid steps accumulated into one output block
LANES = 128
MAX_WIDTH = 128         # a layer's weights and a chunk's activations:
#                         one pass of the MXU's width, a few vregs a row
# the widest input: a grid step's (F8, ROW_TILE) block of xT, double
# buffered, is F8 x 128 KiB of VMEM. The v5e's compiler takes F8 = 120
# and refuses 128 and above at every hidden width from 16 to 128
# (`tests/test_chip_compile.py` compiles both sides): at 128 the block
# alone is the kernel's whole 16 MiB
MAX_INPUT = 120

_ACTS = ("tanh", "ptanh", "sigmoid", "relu", "leakyrelu", "linear")


def _loss_kind(name: str) -> str:
    """`nn.loss_fn`'s own reading of `spec.loss`."""
    if name.startswith("log"):
        return "log"
    return "absolute" if name.startswith("abs") else "squared"


def serves(spec: nn_mod.MLPSpec) -> bool:
    """True for the nets the kernel implements: one output unit, at
    most MAX_INPUT inputs, at least one hidden layer and none wider than
    MAX_WIDTH, no dropout, float32 compute, activations it has a
    derivative for. A wider input is XLA's epoch: its row tile would not
    fit the kernel's VMEM."""
    acts = [str(a).lower() for a in spec.activations]
    return (spec.output_dim == 1
            and spec.input_dim <= MAX_INPUT
            and 1 <= len(spec.hidden_dims)
            and max(spec.hidden_dims) <= MAX_WIDTH
            and spec.dropout_rate == 0
            and spec.compute_dtype == "float32"
            and all(a in _ACTS for a in acts)
            and str(spec.output_activation).lower() in _ACTS)


def on_chip() -> bool:
    """A TPU runs the kernel; elsewhere only tests reach it, in
    interpret mode."""
    return jax.default_backend() == "tpu"


def _up(n: int, to: int) -> int:
    return -(-n // to) * to


def lay_rows(x, y, w_bags):
    """(R, F) features, (R,) labels and (B, R) weights as the kernel
    reads them: `xT` (F8, Rp), `y` (Rp / CHUNK, CHUNK), `w` (B,
    Rp / CHUNK, CHUNK), Rp the rows padded to the row tile with
    weight-0 rows. One program a job, before the epochs."""
    return _lay_rows(x, y, w_bags, ROW_TILE, CHUNK)


@functools.partial(jax.jit, static_argnames=("row_tile", "chunk"))
def _lay_rows(x, y, w_bags, row_tile: int, chunk: int):
    r, f = x.shape
    rp = _up(r, row_tile)
    xT = jnp.pad(x.astype(jnp.float32).T, ((0, _up(f, 8) - f), (0, rp - r)))
    y = jnp.pad(y.astype(jnp.float32), (0, rp - r))
    w = jnp.pad(w_bags.astype(jnp.float32), ((0, 0), (0, rp - r)))
    return (xT, y.reshape(rp // chunk, chunk),
            w.reshape(w.shape[0], rp // chunk, chunk))


def _act_grad(name: str, a, upstream):
    """upstream * act'(z), from the activation's own value a = act(z),
    as jax differentiates `nn.ACTIVATIONS` (relu: 0 at 0; leakyrelu: 1
    at 0)."""
    if name == "sigmoid":
        return upstream * (a * (1.0 - a))
    if name in ("tanh", "ptanh"):
        return upstream * (1.0 - a * a)
    if name == "relu":
        return jnp.where(a > 0, upstream, 0.0)
    if name == "leakyrelu":
        return jnp.where(a >= 0, upstream, 0.01 * upstream)
    return upstream


def _row_loss(kind: str, p, y):
    """A row's loss and its derivative by the prediction
    (`nn.loss_fn`'s three, written out)."""
    if kind == "log":
        eps = 1e-7
        return (-(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps)),
                (1 - y) / (1 - p + eps) - y / (p + eps))
    if kind == "absolute":
        return jnp.abs(y - p), jnp.sign(p - y)
    return 0.5 * jnp.square(y - p), p - y


def _fold(a):
    """(d, CHUNK) -> (d, LANES): the lane tiles added, vreg by vreg."""
    out = a[:, :LANES]
    for k in range(1, a.shape[1] // LANES):
        out = out + a[:, k * LANES:(k + 1) * LANES]
    return out


def _chunk_sums(acts, out_act, kind, x, y, w, hidden, wlast, blast):
    """One chunk of rows, forward and straight back. x (F8, CHUNK); y,
    w (1, CHUNK); `hidden` a (W^T, b, W) a hidden layer; the last
    layer's w (d, 1) and b (1, 1). Returns the chunk's sums in the
    order of the kernel's outputs, each folded to LANES: the weighted
    loss, every hidden layer's dW^T and db, the last layer's dw, db."""
    def mxu(a, b, contract):
        return jax.lax.dot_general(
            a, b, (contract, ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    hs = [x]
    for name, (wT, b, _) in zip(acts, hidden):
        hs.append(nn_mod.activation(name)(
            mxu(wT, hs[-1], ((1,), (0,))) + b))
    # the single output unit: a float32 multiply and sublane reduce on
    # the vector unit, nothing rounded
    pre = jnp.sum(hs[-1] * wlast, axis=0, keepdims=True) + blast
    p = nn_mod.activation(out_act)(pre)                     # (1, CHUNK)
    per, dper = _row_loss(kind, p, y)
    dout = _act_grad(out_act, p, dper * w)                  # (1, CHUNK)
    sums = [_fold(hs[-1] * dout), _fold(dout)]
    da = wlast * dout                                       # (d, CHUNK)
    for layer in reversed(range(len(acts))):
        dz = _act_grad(acts[layer], hs[layer + 1], da)
        # NT contraction over the chunk's rows, as the histogram
        # kernel's: (d_out, CHUNK) . (d_in, CHUNK)^T
        sums = [mxu(dz, hs[layer], ((1,), (1,))), _fold(dz)] + sums
        if layer:
            da = mxu(hidden[layer][2], dz, ((1,), (0,)))
    return [_fold(per * w)] + sums


def _kernel(*refs, acts: Tuple[str, ...], out_act: str, kind: str):
    """refs: xT, y, w; a hidden layer's W^T (d_out, d_in), b (d_out, 1)
    and W (d_in, d_out: the input gradient's, unread for the first
    layer); the last layer's w (d, 1) and b (1, 1); then the outputs:
    the loss (1, LANES), a hidden layer's dW^T and db (d_out, LANES),
    the last layer's dw (d, LANES) and db (1, LANES)."""
    from jax.experimental import pallas as pl
    n_hidden = len(acts)
    xT_ref, y_ref, w_ref = refs[:3]
    hidden = [tuple(r[...] for r in refs[3 + 3 * k:6 + 3 * k])
              for k in range(n_hidden)]
    wlast, blast = (r[...] for r in refs[3 + 3 * n_hidden:5 + 3 * n_hidden])
    outs = refs[5 + 3 * n_hidden:]

    # the first row tile of a partial block starts it at zero
    @pl.when(pl.program_id(0) % TILES_PER_PARTIAL == 0)
    def _start():
        for ref in outs:
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    # every chunk in one basic block, the loop unrolled by the lowering
    # and not by Python: the scheduler runs one chunk's vector work
    # under the next one's matrix products (a rolled `fori_loop` over
    # the same chunks took 2-5 times as long on the v5e), and the chunk
    # is traced once, not CHUNKS times (seconds of a job's first call)
    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        sums = _chunk_sums(acts, out_act, kind, xT_ref[:, rows],
                           y_ref[pl.ds(c, 1), :], w_ref[pl.ds(c, 1), :],
                           hidden, wlast, blast)
        for ref, part in zip(outs, sums):
            ref[...] += part
        return carry

    jax.lax.fori_loop(0, CHUNKS, chunk, None, unroll=True)


def _sums(spec: nn_mod.MLPSpec, params, xT, y, w, interpret: bool):
    """The kernel call: the weighted loss SUM over rows and the sum of
    every row's weighted gradient, as a pytree like `params`."""
    # imported by the jobs that run the kernel, not by every trainer:
    # pallas takes a second to import (`trainer` imports this module)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f8, rp = xT.shape
    n_tiles = rp // ROW_TILE
    n_part = -(-n_tiles // TILES_PER_PARTIAL)
    widths = [f8] + [_up(d, 8) for d in spec.hidden_dims]
    n_hidden = len(spec.hidden_dims)
    f32 = jnp.float32

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    def partial_block(rows, lanes=LANES):
        return (pl.BlockSpec((None, rows, lanes),
                             lambda i: (i // TILES_PER_PARTIAL, 0, 0)),
                jax.ShapeDtypeStruct((n_part, rows, lanes), f32))

    operands = [xT, y, w]
    in_specs = [pl.BlockSpec((f8, ROW_TILE), lambda i: (0, i)),
                pl.BlockSpec((CHUNKS, CHUNK), lambda i: (i, 0)),
                pl.BlockSpec((CHUNKS, CHUNK), lambda i: (i, 0))]
    outs = [partial_block(1)]
    for layer in range(n_hidden):
        d_in, d_out = widths[layer], widths[layer + 1]
        wl = params[layer]["w"].astype(f32)
        wl = jnp.pad(wl, ((0, d_in - wl.shape[0]), (0, d_out - wl.shape[1])))
        bl = params[layer]["b"].astype(f32)
        operands += [wl.T, jnp.pad(bl, (0, d_out - bl.shape[0]))[:, None],
                     wl]
        in_specs += [whole((d_out, d_in)), whole((d_out, 1)),
                     whole((d_in, d_out))]
        outs += [partial_block(d_out, d_in), partial_block(d_out)]
    d_last = widths[-1]
    wl = params[-1]["w"].astype(f32)
    operands += [jnp.pad(wl, ((0, d_last - wl.shape[0]), (0, 0))),
                 params[-1]["b"].astype(f32).reshape(1, 1)]
    in_specs += [whole((d_last, 1)), whole((1, 1))]
    outs += [partial_block(d_last), partial_block(1)]

    got = pl.pallas_call(
        functools.partial(
            _kernel, acts=tuple(str(a).lower() for a in spec.activations),
            out_act=str(spec.output_activation).lower(),
            kind=_loss_kind(spec.loss)),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[o[0] for o in outs],
        out_shape=[o[1] for o in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="shifu_mlp_loss_grad",
    )(*operands)

    # the partial blocks, and the lanes of the folded ones, added here
    loss_sum = jnp.sum(got[0])
    grads = []
    for layer in range(n_hidden):
        d_in, d_out = params[layer]["w"].shape
        dwT = jnp.sum(got[1 + 2 * layer], axis=0)
        db = jnp.sum(got[2 + 2 * layer], axis=(0, 2))
        grads.append({"w": dwT[:d_out, :d_in].T, "b": db[:d_out]})
    d_in = params[-1]["w"].shape[0]
    grads.append({"w": jnp.sum(got[-2], axis=(0, 2))[:d_in, None],
                  "b": jnp.sum(got[-1]).reshape(1)})
    return loss_sum, grads


def loss_and_grads(spec: nn_mod.MLPSpec, params, xT, y, w,
                   interpret: bool = False):
    """The weighted mean loss over the rows (no L1/L2 term) and its
    gradient by `params`, from one kernel call: what
    `jax.value_and_grad(nn.loss_fn)` gives for a spec without penalty,
    on rows laid out by `lay_rows`."""
    loss_sum, grads = _sums(spec, params, xT, y, w, interpret)
    inv = 1.0 / jnp.maximum(jnp.sum(w), 1e-12)
    return loss_sum * inv, jax.tree.map(lambda g: g * inv, grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 5))
def _data_loss(spec, params, xT, y, w, interpret):
    return loss_and_grads(spec, params, xT, y, w, interpret)[0]


def _data_loss_fwd(spec, params, xT, y, w, interpret):
    return loss_and_grads(spec, params, xT, y, w, interpret)


def _data_loss_bwd(spec, interpret, grads, g):
    # the rows are data: nothing is asked of xT, y or w
    return jax.tree.map(lambda t: g * t, grads), None, None, None


_data_loss.defvjp(_data_loss_fwd, _data_loss_bwd)


def loss(spec: nn_mod.MLPSpec, params, xT, y, w,
         interpret: bool = False):
    """`nn.loss_fn` on rows laid out by `lay_rows`: differentiable by
    `params`, forward and backward in the one kernel call."""
    return _data_loss(spec, params, xT, y, w, interpret) \
        + nn_mod.penalty(spec, params)
