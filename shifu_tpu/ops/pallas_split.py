"""Pallas TPU kernel: fused GBT split search (cumsum + gain + argmax).

`models/gbdt._best_splits` is a chain of XLA ops over the level's
`(nodes, C, B)` G/H histograms — two cumulative sums, two gain tensors,
masking, and a flat argmax — each materializing an `(N, C, B)` f32
intermediate in HBM. This kernel fuses the whole chain: each
(node tile, column tile) block is cumulative-summed, gain-scored
(including the min-instances mask, the feature mask, and the
last-main-bin exclusion) and arg-reduced in VMEM; only a packed
(N, 1, 128) result ever leaves it. The XLA path in `_best_splits` stays
as-is and is the reference the parity suite
(tests/test_pallas_split.py) checks against.

Layout, chosen for what the chip's compiler (Mosaic) lowers: bins ride
the LANE axis, zero-padded to a multiple of 128 so lane rotation works
on whole vregs; columns ride sublanes in tiles of a multiple of 8 (or
the whole padded column count); nodes are the untiled leading axis.
Every per-node / per-column quantity stays rank 3 with `keepdims`
reductions — no lane slicing, no rank-changing relayouts, no
`jnp.stack`. The per-node feature mask arrives as (N, C, 1) so its
block's last two dims are (column tile, whole array).

The cumulative sum is a two-level blocked scan built from `pltpu.roll`
and masked adds (Mosaic has no `cumsum`); see `_lane_cumsum` for why its
interpret-mode result is bitwise the XLA chain's.

Tie-breaking is deterministic and matches `jnp.argmax`'s
first-occurrence rule exactly: within a column tile the winner among
equal-gain cells is the minimum flat index (feature·(B-1) + bin), and
across tiles a later tile only takes over on a STRICTLY greater gain —
tiles visit columns in ascending order, so the earliest flat maximum
always wins. An all-masked node (every gain -inf) resolves to flat
index 0, again matching `jnp.argmax` on an all-equal row.

The packed output rides lanes [best_gain, best_flat_idx, default_left,
g_tot, h_tot] — flat indices are exact in f32 (C·B is far below 2^24).
Routing: SHIFU_TPU_SPLIT_FUSED = auto (Pallas on TPU, XLA elsewhere) |
pallas | xla, mirroring SHIFU_TPU_SCORE_FUSED. `interpret=True` runs
the kernel on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from shifu_tpu.config.environment import knob_str

__all__ = ["split_fused_mode", "best_splits_pallas"]

_BIG = 3.0e38  # > any flat index; sentinel for the min-index reduce
_LANES = 128
# Mosaic unrolls every vector op over the block's vregs, so compile
# time grows with the block, not the array: 64 vregs (1024 f32 each)
# per operand compiles in ~2 s where a VMEM-filling block takes minutes.
# At this size VMEM is no constraint (~25 live copies ≈ 6 MiB, under
# the compiler's default 16 MiB), so no budget knob enters here.
_BLOCK_ELEMS = 64 * 1024


def split_fused_mode() -> str:
    """Fused split-search route: "pallas" | "xla"; "auto" resolves by
    backend (Pallas on TPU, XLA fallback elsewhere)."""
    mode = knob_str("SHIFU_TPU_SPLIT_FUSED").lower()
    if mode in ("pallas", "xla"):
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _derive_tiles(n_nodes: int, n_cols_pad: int, n_lanes: int):
    """(node_tile, col_tile) for a block of at most _BLOCK_ELEMS.
    Prefers the whole (8-padded) column axis in one tile and shrinks it
    in multiples of 8 only when one node's columns exceed the cap; the
    node tile takes what is left."""
    tc = min(n_cols_pad, max(8, (_BLOCK_ELEMS // n_lanes) // 8 * 8))
    nb_max = max(1, _BLOCK_ELEMS // (tc * n_lanes))
    # even out the node tiles so the pad is at most one tile's remainder
    steps = -(-n_nodes // nb_max)
    return -(-n_nodes // steps), tc


def _lane_cumsum(x, n: int):
    """Inclusive prefix sum over lanes [0, n) of the last axis; lanes
    ≥ n come back as garbage the caller masks. Mosaic has no `cumsum`,
    so this is a two-level blocked scan built from lane rotations and
    masked adds: (1) a sequential scan inside every 16-lane tile,
    (2) a sequential scan of the tile totals (they sit at lanes ≡ 15
    mod 16), (3) each tile adds the running total of the tiles before
    it. Lane i only ever reads lanes < i. The additions and their
    order are exactly those of XLA:CPU's reduce-window `jnp.cumsum`
    (base-16 tiles) for up to 256 lanes, so interpret-mode gains are
    bitwise the XLA chain's — what the parity suite pins — and empty
    bins tie exactly as they do there."""
    last = x.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, last)
    in_tile = lane & 15
    inner = x
    for k in range(1, min(16, n)):
        inner = jnp.where(in_tile == k, pltpu.roll(inner, 1, last) + inner,
                          inner)
    n_tiles = -(-n // 16)
    if n_tiles == 1:
        return inner
    totals = inner                       # running tile totals, in place
    for t in range(1, n_tiles - 1):
        totals = jnp.where(lane == 16 * t + 15,
                           pltpu.roll(totals, 16, last) + totals, totals)
    # carry of tile t = running total of tile t-1: move it to the
    # tile's first lane, then double it across the 16 lanes (each add
    # meets a zero, so the copy is exact; nothing wraps into tile 1+)
    carry = jnp.where((in_tile == 0) & (lane >= 16),
                      pltpu.roll(totals, 1, last), 0.0)
    for d in (1, 2, 4, 8):
        carry = carry + pltpu.roll(carry, d, last)
    return jnp.where(lane >= 16, inner + carry, inner)


def _split_kernel(g_ref, h_ref, m_ref, out_ref, *, lam, min_inst, bm, tc):
    # grid = (node_tiles, col_tiles), columns innermost and ascending —
    # the ordering is what makes the strict `>` take-over rule equal
    # jnp.argmax's first-occurrence tie-break
    j = pl.program_id(1)
    g = g_ref[...]                       # (NB, TC, L): main bins, the
    h = h_ref[...]                       # missing bin at lane bm, 0-pad
    mask = m_ref[...]                    # (NB, TC, 1) f32 0/1 (0 on pads)
    lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 2)

    def pick(x, at):                     # lane `at` of x, as (NB, TC, 1)
        return jnp.max(jnp.where(lane == at, x, -jnp.inf), axis=2,
                       keepdims=True)

    g_miss = pick(g, bm)
    h_miss = pick(h, bm)
    gl = _lane_cumsum(g, bm)             # left sums after bin b
    hl = _lane_cumsum(h, bm)
    g_tot = pick(gl, bm - 1) + g_miss    # (NB, TC, 1)
    h_tot = pick(hl, bm - 1) + h_miss

    def gain_of(gl_, hl_):
        gr_ = g_tot - gl_
        hr_ = h_tot - hl_
        score = (gl_ ** 2 / (hl_ + lam) + gr_ ** 2 / (hr_ + lam)
                 - (g_tot ** 2 / (h_tot + lam)))
        ok = (hl_ >= min_inst) & (hr_ >= min_inst)
        return jnp.where(ok, score, -jnp.inf)

    gain_left = gain_of(gl + g_miss, hl + h_miss)
    gain_right = gain_of(gl, hl)
    dl = (gain_left >= gain_right).astype(jnp.float32)
    gain = jnp.maximum(gain_left, gain_right)
    # the last main bin as split point sends everything left — exclude
    # it, and with it the missing-bin and pad lanes the scan left dirty
    gain = jnp.where((mask > 0) & (lane < bm - 1), gain, -jnp.inf)

    col = j * tc + jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    # lanes ≥ bm would alias the next column's flat index — park them
    flat = jnp.where(lane < bm, (col * bm + lane).astype(jnp.float32),
                     _BIG)

    def reduce2(fn, x):                  # over (cols, lanes) → (NB, 1, 1)
        return fn(fn(x, axis=2, keepdims=True), axis=1, keepdims=True)

    tile_max = reduce2(jnp.max, gain)
    tile_idx = reduce2(jnp.min, jnp.where(gain == tile_max, flat, _BIG))
    tile_dl = reduce2(jnp.max, jnp.where(flat == tile_idx, dl, 0.0))

    out_lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 2)

    def packed(fields):                  # (NB, 1, 1) each → lanes 0..k
        out = jnp.zeros(out_ref.shape, jnp.float32)
        for k, v in enumerate(fields):
            out = jnp.where(out_lane == k, v, out)
        return out

    @pl.when(j == 0)
    def _init():
        # tile 0's local column 0 IS global column 0: its total matches
        # the XLA path's g_tot[:, 0] (totals are identical across
        # features — every feature's histogram sums the same rows)
        out_ref[...] = packed([tile_max, tile_idx, tile_dl,
                               g_tot[:, 0:1, :], h_tot[:, 0:1, :]])

    @pl.when(j > 0)
    def _accum():
        old = out_ref[...]
        old_max = jnp.max(jnp.where(out_lane == 0, old, -jnp.inf),
                          axis=2, keepdims=True)
        cand = jnp.where(out_lane < 3,
                         packed([tile_max, tile_idx, tile_dl]), old)
        out_ref[...] = jnp.where(tile_max > old_max, cand, old)


def best_splits_pallas(g, h, feature_mask, lam: float, min_inst: float,
                       col_tile: int = 0, node_tile: int = 0,
                       interpret: bool = False):
    """Best (feature, bin, missing-direction) per node, fused.

    g/h: (N, C, B) f32 level histograms, missing bin LAST (index B-1).
    feature_mask: (N, C) — per-NODE masks so a flattened lockstep
    forest level (T·N nodes) runs as ONE kernel launch.
    Returns the `_best_splits` dict; `g_tot`/`h_tot` come back as (N,)
    scalars (the XLA path's per-feature copies are redundant).
    """
    n, c, b = g.shape
    bm = b - 1
    lanes = -(-b // _LANES) * _LANES
    d_nb, d_tc = _derive_tiles(n, -(-c // 8) * 8, lanes)
    tc = col_tile or d_tc
    nb = node_tile or d_nb
    pad = ((0, (-n) % nb), (0, (-c) % tc), (0, lanes - b))
    gp = jnp.pad(g.astype(jnp.float32), pad)
    hp = jnp.pad(h.astype(jnp.float32), pad)
    # zero-padded mask columns score -inf and can never win the argmax
    mp = jnp.pad(feature_mask.astype(jnp.float32)[:, :, None],
                 pad[:2] + ((0, 0),))
    n_pad, c_pad = gp.shape[:2]

    out = pl.pallas_call(
        functools.partial(_split_kernel, lam=float(lam),
                          min_inst=float(min_inst), bm=bm, tc=tc),
        grid=(n_pad // nb, c_pad // tc),
        in_specs=[
            pl.BlockSpec((nb, tc, lanes), lambda i, j: (i, j, 0)),
            pl.BlockSpec((nb, tc, lanes), lambda i, j: (i, j, 0)),
            pl.BlockSpec((nb, tc, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((nb, 1, _LANES), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1, _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="shifu_best_splits",
    )(gp, hp, mp)

    out = out[:n, 0, :]
    best = out[:, 1].astype(jnp.int32)
    return {"feature": (best // bm).astype(jnp.int32),
            "bin": (best % bm).astype(jnp.int32),
            "gain": out[:, 0],
            "default_left": out[:, 2] > 0.5,
            "g_tot": out[:, 3],
            "h_tot": out[:, 4]}
