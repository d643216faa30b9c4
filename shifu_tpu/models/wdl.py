"""Wide-and-Deep — embeddings + wide crosses + deep MLP, TPU-native.

Replaces the reference's homegrown layer graph
(`wdl/WideAndDeep.java:78-249`: dense input + per-categorical
`EmbedFieldLayer` + `WideFieldLayer` + hidden `DenseLayer`s + logistic
output; layer lib `core/dtrain/layer/*`). Here:

- all per-column embedding tables are ONE ragged (ΣV, E) array, column
  c's rows at [offset[c], offset[c] + V_c) behind a static offset
  vector (V_c counts the column's missing slot, its last) — the
  per-row lookup is a single gather of `idx + offset[col]`, a column
  of 3 values costs 3 rows beside one of ten million, and under a
  device mesh the table shards over the 'model' axis by ROW (the
  expert/embedding-parallel analog for tabular data). On the device
  the table is held LANE-PACKED (`pack_table`): 128 // E of its rows
  side by side in one 128-lane row, the row-major reshape of the same
  array, so a lookup and its gradient's scatter-add move whole lane
  rows (a (ΣV, 32) float32 array is kept rows-minor on a TPU, where
  the scatter-add of a batch's lookups measured six times slower);
  model files hold the plain (ΣV, E) array;
- the wide part is a (ΣV,) weight table behind the same offsets +
  dense-side linear (`WideDenseLayer`), summed into the logit;
- the deep part is an MLP over [dense ⊕ flattened embeddings];
- output = sigmoid(deep_logit + wide_logit) with log loss, matching the
  reference's logistic output + cross-entropy.

Inputs come from the *_INDEX norm families: a float dense block and an
int32 index block (missing category = vocab_len slot), exactly what
`WDLWorker.java:97` parses from normalized records.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tpu.models import nn as nn_mod


@dataclass(frozen=True)
class WDLSpec:
    dense_dim: int
    n_cat: int
    vocab_sizes: tuple            # per-column table rows incl. missing slot
    embed_size: int = 8
    hidden_dims: tuple = (64, 32)
    activations: tuple = ("relu", "relu")
    l2: float = 0.0
    wide_enable: bool = True
    deep_enable: bool = True
    # "bfloat16" runs the deep-trunk GEMMs in bf16 with f32
    # accumulation (see nn.forward); embeddings, the wide logit and
    # the loss stay f32. train#params ComputeDtype or the package-wide
    # SHIFU_TPU_COMPUTE_DTYPE knob.
    compute_dtype: str = "float32"

    @classmethod
    def from_train_params(cls, params: Dict[str, Any], dense_dim: int,
                          n_cat: int, vocab_sizes) -> "WDLSpec":
        get = nn_mod.param_getter(params)
        nodes, acts = nn_mod.parse_arch_params(
            params, default_nodes=(64, 32), default_acts=("relu",),
            honor_num_layers=False)
        return cls(
            dense_dim=dense_dim, n_cat=n_cat, vocab_sizes=vocab_sizes,
            embed_size=int(get("EmbedSize", get("EmbedColumnNum", 8) or 8) or 8),
            hidden_dims=nodes, activations=acts,
            l2=float(get("RegularizedConstant", 0.0) or 0.0),
            wide_enable=bool(get("WideEnable", True)),
            deep_enable=bool(get("DeepEnable", True)),
            compute_dtype=nn_mod.resolve_compute_dtype(
                get("ComputeDtype"), model_knob=None),
        )

    def __post_init__(self):
        object.__setattr__(self, "vocab_sizes",
                           tuple(int(v) for v in self.vocab_sizes))
        if len(self.vocab_sizes) != self.n_cat:
            raise ValueError(f"{self.n_cat} categorical columns but "
                             f"{len(self.vocab_sizes)} vocab_sizes")

    @classmethod
    def from_meta(cls, spec: Dict[str, Any]) -> "WDLSpec":
        """The spec a model file's meta holds. A file from before the
        ragged tables holds one padded `vocab_size` for every column and
        stacked (Cc, V, E) / (Cc, V) tables: equal-sized columns, whose
        tables `device_params` lays end to end."""
        fields = {k: v for k, v in spec.items() if k != "vocab_size"}
        sizes = spec.get("vocab_sizes") or \
            (spec["vocab_size"],) * int(spec["n_cat"])
        return cls(**{**fields, "vocab_sizes": sizes,
                      "hidden_dims": tuple(spec["hidden_dims"]),
                      "activations": tuple(spec["activations"])})

    @property
    def table_rows(self) -> int:
        return int(sum(self.vocab_sizes))

    @property
    def embed_pack(self) -> int:
        """Embedding rows side by side in one row of the device's table:
        as many as fill the 128 lanes, where E divides them."""
        e = self.embed_size
        return LANES // e if e and LANES % e == 0 else 1

    @property
    def deep_input_dim(self) -> int:
        return self.dense_dim + self.n_cat * self.embed_size

    @property
    def deep_spec(self) -> "nn_mod.MLPSpec":
        return nn_mod.MLPSpec(
            input_dim=self.deep_input_dim, hidden_dims=self.hidden_dims,
            activations=self.activations, output_dim=1,
            output_activation="linear",
            compute_dtype=self.compute_dtype)


LANES = 128
SCORE_BLOCK_ROWS = 16384


def table_offsets(vocab_sizes) -> Tuple[int, ...]:
    """First table row of each column."""
    return tuple(int(o) for o in
                 np.cumsum([0, *vocab_sizes[:-1]], dtype=np.int64))


def pack_table(table, pack: int):
    """(rows, E) → (ceil(rows / pack), pack·E): `pack` rows side by side,
    the row-major reshape of the same array (zero rows fill the last)."""
    xp = jnp if isinstance(table, jax.Array) else np
    short = -table.shape[0] % pack
    if short:
        table = xp.pad(table, ((0, short), (0, 0)))
    return table.reshape(-1, pack * table.shape[1])


def pad_tables(params: Dict[str, Any], multiple: int) -> Dict[str, Any]:
    """Zero rows after the last of both tables (one UNSTACKED parameter
    set), so that each one's row count is a multiple of `multiple`: a
    mesh's model axis divides a table by row only where the split is
    even, and the packed table and `wide_cat` differ in length. No
    lookup reaches the added rows and `file_params` drops them."""
    if multiple <= 1 or "embed" not in params:
        return params

    def padded(a):
        short = -a.shape[0] % multiple
        return jnp.pad(a, [(0, short)] + [(0, 0)] * (a.ndim - 1))

    return {**params, "embed": padded(params["embed"]),
            "wide_cat": padded(params["wide_cat"])}


def device_params(spec: WDLSpec, params: Dict[str, Any]) -> Dict[str, Any]:
    """A model file's parameters as `forward` takes them. The file holds
    `embed` as (ΣV, E), or stacked (Cc, V, E) with `wide_cat` (Cc, V) if
    it is from before the ragged tables: equal columns end to end ARE the
    ragged layout, so those reshape in place."""
    if "embed" not in params:
        return params
    embed = np.asarray(params["embed"])
    return {**params,
            "embed": pack_table(embed.reshape(-1, embed.shape[-1]),
                                spec.embed_pack),
            "wide_cat": np.reshape(params["wide_cat"], (-1,))}


def file_params(spec: WDLSpec, params: Dict[str, Any]) -> Dict[str, Any]:
    """Host parameters as a model file holds them: `embed` (ΣV, E), a
    view of the packed table, and `wide_cat` (ΣV,), both without the
    rows `pack_table` and `pad_tables` added."""
    if "embed" not in params:
        return params
    return {**params,
            "embed": np.asarray(params["embed"]).reshape(
                -1, spec.embed_size)[:spec.table_rows],
            "wide_cat": np.asarray(params["wide_cat"])[:spec.table_rows]}


def lookup(table, rows, width: int):
    """Rows of a lane-packed table: table (R, pack·width), rows any
    shape of plain row numbers → rows.shape + (width,). The gather moves
    whole packed rows and a select keeps the asked one; its transpose is
    a scatter-add of whole packed rows, zeros beside the row's own
    gradient, so duplicates accumulate and a neighbour's value is
    untouched bit for bit."""
    pack = table.shape[-1] // width
    got = table[rows // pack]
    if pack == 1:
        return got
    # lane l of a packed row belongs to the row's part l // width; the
    # parts are summed as lane slices (a (..., pack, width) view would be
    # tiled 8 x 128 on a TPU, eight times its size)
    mine = (rows % pack)[..., None] == jnp.arange(pack * width) // width
    got = jnp.where(mine, got, 0.0)
    return sum(got[..., k * width:(k + 1) * width] for k in range(pack))


def table_index(vocab_sizes, idx):
    """(N, Cc) per-column ids → rows of the ragged tables: an id past a
    column's vocabulary (missing, unseen) is the column's last slot."""
    sizes = np.asarray(vocab_sizes, np.int32)
    return jnp.clip(idx, 0, sizes - 1) + np.asarray(
        table_offsets(vocab_sizes), np.int32)


def init_params(spec: WDLSpec, key: jax.Array) -> Dict[str, Any]:
    k_embed, k_wide, k_deep = jax.random.split(key, 3)
    params: Dict[str, Any] = {}
    if spec.n_cat:
        params["embed"] = pack_table(jax.random.normal(
            k_embed, (spec.table_rows, spec.embed_size)) * 0.05,
            spec.embed_pack)
        params["wide_cat"] = jnp.zeros((spec.table_rows,))
    params["wide_dense"] = jnp.zeros((spec.dense_dim,))
    params["wide_bias"] = jnp.zeros(())
    params["deep"] = nn_mod.init_params(spec.deep_spec, k_deep)
    return params


def forward(spec: WDLSpec, params: Dict[str, Any], dense: jax.Array,
            idx: jax.Array) -> jax.Array:
    """(N, Dd) dense + (N, Cc) int32 indices → (N,) probability."""
    n = dense.shape[0] if spec.dense_dim else idx.shape[0]
    logit = jnp.zeros(n)
    deep_in = [dense] if spec.dense_dim else []
    if spec.n_cat:
        rows = table_index(spec.vocab_sizes, idx)
        if spec.wide_enable:
            with jax.named_scope("wide"):
                logit = logit + params["wide_cat"][rows].sum(axis=1)
        with jax.named_scope("embed"):
            emb = lookup(params["embed"], rows, spec.embed_size)  # (N,Cc,E)
        deep_in.append(emb.reshape(n, -1))
    with jax.named_scope("wide"):
        if spec.wide_enable and spec.dense_dim:
            logit = logit + dense @ params["wide_dense"]
        logit = logit + params["wide_bias"]
    if spec.deep_enable and deep_in:
        with jax.named_scope("deep"):
            deep_logit = nn_mod.forward(spec.deep_spec, params["deep"],
                                        jnp.concatenate(deep_in, axis=1))
        logit = logit + deep_logit
    return jax.nn.sigmoid(logit)


def loss_fn(spec: WDLSpec, params, dense, idx, y, w) -> jax.Array:
    """Weighted cross-entropy + L2 (WDL trains with log loss)."""
    p = forward(spec, params, dense, idx)
    eps = 1e-7
    per = -(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps))
    loss = jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1e-12)
    if spec.l2 > 0:
        reg = sum(jnp.sum(jnp.square(l["w"])) for l in params["deep"])
        if spec.n_cat:
            reg = reg + jnp.sum(jnp.square(params["embed"]))
        loss = loss + spec.l2 * reg
    return loss


def mse(spec: WDLSpec, params, dense, idx, y, w) -> jax.Array:
    """Weighted squared error (the validation metric). The rows go
    through in blocks of `SCORE_BLOCK_ROWS`, zero-weight rows filling
    the last: a lookup's temporaries (a gathered 128-lane row a lookup)
    stay a block large whatever the validation set's size."""
    n = y.shape[0]
    block = min(SCORE_BLOCK_ROWS, max(n, 1))
    n_blocks = -(-n // block)
    blocks = [jnp.pad(a, [(0, n_blocks * block - n)]
                      + [(0, 0)] * (a.ndim - 1)).reshape(
                          (n_blocks, block) + a.shape[1:])
              for a in (dense, idx, y, w)]

    def one(rows):
        d_, i_, y_, w_ = rows
        p = forward(spec, params, d_, i_)
        return jnp.sum(jnp.square(y_ - p) * w_)

    return jnp.sum(jax.lax.map(one, blocks)) / jnp.maximum(jnp.sum(w), 1e-12)


def predict(meta: Dict[str, Any], params: Any, dense: np.ndarray,
            idx: Optional[np.ndarray]) -> np.ndarray:
    spec = WDLSpec.from_meta(meta["spec"])
    params = device_params(spec, params)
    jd = jnp.asarray(dense if dense is not None else
                     np.zeros((idx.shape[0], 0), np.float32))
    ji = jnp.asarray(idx if idx is not None else
                     np.zeros((dense.shape[0], 0), np.int32))
    return np.asarray(forward(spec, jax.tree.map(jnp.asarray, params), jd, ji))
