"""Feed-forward NN — the flagship model family, as a JAX pytree.

Replaces the reference's Encog-derived float network stack
(`core/dtrain/dataset/FloatFlatNetwork.java`, `BasicFloatNetwork`,
backprop kernel `core/dtrain/Gradient.java:171-194`) with a functional
MLP: parameters are a pytree, the forward pass is pure, gradients come
from `jax.grad`, and the whole train step jits onto the MXU as batched
matmuls — per-record Java loops become (batch × features) GEMMs.

Config surface matches `train#params` of the reference
(`ModelTrainConf.createParamsByAlg`, NNTrainer/NNMaster):
NumHiddenLayers, NumHiddenNodes, ActivationFunc, RegularizedConstant,
L1orL2, Propagation, LearningRate, LearningDecay, DropoutRate,
WeightInitializer, Loss, FixedLayers, Momentum/AdamBeta1/AdamBeta2.

Activations mirror `core/dtrain/layer/activation/*`
(Sigmoid, TanH, ReLU, LeakyReLU, Swish, Gaussian, Log, Sin, Linear).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tpu.config.environment import knob_is_set, knob_str

Params = List[Dict[str, jax.Array]]


def resolve_compute_dtype(explicit: Optional[str] = None,
                          model_knob: Optional[str] =
                          "SHIFU_TPU_NN_COMPUTE") -> str:
    """One precedence chain for the mixed-precision dtype, shared by
    NN/WDL/MTL: explicit train#params ComputeDtype > the model-family
    env knob (set) > package-wide SHIFU_TPU_COMPUTE_DTYPE > float32.
    Returns the normalized name ("float32" | "bfloat16")."""
    cd = explicit
    if cd is None and model_knob and knob_is_set(model_knob):
        cd = knob_str(model_knob)
    if cd is None:
        cd = knob_str("SHIFU_TPU_COMPUTE_DTYPE")
    cd = str(cd or "float32").lower()
    return "bfloat16" if cd in ("bf16", "bfloat16") else "float32"


def mm_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    """Matmul that always accumulates in f32: bf16×bf16 operands hit
    the MXU's low-precision path but the product leaves the unit as
    f32 (preferred_element_type), so reductions never round in bf16."""
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Activations (core/dtrain/layer/activation/*.java + ActivationFactory)
# ---------------------------------------------------------------------------

def _log_act(x):
    """Encog ActivationLOG: sign-symmetric log."""
    return jnp.where(x >= 0, jnp.log1p(x), -jnp.log1p(-x))


ACTIVATIONS: Dict[str, Callable] = {
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "leakyrelu": lambda x: jax.nn.leaky_relu(x, 0.01),
    "swish": lambda x: x * jax.nn.sigmoid(x),
    "gaussian": lambda x: jnp.exp(-jnp.square(x)),
    "log": _log_act,
    "sin": jnp.sin,
    "linear": lambda x: x,
    "ptanh": jnp.tanh,  # reference alias
}


def activation(name: str) -> Callable:
    fn = ACTIVATIONS.get(str(name).lower())
    if fn is None:
        raise ValueError(f"unknown ActivationFunc {name!r}; known: "
                         f"{sorted(ACTIVATIONS)}")
    return fn


# ---------------------------------------------------------------------------
# Architecture
# ---------------------------------------------------------------------------

def param_getter(params: Dict[str, Any]):
    """Case-insensitive train#params lookup (reference keys are
    TitleCase: NumHiddenLayers, LearningRate, ...). Shared by every
    model family's from_train_params."""
    def get(key, default=None):
        for k, v in params.items():
            if k.lower() == key.lower():
                return v
        return default
    return get


def parse_arch_params(params: Dict[str, Any],
                      default_nodes=(50,), default_acts=("tanh",),
                      honor_num_layers: bool = True):
    """Normalize NumHiddenNodes / ActivationFunc lists (scalars become
    one-element lists; short lists repeat their tail; NumHiddenLayers
    truncates/extends when honored). Returns (nodes, acts)."""
    get = param_getter(params)
    nodes = get("NumHiddenNodes", list(default_nodes))
    acts = get("ActivationFunc", list(default_acts))
    if not isinstance(nodes, list):
        nodes = [nodes]
    if not isinstance(acts, list):
        acts = [acts]
    nodes = [int(n) for n in nodes]
    acts = [str(a) for a in acts]
    if honor_num_layers:
        n_layers = int(get("NumHiddenLayers", len(nodes)) or 0)
        nodes = nodes[:n_layers]
        acts = acts[:n_layers]
        while len(nodes) < n_layers:
            nodes.append(nodes[-1] if nodes else int(default_nodes[0]))
    while len(acts) < len(nodes):
        acts.append(acts[-1] if acts else str(default_acts[0]))
    return tuple(nodes), tuple(acts[:len(nodes)])


@dataclass(frozen=True)
class MLPSpec:
    """Static architecture derived from train#params. Frozen/hashable so
    it can be a static argument of jitted train steps; list-like fields
    are tuples."""
    input_dim: int
    hidden_dims: tuple
    activations: tuple
    output_dim: int = 1
    output_activation: str = "sigmoid"  # Encog nets end in sigmoid for binary
    dropout_rate: float = 0.0
    l2: float = 0.0
    l1: float = 0.0
    loss: str = "squared"  # squared | log | absolute (core/dtrain/loss/*)
    weight_init: str = "xavier"  # xavier | he | lecun | zero | default
    # "bfloat16" runs the GEMMs/activations in bf16 while master
    # weights, gradients and the optimizer stay f32 (mixed precision:
    # halves the HBM bytes per epoch — the wide-net training path is
    # memory-bound before it is MXU-bound). train#params ComputeDtype
    # or SHIFU_TPU_NN_COMPUTE=bfloat16.
    compute_dtype: str = "float32"

    @classmethod
    def from_train_params(cls, params: Dict[str, Any], input_dim: int,
                          output_dim: int = 1) -> "MLPSpec":
        get = param_getter(params)
        nodes, acts = parse_arch_params(params)
        reg = float(get("RegularizedConstant", 0.0) or 0.0)
        l1orl2 = str(get("L1orL2", "L2") or "L2").upper()
        cd = resolve_compute_dtype(get("ComputeDtype"))
        return cls(
            input_dim=input_dim, hidden_dims=nodes,
            activations=acts, output_dim=output_dim,
            dropout_rate=float(get("DropoutRate", 0.0) or 0.0),
            l2=reg if l1orl2 != "L1" else 0.0,
            l1=reg if l1orl2 == "L1" else 0.0,
            loss=str(get("Loss", "squared") or "squared").lower(),
            weight_init=str(get("WeightInitializer", "xavier") or "xavier").lower(),
            compute_dtype=cd,
        )

    @property
    def layer_dims(self) -> List[int]:
        return [self.input_dim] + list(self.hidden_dims) + [self.output_dim]


def init_params(spec: MLPSpec, key: jax.Array) -> Params:
    """Weight init families from `core/dtrain/random/*`
    (Xavier/He/Lecun + uniform default)."""
    params: Params = []
    dims = spec.layer_dims
    for i in range(len(dims) - 1):
        key, sub = jax.random.split(key)
        fan_in, fan_out = dims[i], dims[i + 1]
        if spec.weight_init in ("he", "lecun"):
            # the barrier keeps the scale a multiply of its own inside a
            # jit too (`trainer.fresh_nn_state`), where XLA would fold it
            # into the draw's own constant: the eager bits either way
            w = jax.lax.optimization_barrier(
                jax.random.normal(sub, (fan_in, fan_out))) * math.sqrt(
                    (2.0 if spec.weight_init == "he" else 1.0) / fan_in)
        elif spec.weight_init == "zero":
            w = jnp.zeros((fan_in, fan_out))
        else:  # xavier / default
            limit = math.sqrt(6.0 / (fan_in + fan_out))
            w = jax.random.uniform(sub, (fan_in, fan_out), minval=-limit,
                                   maxval=limit)
        params.append({"w": w.astype(jnp.float32),
                       "b": jnp.zeros((fan_out,), jnp.float32)})
    return params


def compare_structure(old_dims: Sequence[int],
                      new_dims: Sequence[int]) -> int:
    """0 = identical, 1 = the new network can absorb the old one,
    -1 = it cannot (`NNStructureComparator.compare`: input count,
    output count, and per-layer feed counts aligned at the input end
    must all be >=; `TrainModelProcessor.inputOutputModelCheckSuccess:
    1389-1450` additionally requires equal output counts, which is the
    check used here since the output layer's meaning must not change).
    `*_dims` are forward-order layer widths [input, *hidden, output]."""
    old, new = list(old_dims), list(new_dims)
    if old == new:
        return 0
    if len(new) < len(old) or new[-1] != old[-1]:
        return -1
    # input-end alignment: old layer i ↔ new layer i (extra new layers
    # sit nearest the output, mirroring fitExistingModelIn's
    # toLayer = toLen - (fromLen - layer) walk over Encog's
    # output-first arrays). Every aligned old width — INCLUDING the old
    # output when depth grows (it lands on a hidden layer) — must fit.
    ok = all(new[i] >= old[i] for i in range(len(old)))
    return 1 if ok else -1


def absorb_params(old_params: Params, new_params: Params,
                  fixed_layers: Optional[Sequence[int]] = None,
                  fixed_bias: bool = True):
    """Fit a smaller trained network into a freshly-initialized larger
    one (`NNMaster.fitExistingModelIn:644-684`): each old layer's
    weight matrix copies into the top-left corner of the aligned new
    layer, biases into the leading slots. Returns (params, grad_mask)
    where grad_mask zeros the absorbed positions of 1-based
    `fixed_layers` (the reference freezes only the copied indices —
    the grown portion of a fixed layer still trains).

    TPU-first deviation, documented: the cross-block rows
    w[old_in:, :old_out] of every absorbed layer are ZEROED, so the
    grown units feed the absorbed units nothing at step 0 — for
    same-depth growth the new network starts as an exact functional
    copy of the old model (validation error resumes where it left
    off), instead of the reference's randomly-perturbed start. The
    zeros are trainable unless the layer is fixed."""
    params = [dict(layer) for layer in new_params]
    grad_mask = [
        {k: jnp.ones_like(v) for k, v in layer.items()}
        for layer in new_params]
    fixed = {int(f) for f in (fixed_layers or ())}
    for i, old_layer in enumerate(old_params):
        oi, oo = old_layer["w"].shape
        w = params[i]["w"]
        w = w.at[:oi, :oo].set(jnp.asarray(old_layer["w"]))
        w = w.at[oi:, :oo].set(0.0)
        params[i]["w"] = w
        params[i]["b"] = params[i]["b"].at[:oo].set(
            jnp.asarray(old_layer["b"]))
        if (i + 1) in fixed:
            # freeze exactly the absorbed indices (getFixedWights /
            # fitExistingModelIn add only copied weights to the set)
            mw = grad_mask[i]["w"].at[:oi, :oo].set(0.0)
            grad_mask[i]["w"] = mw
            if fixed_bias:
                grad_mask[i]["b"] = grad_mask[i]["b"].at[:oo].set(0.0)
    return params, grad_mask


def forward(spec: MLPSpec, params: Params, x: jax.Array,
            dropout_key: Optional[jax.Array] = None) -> jax.Array:
    """Batched forward pass → (N,) score in (0,1) for binary output.
    Dropout (train-time only) mirrors NNMaster's per-iteration node
    sampling (`NNMaster.doCompute:323` dropout nodes)."""
    return forward_from_sums(spec, params, first_layer_sums(spec, params, x),
                             dropout_key)


def _cast(spec: MLPSpec):
    # bfloat16 compute: GEMM operands and stored activations in bf16,
    # accumulation pinned to f32 (mm_f32's preferred_element_type), so
    # bias-add, activation and every reduction happen in f32; master
    # params/grads stay f32 — autodiff through the casts yields f32
    # grads, so the optimizer and checkpoints are unchanged. Halves the
    # HBM bytes the wide training shape streams per epoch.
    if spec.compute_dtype == "bfloat16":
        return lambda a: a.astype(jnp.bfloat16)
    return lambda a: a


def first_layer_sums(spec: MLPSpec, params: Params,
                     x: jax.Array) -> jax.Array:
    """x W1 + b1, (N, units of the first layer): the first hidden
    layer's sums, or for LR the logit. The product multiplies in
    `first_layer_operand_dtype(spec)`."""
    cast = _cast(spec)
    x = cast(x)
    # one named scope a layer (metadata only): a profiler trace then
    # names each device op by its layer, forward and backward
    with jax.named_scope("layer0"):
        return mm_f32(x, cast(params[0]["w"])) + params[0]["b"]


# jax's default matmul precisions that multiply float32 in one
# bfloat16 pass on a TPU
_ONE_PASS = (None, "default", "bfloat16", "fastest")


def first_layer_operand_dtype(spec: MLPSpec):
    """The dtype `first_layer_sums`' product multiplies in: bfloat16
    where the spec computes in it, or where jax's default precision on
    a TPU rounds float32 operands for one MXU pass; float32 elsewhere:
    a CPU, a precision above the default, and a product onto a single
    unit (an LR logit), which XLA computes on a TPU as a float32
    multiply and reduce on the vector unit. A caller that takes a
    column's term out of the sums rounds it so (`processor/sensitivity`)."""
    if spec.compute_dtype == "bfloat16":
        return jnp.bfloat16
    if (jax.default_backend() == "tpu" and spec.layer_dims[1] > 1
            and jax.config.jax_default_matmul_precision in _ONE_PASS):
        return jnp.bfloat16
    return jnp.float32


def forward_from_sums(spec: MLPSpec, params: Params, z: jax.Array,
                      dropout_key: Optional[jax.Array] = None,
                      rows_last: bool = False) -> jax.Array:
    """`forward` from the first layer's sums z (`first_layer_sums`) on:
    what Shifu's `CacheBasicFloatNetwork` scores from a row's cached
    first layer. z is (..., N, units), or with `rows_last` (..., units,
    N), the rows on the minor axis; the scores are (..., N) for one
    output, else (..., N, outputs) or (..., outputs, N)."""
    cast = _cast(spec)
    units = -2 if rows_last else -1

    def dense(h, layer):
        if rows_last:
            return jnp.einsum("...ir,io->...or", h, cast(layer["w"]),
                              preferred_element_type=jnp.float32) \
                + layer["b"][:, None]
        return mm_f32(h, cast(layer["w"])) + layer["b"]

    h = z
    for i, layer in enumerate(params[:-1]):
        with jax.named_scope(f"layer{i}"):
            if i:
                h = dense(h, layer)
            h = activation(spec.activations[i])(h)
            if dropout_key is not None and spec.dropout_rate > 0.0:
                dropout_key, sub = jax.random.split(dropout_key)
                keep = jax.random.bernoulli(sub, 1.0 - spec.dropout_rate,
                                            h.shape)
                h = jnp.where(keep, h / (1.0 - spec.dropout_rate),
                              jnp.zeros((), h.dtype))
            h = cast(h)
    with jax.named_scope(f"layer{len(params) - 1}"):
        out = dense(h, params[-1]) if len(params) > 1 else h
        if spec.output_activation == "softmax":
            # multi-class NATIVE head: one unit per flattened tag
            # (train#multiClassifyMethod NATIVE — the reference builds an
            # Encog net with tags.size() output neurons)
            return jax.nn.softmax(out, axis=units)
        out = activation(spec.output_activation)(out)
        if spec.output_dim > 1:
            return out
        return out[..., 0, :] if rows_last else out[..., 0]


def penalty(spec: MLPSpec, params: Params):
    """The L1/L2 regularization terms of the loss (`Weight.java` reg
    terms); 0.0 where the spec sets neither."""
    reg = 0.0
    if spec.l2 > 0.0:
        reg = reg + spec.l2 * sum(jnp.sum(jnp.square(p["w"])) for p in params)
    if spec.l1 > 0.0:
        reg = reg + spec.l1 * sum(jnp.sum(jnp.abs(p["w"])) for p in params)
    return reg


def loss_fn(spec: MLPSpec, params: Params, x: jax.Array, y: jax.Array,
            w: jax.Array, dropout_key: Optional[jax.Array] = None) -> jax.Array:
    """Weighted loss (`core/dtrain/loss/*`: squared / log / absolute) +
    L1/L2 regularization (`Weight.java` reg terms). Weights double as
    bagging sample multipliers (Poisson/Bernoulli masks)."""
    pred = forward(spec, params, x, dropout_key)
    if spec.output_dim > 1:
        # multi-class: y holds class indices; cross-entropy on the
        # softmax probabilities (log loss) or Brier vs one-hot (squared)
        onehot = jax.nn.one_hot(y.astype(jnp.int32), spec.output_dim)
        if spec.loss.startswith("log"):
            per = -jnp.sum(onehot * jnp.log(pred + 1e-7), axis=-1)
        else:
            per = 0.5 * jnp.sum(jnp.square(onehot - pred), axis=-1)
        total_w = jnp.maximum(jnp.sum(w), 1e-12)
        return jnp.sum(per * w) / total_w + penalty(spec, params)
    if spec.loss.startswith("log"):
        eps = 1e-7
        per = -(y * jnp.log(pred + eps) + (1 - y) * jnp.log(1 - pred + eps))
    elif spec.loss.startswith("abs"):
        per = jnp.abs(y - pred)
    else:
        per = 0.5 * jnp.square(y - pred)
    total_w = jnp.maximum(jnp.sum(w), 1e-12)
    return jnp.sum(per * w) / total_w + penalty(spec, params)


def mse(spec: MLPSpec, params: Params, x: jax.Array, y: jax.Array,
        w: jax.Array) -> jax.Array:
    """Validation error metric — the reference reports mean squared error
    per epoch regardless of training loss (NNMaster trainError)."""
    pred = forward(spec, params, x)
    total_w = jnp.maximum(jnp.sum(w), 1e-12)
    if spec.output_dim > 1:
        onehot = jax.nn.one_hot(y.astype(jnp.int32), spec.output_dim)
        per = jnp.mean(jnp.square(onehot - pred), axis=-1)
        return jnp.sum(per * w) / total_w
    return jnp.sum(jnp.square(y - pred) * w) / total_w


def num_params(spec: MLPSpec) -> int:
    dims = spec.layer_dims
    return sum(dims[i] * dims[i + 1] + dims[i + 1] for i in range(len(dims) - 1))
