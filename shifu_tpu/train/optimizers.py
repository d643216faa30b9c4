"""Optimizer mapping: Shifu `Propagation` codes → optax transforms.

The reference's master-side weight updater (`core/dtrain/Weight.java:
33,122-190`) implements BackProp(B) / QuickProp(Q) / Resilient(R) /
ADAM / AdaGrad / RMSProp / Momentum(M) / Nesterov(N) over flat float
arrays, applied once per BSP iteration to the aggregated full-batch
gradient. Here the same update rules are optax GradientTransformations
applied inside the jitted train step; RPROP and QuickProp (absent from
optax) are implemented natively below with the reference's constants
(initial delta 0.1, eta+ 1.2 / eta− 0.5, max step 50).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


# distinct settings whose static arguments `program_static` keeps alive
STATICS_KEPT = 16


def program_static(factory):
    """Memoise a factory of `train_bags_carry`'s static arguments (a
    loss/metric pair, an optimizer): equal arguments return the SAME
    object. jit finds a program by its statics' hash and equality, and
    a function's are its identity, so a loss or an optimizer built anew
    for every job would be a new program to it every time, traced,
    lowered and read back from the compile cache with the device
    waiting. The factory's arguments must be hashable and must hold
    EVERYTHING the result bakes into a trace: two settings that share a
    key would share a program. A small LRU: a grid search over a
    thousand settings keeps the last `STATICS_KEPT` here (what jit's
    own cache holds of a dropped one goes at jit's own eviction)."""
    return functools.lru_cache(maxsize=STATICS_KEPT)(factory)


class RPropState(NamedTuple):
    step: jax.Array
    deltas: Any
    prev_grad: Any


def rprop(init_delta: float = 0.1, eta_plus: float = 1.2,
          eta_minus: float = 0.5, max_delta: float = 50.0,
          min_delta: float = 1e-6) -> optax.GradientTransformation:
    """iRPROP− (`Weight.java` RESILIENTPROPAGATION branch; Encog
    ResilientPropagation constants). Sign-driven per-weight step sizes;
    learning rate is ignored, as in the reference."""

    def init(params):
        return RPropState(
            step=jnp.zeros([], jnp.int32),
            deltas=jax.tree.map(lambda p: jnp.full_like(p, init_delta), params),
            prev_grad=jax.tree.map(jnp.zeros_like, params))

    def update(grads, state, params=None):
        def new_delta(g, d, gp):
            sign = g * gp
            return jnp.where(sign > 0, jnp.minimum(d * eta_plus, max_delta),
                             jnp.where(sign < 0,
                                       jnp.maximum(d * eta_minus, min_delta),
                                       d))

        def eff_grad(g, gp):
            return jnp.where(g * gp < 0, 0.0, g)

        deltas = jax.tree.map(new_delta, grads, state.deltas, state.prev_grad)
        prev = jax.tree.map(eff_grad, grads, state.prev_grad)
        updates = jax.tree.map(lambda g, d: -jnp.sign(g) * d, prev, deltas)
        return updates, RPropState(state.step + 1, deltas, prev)

    return optax.GradientTransformation(init, update)


class QuickPropState(NamedTuple):
    step: jax.Array
    prev_grad: Any
    prev_update: Any


def quickprop(learning_rate: float, max_growth: float = 1.75
              ) -> optax.GradientTransformation:
    """QuickProp (`Weight.java` QUICKPROPAGATION branch; Fahlman 1988):
    quadratic step dw = dw_prev * g / (g_prev − g), growth-capped, with
    gradient-descent fallback on the first step / unstable denominator."""

    def init(params):
        return QuickPropState(
            step=jnp.zeros([], jnp.int32),
            prev_grad=jax.tree.map(jnp.zeros_like, params),
            prev_update=jax.tree.map(jnp.zeros_like, params))

    def update(grads, state, params=None):
        first = state.step == 0

        def per_leaf(g, gp, up):
            denom = gp - g
            quick = up * g / jnp.where(jnp.abs(denom) < 1e-12, 1e-12, denom)
            cap = jnp.abs(up) * max_growth
            quick = jnp.clip(quick, -jnp.maximum(cap, 1e-12),
                             jnp.maximum(cap, 1e-12))
            gd = -learning_rate * g
            use_gd = first | (jnp.abs(up) < 1e-12) | (jnp.abs(denom) < 1e-12)
            new_up = jnp.where(use_gd, gd, quick)
            return new_up

        updates = jax.tree.map(per_leaf, grads, state.prev_grad,
                               state.prev_update)
        return updates, QuickPropState(state.step + 1, grads, updates)

    return optax.GradientTransformation(init, update)


@program_static
def make_optimizer(propagation: str, learning_rate: float,
                   learning_decay: float = 0.0,
                   momentum: float = 0.5,
                   adam_beta1: float = 0.9, adam_beta2: float = 0.999,
                   reg_l2_decay: float = 0.0) -> optax.GradientTransformation:
    """`Weight.calculateWeights` dispatch. learning_decay shrinks the
    rate each epoch: lr_t = lr · (1 − decay)^t (Weight.java
    learningDecay semantics). The same object for the same values
    (`program_static`): the rate is a constant of the program that
    trains with it, a decay schedule a lambda inside it."""
    p = (propagation or "Q").strip().upper()
    if learning_decay > 0.0:
        sched = lambda step: learning_rate * (1.0 - learning_decay) ** step  # noqa: E731
    else:
        sched = learning_rate
    if p in ("B", "BACKPROP", "SGD"):
        return optax.sgd(sched)
    if p in ("Q", "QUICK", "QUICKPROP"):
        return quickprop(learning_rate)
    if p in ("R", "RESILIENT", "RPROP"):
        return rprop()
    if p in ("M", "MOMENTUM"):
        return optax.sgd(sched, momentum=momentum)
    if p in ("N", "NESTEROV"):
        return optax.sgd(sched, momentum=momentum, nesterov=True)
    if p == "ADAM":
        return optax.adam(sched, b1=adam_beta1, b2=adam_beta2)
    if p == "ADAGRAD":
        return optax.adagrad(sched)
    if p == "RMSPROP":
        return optax.rmsprop(sched)
    raise ValueError(f"unknown Propagation {propagation!r}")


def optimizer_from_params(params: Dict[str, Any]) -> optax.GradientTransformation:
    def get(key, default=None):
        for k, v in params.items():
            if k.lower() == key.lower():
                return v
        return default

    return make_optimizer(
        propagation=str(get("Propagation", "Q")),
        learning_rate=float(get("LearningRate", 0.1) or 0.1),
        learning_decay=float(get("LearningDecay", 0.0) or 0.0),
        momentum=float(get("Momentum", 0.5) or 0.5),
        adam_beta1=float(get("AdamBeta1", 0.9) or 0.9),
        adam_beta2=float(get("AdamBeta2", 0.999) or 0.999))
