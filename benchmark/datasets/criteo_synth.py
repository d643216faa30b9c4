"""Criteo-shaped synthetic rows from a key, made on the device.

The shape is the Kaggle Criteo Display Advertising table's: 13 numeric and
26 categorical columns and a binary click label. Everything else is this
file's, from the configuration's keys:

- numeric columns: N(0,1) clipped at 4, as `shifu norm` (ZSCALE) hands
  them to train;
- ids: column c has n_c = `vocab_sizes[c]` - 1 real ids and one missing
  slot (id n_c), drawn with probability `missing_rate`. A real id is a
  rank drawn from a power law with exponent `zipf_exponent` (s) over
  1..n_c, by inverting the law's continuous form in float32:
  rank = floor((1 + u ((n_c+1)^(1-s) - 1))^(1/(1-s))), u uniform in [0,1),
  so P(rank = k) = (k^(1-s) - (k+1)^(1-s)) / (1 - (n_c+1)^(1-s))
  (`rank_probabilities`: what `work/wdl.py` derives its bytes from); the
  rank is then spread over the column's ids by a fixed bijection,
  id = ((rank-1) A1 mod n_c) A2 + B mod n_c with A1, A2 the first primes of
  `SPREAD_PRIMES` that do not divide n_c, so the hot ids do not sit side by
  side in the table;
- label: Bernoulli(sigmoid(`label_scale` * logit + `label_shift`)), the
  logit a weighted sum of the first `label_dense` numeric columns and of a
  fixed N(0,1) effect an id of the `label_columns` (0 for a missing id).
  The effects come from the seed's stream 2, so the training rows (stream
  0) and the validation rows (stream 1) of one seed share them.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.datasets.higgs_synth import seed_key  # noqa: F401

N_DENSE = 13
STD_DEV_CUTOFF = 4.0
MAX_BLOCK_ROWS = 1 << 18
SPREAD_PRIMES = (1009, 757, 947, 811, 983, 859)
SPREAD_SHIFT = 12345


def real_ids(config):
    """n_c: the ids of each column that are not its missing slot."""
    return np.asarray(config["vocab_sizes"], np.int64) - 1


def rank_probabilities(n: int, exponent: float):
    """P(rank = k), k = 1..n, of the draw above, in float64."""
    k = np.arange(1, n + 2, dtype=np.float64) ** (1.0 - exponent)
    return (k[:-1] - k[1:]) / (1.0 - k[-1])


def spread_constants(n_real):
    """Per column (A1, A2, B): two primes that do not divide n_c and the
    shift, all below 2^10 or n_c so that every product fits 32 bits."""
    out = []
    for n in n_real:
        primes = [p for p in SPREAD_PRIMES if n % p][:2]
        out.append((primes[0], primes[1], SPREAD_SHIFT % n))
    return np.asarray(out, np.uint32).T


def spread(rank0, n, consts):
    """The fixed bijection of 0..n-1 (uint32 throughout; rank0 < 2^22)."""
    a1, a2, b = consts
    return ((rank0 * a1) % n * a2 + b) % n


def _constants(config):
    n = real_ids(config)
    if n.max() >= 1 << 22 or n.min() < 1:
        raise ValueError("a column's ids must number 1 to 2^22 - 1")
    s = float(config["zipf_exponent"])
    span = (n + 1.0) ** (1.0 - s) - 1.0
    return n, s, span.astype(np.float32), spread_constants(n)


def id_effects(config, seed: int):
    """[(column, weight, (n_c + 1,) effects, the missing slot's 0)]."""
    n = real_ids(config)
    world = seed_key(seed, 2)
    return [(c, wt, jnp.concatenate([
        jax.random.normal(jax.random.fold_in(world, c), (int(n[c]),)),
        jnp.zeros((1,))]))
        for c, wt in zip(config["label_columns"], config["label_weights"])]


def block(key, b: int, config, effects):
    """One block of b rows: ((b, 13) float32, (b, 26) int32, (b,) labels)."""
    n, s, span, consts = _constants(config)
    kx, ku, km, ky = jax.random.split(key, 4)
    dense = jnp.clip(jax.random.normal(kx, (b, N_DENSE), jnp.float32),
                     -STD_DEV_CUTOFF, STD_DEV_CUTOFF)
    u = jax.random.uniform(ku, (b, len(n)), jnp.float32)
    rank = jnp.floor((1.0 + u * span) ** np.float32(1.0 / (1.0 - s)))
    n32 = n.astype(np.uint32)
    rank0 = jnp.clip(rank, 1, n.astype(np.float32)).astype(jnp.uint32) - 1
    ids = spread(rank0, n32, consts).astype(jnp.int32)
    missing = jax.random.uniform(km, ids.shape) < config["missing_rate"]
    ids = jnp.where(missing, n.astype(np.int32), ids)
    dw = jnp.linspace(1.0, 0.4, config["label_dense"], dtype=jnp.float32)
    logit = dense[:, :config["label_dense"]] @ dw
    for c, wt, eff in effects:
        logit = logit + wt * eff[ids[:, c]]
    p = jax.nn.sigmoid(config["label_scale"] * logit + config["label_shift"])
    y = jax.random.uniform(ky, (b,)) < p
    return dense, ids, y.astype(jnp.float32)


def fill(key, n_rows: int, config, effects):
    """(dense, ids, y) of n_rows rows, filled block by block inside one
    program so that temporaries stay one block large."""
    n_blocks = -(-n_rows // MAX_BLOCK_ROWS)
    b = -(-n_rows // n_blocks)

    def body(i, outs):
        start = jnp.minimum(i * b, n_rows - b)
        made = block(jax.random.fold_in(key, i), b, config, effects)
        return tuple(jax.lax.dynamic_update_slice(
            o, m, (start,) + (0,) * (o.ndim - 1)) for o, m in zip(outs, made))

    outs = (jnp.zeros((n_rows, N_DENSE), jnp.float32),
            jnp.zeros((n_rows, len(config["vocab_sizes"])), jnp.int32),
            jnp.zeros((n_rows,), jnp.float32))
    return jax.lax.fori_loop(0, n_blocks, body, outs)
