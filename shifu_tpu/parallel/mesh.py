"""Device-mesh construction and sharding layouts.

This module is the whole replacement for the reference's distributed
substrate (Guagua master–worker over YARN + Netty parameter shipping +
ZooKeeper coordination, SURVEY.md §2.9): in SPMD JAX there is no
master — the "aggregate worker gradients" step IS the psum XLA inserts
when a mean over a row-sharded matrix feeds replicated parameter
updates; "broadcast new weights" is the replicated sharding of params.
One jitted train step under a Mesh replaces the whole BSP protocol,
with collectives riding ICI (and DCN between hosts via
`jax.distributed`, see parallel/dist.py).

Axes:
- "data": rows of the feature matrix (the reference's worker-split
  axis; ~150MB/worker sizing in TrainModelProcessor.java:1789-1838
  becomes simply R/n_devices rows per chip);
- "model": wide parameter dimensions — MLP hidden units (tensor
  parallel) and WDL per-column embedding tables (the expert-parallel
  analog for tabular data).
"""

from __future__ import annotations

import functools
import logging
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from shifu_tpu.config.environment import knob_int, knob_str

log = logging.getLogger("shifu_tpu")


_MESH_CACHE: dict = {}


# ---------------------------------------------------------------------------
# logical→physical axis rules
# ---------------------------------------------------------------------------

# the LOGICAL tensor-dimension names the layouts below speak, mapped to
# the physical mesh axis each shards over (None = replicate). Layouts
# written against these names re-resolve on whatever mesh the process
# actually has, which is what makes a checkpoint's sharding sidecar
# topology-portable: "rows over 'data', hidden units over 'model'" is
# meaningful on 1, 4, 8 or 16 devices, while "split 2 ways over chips
# 6-7" is not.
_DEFAULT_RULES: Dict[str, Optional[str]] = {
    "rows": "data",      # feature-matrix rows (the worker-split axis)
    "hidden": "model",   # MLP hidden units (Megatron split)
    "vocab": "model",    # WDL embedding/wide table rows (all columns' ids)
    "task": "model",     # MTL per-task head rows
}


class MeshRules:
    """Logical→physical mesh-axis mapping. `rules("rows", "hidden")`
    resolves logical tensor-dimension names to physical axis names
    (unknown names resolve to None = replicated); `rules.spec(...)`
    wraps the resolution in a PartitionSpec. Overrides come from
    SHIFU_TPU_MESH_RULES ("hidden=,vocab=data" — an empty right side
    replicates that logical axis)."""

    def __init__(self, overrides: Optional[Dict[str, Optional[str]]] = None):
        self._rules = dict(_DEFAULT_RULES)
        if overrides:
            self._rules.update(overrides)

    def __call__(self, *logical: Optional[str]) -> Tuple[Optional[str], ...]:
        # a physical mesh axis may shard at most one positional dim; the
        # first logical name to claim it wins, later claims replicate
        out: list = []
        used: set = set()
        for n in logical:
            ax = self._rules.get(n) if n else None
            if ax is not None and ax in used:
                ax = None
            if ax is not None:
                used.add(ax)
            out.append(ax)
        return tuple(out)

    def spec(self, *logical: Optional[str]) -> P:
        return P(*self(*logical))

    def to_dict(self) -> Dict[str, Optional[str]]:
        return dict(self._rules)


def _parse_rules_env(raw: str) -> Dict[str, Optional[str]]:
    out: Dict[str, Optional[str]] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"bad SHIFU_TPU_MESH_RULES entry {part!r}: want "
                "logical=physical (empty physical = replicate)")
        k, _, v = part.partition("=")
        out[k.strip()] = v.strip() or None
    return out


def default_rules() -> MeshRules:
    """The process-wide rules: package defaults plus any
    SHIFU_TPU_MESH_RULES overrides."""
    raw = knob_str("SHIFU_TPU_MESH_RULES")
    return MeshRules(_parse_rules_env(raw) if raw else None)


_devices_enumerated = False


def leased_devices(devs: Optional[Sequence] = None):
    """The device-slice lease seam: the devices THIS process may build
    meshes over. When the DAG scheduler leased this process a slice it
    exported SHIFU_TPU_DEVICE_SLICE=i,j,k — filter `devs` (default:
    all devices) down to those ids so `default_mesh`/`local_mesh` and
    every jit/shard_map path behind them inherit the placement with
    zero call-site changes. No slice env means the whole set.

    TPU runtimes that honor chip-visibility env (TPU_VISIBLE_DEVICES,
    exported alongside the slice) renumber devices from 0, so the
    leased ids may match nothing: when the visible set is already no
    larger than the lease, visibility did the narrowing — return it.
    A partial match or an oversized visible set is a placement bug and
    raises rather than silently running on chips another node leased.
    """
    global _devices_enumerated
    _devices_enumerated = True
    if devs is None:
        devs = jax.devices()
    devs = list(devs)
    raw = knob_str("SHIFU_TPU_DEVICE_SLICE")
    if not raw:
        return devs
    try:
        want = {int(p) for p in raw.split(",") if p.strip()}
    except ValueError as e:
        raise ValueError(
            f"bad SHIFU_TPU_DEVICE_SLICE={raw!r}: want comma-separated "
            "device ids (the DAG scheduler exports this; do not hand-"
            "edit)") from e
    picked = [d for d in devs if d.id in want]
    if len(picked) == len(want):
        return picked
    if not picked and len(devs) <= len(want):
        return devs   # runtime renumbered after visibility narrowing
    raise RuntimeError(
        f"SHIFU_TPU_DEVICE_SLICE={raw!r} leased {len(want)} device(s) "
        f"but only {len(picked)} of {len(devs)} visible ids match — "
        "refusing to build a mesh over chips outside the lease")


def leased_local_devices():
    """`leased_devices` over this process's addressable devices — the
    count the streaming data plane pads per-process chunk blocks to."""
    return leased_devices(jax.local_devices())


def devices_enumerated() -> bool:
    """Whether THIS process has asked the runtime for its devices
    through the lease seam — i.e. holds a backend it may report on.
    A DAG/combo parent (and every pure file command) answers False;
    metrics use this instead of peeking at jax internals, so merely
    recording a step never creates a backend."""
    return _devices_enumerated


@functools.lru_cache(maxsize=1)
def device_inventory() -> int:
    """The local device pool size the DAG slice allocator leases from.
    Counted in a short-lived CHILD process: the caller is the scheduler
    parent, which must never create a backend itself — a chip belongs
    to one process at a time, so a parent that asked the runtime would
    own the chip and every device node it then starts would fail or
    hang. The child exits (releasing the chip) before the first node
    starts. SHIFU_TPU_DAG_DEVICES skips the probe altogether."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(len(jax.local_devices()))"],
        capture_output=True, text=True, timeout=300, check=True)
    return int(out.stdout.strip().splitlines()[-1])


def _knobbed_mesh(devs, cache_tag: str) -> Mesh:
    """The shared default_mesh/local_mesh body: apply the device-count
    cap and model-axis carve knobs to `devs` and cache the result."""
    cap = knob_int("SHIFU_TPU_MESH_DEVICES")
    n = min(int(cap), len(devs)) if cap else len(devs)
    # SHIFU_TPU_MESH_MODEL=K carves K devices onto the 'model' axis for
    # vocab-heavy WDL/MTL configs (embedding tables sharded instead of
    # replicated); default 1 = pure data parallel, the reference's only
    # strategy
    n_model = knob_int("SHIFU_TPU_MESH_MODEL") or 1
    if n_model < 1 or n % n_model != 0:
        raise ValueError(
            f"SHIFU_TPU_MESH_MODEL={n_model} must divide the device "
            f"count {n}")
    key = (cache_tag, n, n_model, tuple(d.id for d in devs[:n]))
    m = _MESH_CACHE.get(key)
    if m is None:
        m = make_mesh(n_data=n // n_model, n_model=n_model,
                      devices=devs[:n])
        _MESH_CACHE[key] = m
    return m


def default_mesh() -> Mesh:
    """The process-wide data mesh every processor executes over by
    default — the round-2 replacement for 'workers': on one chip it is
    a 1-device mesh (the reference's LOCAL mode), on a TPU host it is
    all chips, multi-host it is all global devices (DCN via
    parallel/dist.initialize). SHIFU_TPU_MESH_DEVICES=N caps the
    device count (tests use it to compare 8-device vs 1-device runs).
    A process the DAG scheduler leased a device slice to builds over
    ONLY that slice (`leased_devices`).
    """
    return _knobbed_mesh(leased_devices(), "global")


def local_mesh() -> Mesh:
    """default_mesh restricted to THIS process's addressable devices
    (same cap and model-axis knobs; single-host the two coincide). The
    sharded streaming data plane computes per-chunk partials on this
    mesh: hosts iterate DISJOINT chunk streams, so a global-mesh
    computation — an SPMD program every process must enter in lockstep
    with matching shapes — would desync the pod; a fully-addressable
    mesh keeps each chunk's math local, and identical to what a
    single-host run does for that chunk (bitwise parity of the replay
    merge, given equal per-host device counts — the same assumption
    the trainer's 2×2-vs-1×4 drill pins)."""
    return _knobbed_mesh(leased_local_devices(), "local")


def reprobe_devices() -> int:
    """Re-probe the local device set after an in-process restart
    (supervised `resilience.supervise` retry): drop every cached mesh
    and ask the runtime again, so a restart after losing chips comes
    back on whatever is still healthy instead of building meshes over
    devices that no longer answer. Returns the device count the next
    `default_mesh()` will see."""
    _MESH_CACHE.clear()
    try:
        # jax re-discovers backends lazily after this; on runtimes
        # without the API the stale backend keeps serving, which is
        # still correct when the device set did not actually change
        jax.clear_backends()
    except Exception as e:  # noqa: BLE001 — best-effort
        log.debug("reprobe_devices: clear_backends unavailable (%s)", e)
    n = len(leased_devices())
    log.info("reprobe_devices: %d local device(s) visible", n)
    return n


def shard_axis(mesh: Mesh, a: np.ndarray, axis: int = 0,
               pad_value=0):
    """Place one host array onto the mesh sharded along `axis`, padding
    that axis to a multiple of the data-axis size with `pad_value`
    (weight-0 / NaN-missing padding keeps downstream results exact —
    callers choose the value that is inert for their kernel).

    Accepts device arrays too (on-device data generation): padding
    then uses jnp so the array never round-trips device→host."""
    n_data = mesh.shape["data"]
    on_device = isinstance(a, jax.Array)
    if not on_device:
        a = np.asarray(a)
    pad = (-a.shape[axis]) % n_data
    if pad:
        import jax.numpy as jnp
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        xp = jnp if on_device else np
        a = xp.pad(a, widths, constant_values=pad_value)
    spec = [None] * a.ndim
    spec[axis] = "data"
    return jax.device_put(a, NamedSharding(mesh, P(*spec)))


def place_replicated(mesh: Mesh, tree):
    """device_put a whole pytree fully replicated over the mesh (model
    parameters / optimizer state — the reference's 'broadcast new
    weights' step is this sharding)."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def make_mesh(n_data: Optional[int] = None, n_model: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a 2-D ("data", "model") DCN×ICI mesh. Defaults to all
    devices on the data axis — pure data parallel, the reference's only
    strategy.

    Multi-host, devices are ordered host-major (process_index, id) so
    every model-axis group of `n_model` devices lives within ONE host:
    the model axis's per-step collectives (the Megatron all-reduce
    pair, WDL table gathers) ride ICI, and only the data axis's
    gradient mean crosses the slower DCN — the layout MULTICHIP_r05's
    data=4×model=2 run validated. `n_model` must then divide each
    host's local device count (a model group spanning two hosts would
    put the hottest collective on the coldest link)."""
    devices = list(devices if devices is not None else jax.devices())
    if n_data is None:
        n_data = len(devices) // n_model
    assert n_data * n_model <= len(devices), \
        f"mesh {n_data}x{n_model} needs {n_data * n_model} devices, " \
        f"have {len(devices)}"
    devices = devices[:n_data * n_model]
    n_hosts = len({getattr(d, "process_index", 0) for d in devices})
    if n_hosts > 1:
        devices = sorted(
            devices, key=lambda d: (d.process_index, d.id))
        local = len(devices) // n_hosts
        per_host: Dict[int, int] = {}
        for d in devices:
            per_host[d.process_index] = per_host.get(d.process_index, 0) + 1
        if any(c != local for c in per_host.values()) or \
                n_model > local or local % n_model:
            raise ValueError(
                f"mesh {n_data}x{n_model} over {n_hosts} hosts: the "
                f"model axis ({n_model}) must divide each host's local "
                f"device count ({sorted(per_host.values())}) so model "
                "collectives stay on ICI; shrink SHIFU_TPU_MESH_MODEL "
                "or rebalance hosts")
    arr = np.asarray(devices).reshape(n_data, n_model)
    return Mesh(arr, ("data", "model"))


def mesh_topology(mesh: Mesh) -> dict:
    """JSON-ready description of a mesh — the checkpoint sidecar's
    provenance record and the bench/CLI topology report."""
    return {"axes": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
            "devices": int(mesh.devices.size),
            "hosts": len({getattr(d, "process_index", 0)
                          for d in mesh.devices.flat})}


def resolve_spec(mesh: Mesh, entries, shape, label: str = "") -> P:
    """Re-resolve a RECORDED PartitionSpec (a list of axis names /
    name-tuples / None, as the checkpoint sidecar stores it) against
    the CURRENT mesh: an axis name survives only when this mesh has an
    axis of that name AND the leaf dimension divides its size; anything
    else replicates, loudly when it used to shard — save on
    data=4×model=2, restore on a 1-, 4- or 16-device mesh."""
    out = []
    for i, entry in enumerate(entries):
        if entry is None:
            out.append(None)
            continue
        names = [entry] if isinstance(entry, str) else list(entry)
        kept = [n for n in names if n in mesh.shape]
        size = int(np.prod([mesh.shape[n] for n in kept])) if kept else 1
        if kept and i < len(shape) and shape[i] % size == 0:
            out.append(kept[0] if len(kept) == 1 else tuple(kept))
        else:
            if names and size > 1:
                log.warning(
                    "reshard: %s dim %d (length %s) cannot shard over "
                    "mesh axes %s on this %s-device mesh — replicating "
                    "that dimension", label or "a leaf", i,
                    shape[i] if i < len(shape) else "?", names,
                    mesh.devices.size)
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def data_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard the leading (row) axis across 'data'; trailing axes
    replicated."""
    return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_rows(mesh: Mesh, *arrays):
    """Place row-major host arrays onto the mesh sharded by row.
    Pads the row count to a multiple of the data-axis size with zeros
    (padding rows carry zero weight downstream, so results are
    unchanged)."""
    out = [shard_axis(mesh, a, axis=0) for a in arrays]
    return out if len(out) > 1 else out[0]


def mlp_param_shardings(mesh: Mesh, n_layers: int,
                        rules: Optional[MeshRules] = None):
    """Tensor-parallel layout for an MLP parameter pytree
    [{'w','b'}...]: first hidden layer column-sharded over the axis the
    rules map 'hidden' to, last layer row-sharded, middle layers
    replicated (keeps exactly one all-reduce pair per forward, the
    standard Megatron split). Written in LOGICAL axes so the layout
    re-resolves on whatever mesh the process has."""
    rules = rules or default_rules()
    layouts = []
    for i in range(n_layers):
        if n_layers == 1:
            w, b = P(), P()
        elif i == 0:
            w, b = rules.spec("features", "hidden"), rules.spec("hidden")
        elif i == n_layers - 1:
            w, b = rules.spec("hidden", "out"), P()
        else:
            w, b = P(), P()
        layouts.append({"w": NamedSharding(mesh, w),
                       "b": NamedSharding(mesh, b)})
    return layouts


def wdl_param_shardings(mesh: Mesh, params) -> dict:
    """Dryrun certification layout: wdl_train_shardings with the deep
    MLP additionally Megatron-split (exercises tensor-parallel compile
    paths the product trainer deliberately skips)."""
    return wdl_train_shardings(mesh, params, megatron_deep=True)


def place(params, shardings):
    """device_put a pytree with a matching pytree of shardings."""
    return jax.tree.map(jax.device_put, params, shardings)


def _model_spec(mesh: Mesh, axis_len: int, spec: P,
                label: str = "") -> NamedSharding:
    """Shard the leading axis only when it divides the target mesh axis
    evenly (jax requires it); otherwise replicate that leaf — LOUDLY,
    since the user set the model axis precisely to avoid replicating
    it. The target axis comes from the spec itself (normally 'model',
    but SHIFU_TPU_MESH_RULES may have re-pointed the logical axis)."""
    ax = next((a for a in spec if isinstance(a, str)), None)
    n = mesh.shape.get(ax, 1) if ax else 1
    if n > 1 and axis_len % n == 0:
        return NamedSharding(mesh, spec)
    if n > 1:
        log.warning(
            "model axis: %s axis length %d is not divisible by the "
            "%d-device %r mesh axis — that leaf replicates per chip",
            label or "a parameter", axis_len, n, ax)
    return NamedSharding(mesh, P())


def wdl_train_shardings(mesh: Mesh, params, megatron_deep: bool = False
                        ) -> dict:
    """WDL layout (one UNSTACKED parameter set): the ragged embedding +
    wide tables — the memory hog for vocab-heavy configs, (ΣV, embed)
    floats that data-parallel would replicate per chip — shard over
    'model' on their ROW axis: every chip holds a slice of every
    column's ids, so a few huge columns among many small ones (Criteo's
    three largest hold 76% of the rows) still balance, which a split by
    column cannot. The deep MLP stays replicated in the product trainer
    (a few hundred hidden units buy nothing from tensor parallelism and
    Megatron splits would add two collectives per step);
    `megatron_deep=True` (the dryrun's compile certification) splits it
    anyway. Each table is held to its own length (`embed` is lane-packed,
    so the two differ): `wdl.pad_tables(params, model-axis size)` makes
    both divide, and a table that does not replicates with a warning."""
    rules = default_rules()
    out = {}
    if "embed" in params:
        out["embed"] = _model_spec(mesh, int(np.shape(params["embed"])[0]),
                                   rules.spec("vocab", "embed"),
                                   "WDL embed (packed table rows)")
        out["wide_cat"] = _model_spec(mesh,
                                      int(np.shape(params["wide_cat"])[0]),
                                      rules.spec("vocab"),
                                      "WDL wide_cat (table rows)")
    out["wide_dense"] = NamedSharding(mesh, P())
    out["wide_bias"] = NamedSharding(mesh, P())
    out["deep"] = mlp_param_shardings(mesh, len(params["deep"])) \
        if megatron_deep else [{"w": NamedSharding(mesh, P()),
                                "b": NamedSharding(mesh, P())}
                               for _ in params["deep"]]
    return out


def mtl_train_shardings(mesh: Mesh, params) -> dict:
    """Product-path MTL layout: per-task head rows shard over 'model'
    (tasks are independent — the expert-parallel analog); the shared
    trunk is replicated (every task reads it)."""
    rules = default_rules()
    n_tasks = int(np.shape(params["heads_w"])[0])
    return {"trunk": [{"w": NamedSharding(mesh, P()),
                       "b": NamedSharding(mesh, P())}
                      for _ in params["trunk"]],
            "heads_w": _model_spec(mesh, n_tasks,
                                   rules.spec("task", "hidden"),
                                   "MTL heads (n_tasks)"),
            "heads_b": _model_spec(mesh, n_tasks, rules.spec("task"),
                                   "MTL heads (n_tasks)")}


def place_stacked(tree, shardings):
    """device_put a bag-STACKED pytree (leading (B, ...) axis) using
    per-leaf UNSTACKED shardings — the bag axis is replicated, the
    remaining axes follow the given spec."""
    return jax.tree.map(
        lambda leaf, ns: jax.device_put(
            leaf, NamedSharding(ns.mesh, P(None, *ns.spec))),
        tree, shardings)
