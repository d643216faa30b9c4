"""The trainers' own spans and scopes (tier-1).

(a) a tiny `train_nn` and a tiny `build_gbt` inside a CPU `jax.profiler`
    trace: every `shifu:train.*` span is in the `.xplane.pb`, the phases
    lie inside `shifu:train.job` on one thread and cover it;
(b) the compiled programs carry the device scope names in their
    `op_name`s and every `pallas_call` its kernel name;
(c) with `SHIFU_TPU_TRACE` unset a span touches neither the ring buffer
    nor its lock and writes no file.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import gbdt
from shifu_tpu.models import nn as nn_mod
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.train import trainer

PHASES = ("prepare", "place", "program", "wait", "fetch")


def _toy_rows(n=600, c=4, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, c)).astype(np.float32)
    y = (x[:, 0] + 0.3 * x[:, 1] > 0).astype(np.float32)
    return x, y, np.ones(n, np.float32)


def _train_conf(epochs=3):
    tc = ModelTrainConf()
    tc.numTrainEpochs = epochs
    tc.params = {"NumHiddenLayers": 2, "NumHiddenNodes": [6, 3],
                 "ActivationFunc": ["tanh", "tanh"], "LearningRate": 0.05,
                 "Propagation": "ADAM"}
    return tc


def _nn_job():
    x, y, w = _toy_rows()
    trainer.train_nn(_train_conf(), x, y, w)


def _gbt_job():
    x, y, w = _toy_rows()
    bins = np.clip((x * 2 + 4).astype(np.int32), 0, 6)
    cfg = gbdt.TreeConfig(max_depth=2, n_bins=8, loss="log")
    gbdt.build_gbt(cfg, bins, y, w, n_trees=2)


def _profiled_spans(tmp_path, job):
    """[(name, start_ns, end_ns, stats)] of the `shifu:` events a traced
    `job()` left, by the host-plane line (thread) they lie on."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        job()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    by_line = {}
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                    dict(e.stats)) for e in line.events
                   if e.name.startswith(obs_trace.ANNOTATION_PREFIX)]
            if evs:
                by_line[(plane.name, line.name)] = evs
    return by_line


@pytest.mark.parametrize("job,family,steps", [(_nn_job, "nn", 3),
                                              (_gbt_job, "gbt", 2)],
                         ids=["train_nn", "build_gbt"])
def test_profiler_trace_holds_the_job_and_its_phases(tmp_path, job, family,
                                                     steps):
    by_line = _profiled_spans(tmp_path, job)
    lines = [evs for evs in by_line.values()
             if any(n == "shifu:train.job" for n, *_ in evs)]
    assert len(lines) == 1, "one job, on one thread"
    evs = lines[0]
    jobs = [e for e in evs if e[0] == "shifu:train.job"]
    assert len(jobs) == 1
    _, j0, j1, stats = jobs[0]
    assert stats["family"] == family and int(stats["steps"]) == steps
    assert int(stats["rows"]) == 600 and int(stats["bags"]) == 1
    phases = [e for e in evs if e[0].startswith("shifu:train.")
              and e[0] != "shifu:train.job"]
    assert {n for n, *_ in phases} == {"shifu:train." + p for p in PHASES}
    # every phase on the job's own thread, inside it, and side by side
    assert all(j0 <= s and e <= j1 for _, s, e, _ in phases)
    ordered = sorted(phases, key=lambda e: e[1])
    assert all(a[2] <= b[1] for a, b in zip(ordered, ordered[1:]))
    covered = sum(e - s for _, s, e, _ in phases)
    assert covered >= 0.9 * (j1 - j0), (covered, j1 - j0)
    program = [e for e in phases if e[0] == "shifu:train.program"]
    assert sum(int(e[3]["steps"]) for e in program) == steps


@pytest.mark.parametrize("on_tpu", [False, True], ids=["cpu", "tpu"])
def test_train_job_says_whether_its_program_holds_the_mlp_kernel(
        tmp_path, monkeypatch, on_tpu):
    """`shifu:train.job` of an NN job carries `mlp_kernel`: 1 where the
    epoch program is the fused loss-and-gradient kernel (a narrow net,
    full batch, one TPU device), else 0."""
    from shifu_tpu.ops import pallas_mlp
    monkeypatch.setenv("SHIFU_TPU_MESH_DEVICES", "1")
    monkeypatch.setattr(pallas_mlp, "on_chip", lambda: on_tpu)
    monkeypatch.setattr(pallas_mlp, "CHUNKS", 8)
    monkeypatch.setattr(pallas_mlp, "ROW_TILE", 8 * pallas_mlp.CHUNK)
    by_line = _profiled_spans(tmp_path, _nn_job)
    jobs = [e for evs in by_line.values() for e in evs
            if e[0] == "shifu:train.job"]
    assert len(jobs) == 1
    assert int(jobs[0][3]["mlp_kernel"]) == int(on_tpu)


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _scopes_of(op_names):
    return {s for name in op_names for s in obs_trace.device_scopes(name)}


def test_nn_program_carries_its_scopes():
    spec = nn_mod.MLPSpec(input_dim=4, hidden_dims=(6, 3),
                          activations=("tanh", "tanh"))
    optimizer = trainer.optimizer_from_params({"Propagation": "ADAM",
                                               "LearningRate": 0.05})
    keys = jax.random.split(jax.random.PRNGKey(0), 1)
    stacked = jax.vmap(lambda k: nn_mod.init_params(spec, k))(keys)
    carry = trainer.init_train_carry(optimizer, stacked, keys)
    x, y, w = map(jnp.asarray, _toy_rows(64))
    mask = jax.tree.map(lambda l: jnp.ones_like(l[0]), stacked)
    text = trainer.train_bags_carry.lower(
        lambda p, inp, w_, k: nn_mod.loss_fn(spec, p, inp[0], inp[1], w_),
        lambda p, inp, w_: nn_mod.mse(spec, p, inp[0], inp[1], w_),
        optimizer, 2, 0, 0.0, carry, (x, y), w[None, :], (x, y), w,
        mask).compile().as_text()
    names = _op_names(text)
    scopes = _scopes_of(names)
    assert scopes == {"update", "select", "layer0", "layer1", "layer2",
                      "forward_loss", "validate"}
    # the layers nest inside the step's phases, backward included
    assert any("forward_loss" in n and "layer1" in n and "transpose(" in n
               for n in names)
    assert any("/validate/" in n and "/layer2/" in n for n in names)


def test_gbt_program_carries_its_scopes():
    cfg = gbdt.TreeConfig(max_depth=3, n_bins=8, loss="log")
    r, c = 256, 4
    text = gbdt._gbt_rounds.lower(
        cfg, jnp.zeros((c, r), jnp.int32), jnp.zeros(r), jnp.ones(r),
        jnp.zeros(r), jnp.ones(c), 2, mesh=None,
        subtract=True).compile().as_text()
    assert _scopes_of(_op_names(text)) == {"gradients", "hist", "split",
                                           "route", "leaf"}


def test_wdl_program_carries_its_scopes():
    """The mini-batch program of a WDL job as jax hands it to the
    compiler: lookup and its gradient's scatter-add under `embed` and
    `wide`, the MLP under `deep`, and the tables' optimizer pass AND the
    add that applies it under `update/table_update`. (What the TPU
    compiler builds anew while it rewrites a scatter, the id sort and the
    sorted scatter itself, carries no `op_name` at all: no scope of the
    program's reaches it.)"""
    from shifu_tpu.models import wdl
    from shifu_tpu.processor import train_wdl
    vocab, n_dense, batch, n_batches = (11, 300, 5), 3, 32, 4
    spec = wdl.WDLSpec.from_train_params(
        {"NumHiddenNodes": [8, 4], "ActivationFunc": ["relu", "relu"],
         "EmbedSize": 4}, n_dense, len(vocab), vocab)
    optimizer = train_wdl._tables_scoped(trainer.optimizer_from_params(
        {"Propagation": "ADAGRAD", "LearningRate": 0.01}))
    loss, metric = trainer.objectives(wdl, spec)
    keys = jax.random.split(jax.random.PRNGKey(0), 1)
    stacked = jax.vmap(lambda k: wdl.pad_tables(
        wdl.init_params(spec, k), 1))(keys)
    carry = trainer.init_train_carry(optimizer, stacked, keys)
    rows = (jnp.zeros((n_batches, batch, n_dense)),
            jnp.zeros((n_batches, batch, len(vocab)), jnp.int32),
            jnp.zeros((n_batches, batch)))
    val = (jnp.zeros((batch, n_dense)),
           jnp.zeros((batch, len(vocab)), jnp.int32), jnp.zeros(batch))
    lowered = trainer.train_bags_carry.lower(
        loss, metric, optimizer, 2, 0, 0.0, carry, rows,
        jnp.ones((1, n_batches, batch)), val, jnp.ones(batch), None,
        n_batches=n_batches)
    names = _op_names(lowered.compile().as_text())
    scopes = _scopes_of(names)
    assert {"forward_loss", "update", "validate", "select", "embed", "wide",
            "deep", "table_update"} <= scopes
    assert scopes <= {"forward_loss", "update", "validate", "select",
                      "embed", "wide", "deep", "table_update", "layer0",
                      "layer1", "layer2"}
    # both scatter-adds are the program's, under the scope of their lookup
    for table in ("embed", "wide"):
        assert any(n.endswith(f"forward_loss/transpose(jvp({table}))/"
                              "scatter-add") for n in names), table
    # the optimizer's pass over the tables and the add that applies it
    under = [n for n in names if "/update/table_update/" in n]
    assert any(n.endswith("/add") for n in under)
    assert any(n.endswith("/rsqrt") for n in under)
    # and the MLP's update stays outside it: its adds are `update`'s own
    adds = [n for n in names if n.endswith("/update/add")]
    assert adds, "the deep layers' update is added under `update` alone"


@pytest.mark.parametrize("op_name,scopes", [
    ("jit(_gbt_rounds)/while/body/closed_call/jit(build_tree)/route/"
     "jit(take_along_axis)/gather", ("route",)),
    ("jit(_gbt_bagged_rounds)/while/body/vmap(route)/jit(_where)/select_n",
     ("route",)),
    ("jit(train_bags_carry)/vmap()/while/body/forward_loss/"
     "transpose(jvp(layer1))/dot_general", ("forward_loss", "layer1")),
    ("jit(f)/closed_call/reshape;split/squeeze", ("split",)),
    ("jit(f)/hist/jit(_level_histograms_pallas)/shifu_level_histograms/"
     "pallas_call", ("hist",)),
    ("jit(_gbt_rounds)/while/body/dynamic_update_slice", ()),
    ("jit(split)/split", ()),
])
def test_device_scopes_reads_an_op_name(op_name, scopes):
    assert obs_trace.device_scopes(op_name) == scopes


def test_forest_program_carries_its_scopes():
    cfg = gbdt.TreeConfig(max_depth=2, n_bins=8, loss="log")
    r, c, t = 256, 4, 2
    text = gbdt._gbt_bagged_rounds.lower(
        cfg, jnp.zeros((c, r), jnp.int32), jnp.zeros(r), jnp.ones((t, r)),
        jnp.zeros((t, r)), jnp.ones((t, c)), 2, mesh=None,
        subtract=True).compile().as_text()
    assert _scopes_of(_op_names(text)) == {"gradients", "hist", "split",
                                           "route", "leaf"}


def _pallas_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    _pallas_eqns(getattr(inner, "jaxpr", inner), out)
    return out


def _hist(bt, sl, g, h):
    from shifu_tpu.ops import pallas_hist
    return pallas_hist.level_histograms_pallas(bt, sl, g, h, 2, 8,
                                               interpret=True)


def _hist_fused(v, cuts, sl, g, h):
    from shifu_tpu.ops import pallas_hist
    return pallas_hist.level_histograms_fused(v, cuts, sl, g, h, 2, 8,
                                              interpret=True)


def _split(g, h, m):
    from shifu_tpu.ops import pallas_split
    return pallas_split.best_splits_pallas(g, h, m, 1.0, 1.0, interpret=True)


def _trees(nodes, v, cuts):
    from shifu_tpu.ops import pallas_trees
    return pallas_trees.predict_ensemble(
        nodes, v, cuts, n_trees=2, kind="gbt", loss="log", max_depth=2,
        n_bins=8, interpret=True)


def _score(x, mean, std, w, b):
    from shifu_tpu.ops import pallas_score
    return pallas_score.fused_first_layer(x, mean, std, 4.0, w, b,
                                          mode="pallas", interpret=True)


def _mlp(xT, y, w):
    from shifu_tpu.ops import pallas_mlp
    spec = nn_mod.MLPSpec(input_dim=4, hidden_dims=(6,),
                          activations=("tanh",))
    params = nn_mod.init_params(spec, jax.random.PRNGKey(0))
    return jax.value_and_grad(lambda p: pallas_mlp.loss(
        spec, p, xT, y, w, interpret=True))(params)


F32, I32 = jnp.float32, jnp.int32
KERNELS = [
    ("shifu_level_histograms", _hist,
     [((4, 256), I32), ((256,), I32), ((256,), F32), ((256,), F32)]),
    ("shifu_level_histograms_fused", _hist_fused,
     [((4, 256), F32), ((4, 7), F32), ((256,), I32), ((256,), F32),
      ((256,), F32)]),
    ("shifu_best_splits", _split,
     [((2, 4, 8), F32), ((2, 4, 8), F32), ((2, 4), F32)]),
    ("shifu_predict_ensemble", _trees,
     [((8, 16), F32), ((4, 128), F32), ((4, 7), F32)]),
    ("shifu_first_layer", _score,
     [((16, 4), F32), ((4,), F32), ((4,), F32), ((4, 8), F32), ((8,), F32)]),
    # one row tile of `pallas_mlp.lay_rows`: (F8, 16384), 64 chunks of 256
    ("shifu_mlp_loss_grad", _mlp,
     [((8, 16384), F32), ((64, 256), F32), ((64, 256), F32)]),
]


@pytest.mark.parametrize("name,fn,shapes", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_every_pallas_call_carries_its_name(name, fn, shapes):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    eqns = _pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert [e.params["name"] for e in eqns] == [name]


def test_histogram_kernel_names_keep_what_the_benchmark_reads():
    """`benchmark/trace_reduce.is_hist_kernel` tells the histogram kernels
    by `_level_histograms` in the device event's name, which on the chip
    is the kernel's name (tests/test_chip_compile.py holds the compiled
    instruction to it)."""
    hist = [k[0] for k in KERNELS if "_level_histograms" in k[0]]
    assert hist == ["shifu_level_histograms", "shifu_level_histograms_fused"]


def test_disabled_span_touches_no_ring_no_lock_and_no_file(tmp_path,
                                                           monkeypatch):
    monkeypatch.delenv("SHIFU_TPU_TRACE", raising=False)
    monkeypatch.delenv("SHIFU_TPU_TRACE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)

    def refuse(*a, **k):
        raise AssertionError("the ring buffer was touched with tracing off")

    for attr in ("__init__", "new_id", "opened", "closed"):
        monkeypatch.setattr(obs_trace.Tracer, attr, refuse)
    monkeypatch.setattr(obs_trace, "make_lock", refuse)
    with obs_trace.trace_run(str(tmp_path), "train") as run:
        assert run is None and not obs_trace.active()
        with obs_trace.span("train.job", family="nn", rows=1, steps=1,
                            bags=1):
            with obs_trace.span("train.program", steps=1):
                pass
        with pytest.raises(ValueError):
            with obs_trace.span("train.wait"):
                raise ValueError("an error passes through the span")
        assert obs_trace.open_spans() == []
    assert obs_trace._RUN is None
    assert os.listdir(tmp_path) == []


def test_disabled_phase_span_is_the_annotation_and_the_job_adds_a_record(
        monkeypatch):
    """With every knob unset a phase span is a `TraceAnnotation` and
    nothing else; `train.job` alone is wrapped, and what the wrapper adds
    is one record when the span closes."""
    from jax.profiler import TraceAnnotation
    monkeypatch.delenv("SHIFU_TPU_TRACE", raising=False)
    assert obs_trace._RUN is None
    for stage in obs_trace.SPAN_FAMILIES["train"]:
        if stage not in ("job", "build"):
            assert type(obs_trace.span(f"train.{stage}")) is TraceAnnotation
    assert type(obs_trace.span("host.sync")) is TraceAnnotation
    job = obs_trace.span("train.job", family="nn", rows=3, steps=1, bags=1)
    assert type(job._inner) is TraceAnnotation
    kept = len(obs_trace.job_records())
    with job:
        with obs_trace.span("train.program", steps=1):
            pass
        assert len(obs_trace.job_records()) == kept
    newest = obs_trace.job_records()[-1]
    assert newest["attrs"] == {"family": "nn", "rows": 3, "steps": 1,
                               "bags": 1}
    assert newest["seconds"] >= 0 and newest["builds"]["traced"] == 0


def test_enabled_span_lands_in_ring_and_profiler_alike(tmp_path,
                                                       monkeypatch):
    """One enter/exit, two sinks: with `SHIFU_TPU_TRACE=1` the span a
    profiler session sees is the span the ring buffer exports."""
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    monkeypatch.delenv("SHIFU_TPU_TRACE_DIR", raising=False)
    ring = {}

    def job():
        with obs_trace.trace_run(str(tmp_path / "set"), "train") as run:
            with obs_trace.span("train.job", family="nn", rows=7, steps=1,
                                bags=1):
                with obs_trace.span("train.program", steps=1):
                    pass
            ring["spans"] = run.tracer.spans()

    by_line = _profiled_spans(tmp_path / "prof", job)
    seen = [n for evs in by_line.values() for n, *_ in evs]
    assert sorted(seen) == ["shifu:run.step", "shifu:train.job",
                            "shifu:train.program"]
    names = {s["name"]: s for s in ring["spans"]}
    assert set(names) == {"train.job", "train.program"}   # run.step is open
    assert names["train.program"]["parent"] == names["train.job"]["id"]
    assert names["train.job"]["args"]["rows"] == 7
    monkeypatch.delenv("SHIFU_TPU_TRACE_DIR", raising=False)


def test_trace_scopes_accounts_self_time_by_scope_and_kernel():
    """tools/trace_scopes.py's sums, on hand-made device events: a `while`
    gives its body's time away, a kernel counts under its scope and under
    its own name, and what no scope names is reported as such."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "tools", "trace_scopes.py")
    spec = importlib.util.spec_from_file_location("trace_scopes", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ev = tool.trace_reduce.Event
    kernel = "%shifu_level_histograms.3 = custom-call(), " \
        'custom_call_target="tpu_custom_call"'
    events = [
        (ev("while.1", 0.0, 10.0), "jit(_gbt_rounds)/while"),
        (ev("fusion.2", 0.0, 6.0),
         "jit(_gbt_rounds)/while/body/route/jit(take_along_axis)/gather"),
        (ev("shifu_level_histograms.3", 6.0, 8.0, detail=kernel),
         "jit(_gbt_rounds)/while/body/hist/jit(_level_histograms_pallas)/"
         "shifu_level_histograms/pallas_call"),
        (ev("copy.4", 8.0, 9.0), ""),
    ]
    tool.trace_reduce.set_self_times([e for e, _ in events])
    acc = tool.account(events)
    assert acc["busy_s"] == pytest.approx(10.0)
    assert acc["scopes"] == {"route": 6.0, "hist": 2.0,
                             tool.UNSCOPED: 2.0}     # while's 1 s + copy
    assert acc["innermost"] == acc["scopes"]
    assert acc["kernels"] == {"shifu_level_histograms": 2.0}
    assert acc["unscoped_share"] == pytest.approx(0.2)
    assert acc["ops"][0][:2] == ["fusion.2", "route"]
