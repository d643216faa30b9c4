"""The job records (tier-1): every `train.job` span leaves one, and jax's
build events are booked to the job that caused them.

A first job's record names what was traced, lowered, read back or
compiled for it, by function; a repeat job of equal shapes holds no build;
a build on another thread goes to `outside`; the process's first record is
kept for good beside the newest 64; the listeners are registered once; a
second process on a warm persistent cache reads `compile_s` 0 and a
`load_s`, one on an empty cache the reverse; and under `SHIFU_TPU_TRACE=1`
a build stage is a `train.build` span in the ring, under the span open on
the building thread.
"""

import copy
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu import profiling
from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import gbdt
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.processor import train_wdl
from shifu_tpu.train import optimizers, trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STAGE_SECONDS = ("trace_s", "lower_s", "load_s", "compile_s")
MEMOS = (optimizers.make_optimizer, trainer.nn_objectives,
         trainer.objectives, train_wdl._tables_scoped)
# shapes no other test of this process trains on
N, FEATS, CATS, VOCAB = 611, 5, 2, 13


def _rows():
    rng = np.random.default_rng(35)
    x = rng.normal(size=(N, FEATS)).astype(np.float32)
    idx = rng.integers(0, VOCAB, (N, CATS)).astype(np.int32)
    y = (x[:, 0] + 0.3 * rng.normal(size=N) > 0).astype(np.float32)
    return x, idx, y, np.ones(N, np.float32)


def _conf(**params):
    return ModelTrainConf.from_dict(copy.deepcopy(
        {"numTrainEpochs": 3, "baggingNum": 1, "validSetRate": 0.25,
         "earlyStoppingRounds": 0,
         "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [7],
                    "ActivationFunc": ["tanh"], "Propagation": "ADAM",
                    "LearningRate": 0.05, **params}}))


def _nn_job():
    x, _, y, w = _rows()
    trainer.train_nn(_conf(), x, y, w, seed=5)


def _wdl_job():
    x, idx, y, w = _rows()
    train_wdl.train_wdl(_conf(EmbedSize=4, MiniBatchRows=64), x, idx, y, w,
                        (VOCAB,) * CATS, seed=5)


def _gbt_job():
    x, _, y, w = _rows()
    bins = np.clip((x * 2 + 4).astype(np.int32), 0, 6)
    gbdt.build_gbt(gbdt.TreeConfig(max_depth=2, n_bins=8, loss="log"),
                   bins, y, w, n_trees=2)


def _forget():
    """What a fresh process starts with: no program, no memoised static."""
    jax.clear_caches()
    for memo in MEMOS:
        memo.cache_clear()


def _newest():
    return obs_trace.job_records()[-1]


def _parts(builds):
    return sum(builds[k] for k in STAGE_SECONDS)


@pytest.fixture
def hand_made_events():
    """Build events made up by a test carry made-up times: this thread's
    list of closed events starts empty for them and is left empty, so
    that they and jax's own never take each other for their children."""
    profiling._build_tls.__dict__.clear()
    yield time.time()
    profiling._build_tls.__dict__.clear()


def test_first_job_names_what_was_built_and_a_repeat_builds_nothing():
    _forget()
    _nn_job()
    first = _newest()
    assert tuple(first) == profiling.JOB_FIELDS
    assert first["attrs"]["family"] == "nn" and first["attrs"]["rows"] == N
    builds = first["builds"]
    assert tuple(builds) == profiling.BUILD_FIELDS
    assert builds["traced"] > 0
    assert builds["loaded"] + builds["compiled"] > 0
    assert builds["trace_s"] > 0 and builds["lower_s"] > 0
    # self times: the four parts never add up to more than the job
    assert 0 < _parts(builds) <= first["seconds"]
    functions = {f["fun"]: f for f in builds["functions"]}
    assert len(functions) <= 8
    assert "train_bags_carry" in functions
    carry = functions["train_bags_carry"]
    assert tuple(carry) == ("fun",) + STAGE_SECONDS
    # tracing names the function, lowering and the compiler `jit(f)`:
    # one row holds all of them
    assert carry["trace_s"] > 0 and carry["lower_s"] > 0
    assert carry["load_s"] + carry["compile_s"] > 0
    if first["start_s"] is not None:        # a host with /proc
        assert 0 < first["start_s"] < 24 * 3600

    _nn_job()
    again = _newest()
    assert again is not first
    assert again["attrs"] == first["attrs"]
    # no program: nothing lowered, read back or compiled. (jax still
    # reports the re-trace of some eager primitives, microseconds each,
    # whose programs it then finds in memory.)
    quiet = again["builds"]
    assert (quiet["traced"], quiet["loaded"], quiet["compiled"]) == (0, 0, 0)
    assert quiet["lower_s"] == quiet["load_s"] == quiet["compile_s"] == 0
    assert quiet["trace_s"] < 0.05 * builds["trace_s"]
    assert all(f["lower_s"] == 0 for f in quiet["functions"])
    if first["start_s"] is not None:
        assert again["start_s"] >= first["start_s"] + first["seconds"] - 1e-3


@pytest.mark.parametrize("job,family", [(_gbt_job, "gbt"),
                                        (_wdl_job, "wdl")],
                         ids=["build_gbt", "train_wdl"])
def test_every_trainer_leaves_a_record(job, family):
    _forget()
    seen = len(obs_trace.job_records())
    before = _newest() if seen else None
    job()
    rec = _newest()
    assert rec is not before
    assert rec["attrs"]["family"] == family and rec["attrs"]["rows"] == N
    assert rec["seconds"] > 0
    assert rec["builds"]["traced"] > 0
    assert 0 < _parts(rec["builds"]) <= rec["seconds"]


def test_a_build_on_another_thread_is_booked_outside_not_to_the_job():
    salt = int.from_bytes(os.urandom(2), "big") + 3

    def build_fresh():
        # a program nothing in this process or its cache directory has
        def never_seen_before(v):
            return v * salt + 0.25
        jax.jit(never_seen_before)(jnp.ones(5)).block_until_ready()

    outside_was = obs_trace.outside_builds()["traced"]
    with obs_trace.span("train.job", family="nn", rows=1, steps=1, bags=1):
        other = threading.Thread(target=build_fresh)
        other.start()
        other.join()
    rec = _newest()
    assert rec["builds"]["traced"] == 0 and not rec["builds"]["functions"]
    outside = obs_trace.outside_builds()
    assert outside["traced"] > outside_was
    # and on the job's own thread the same build is the job's
    salt += 1
    with obs_trace.span("train.job", family="nn", rows=1, steps=1, bags=1):
        build_fresh()
    own = _newest()["builds"]
    assert own["traced"] >= 1
    assert "never_seen_before" in {f["fun"] for f in own["functions"]}


def test_first_record_is_kept_for_good_beside_the_newest_64():
    with obs_trace.span("train.job", family="nn", rows=0, steps=0, bags=1):
        pass
    first = obs_trace.job_records()[0]
    for k in range(100):
        with obs_trace.span("train.job", family="nn", rows=k + 1, steps=1,
                            bags=1):
            pass
    records = obs_trace.job_records()
    assert records[0] is first
    assert len(obs_trace._jobs) == 64 and len(records) == 65
    assert [r["attrs"]["rows"] for r in records[1:]] == list(range(37, 101))


def test_listeners_are_registered_once_whoever_asks(monkeypatch):
    from jax._src import monitoring
    with obs_trace.span("train.job", family="nn", rows=1, steps=1, bags=1):
        pass
    counts = (len(monitoring.get_event_listeners()),
              len(monitoring.get_event_duration_listeners()),
              len(monitoring.get_event_time_span_listeners()))
    assert profiling._on_build_span in \
        monitoring.get_event_time_span_listeners()
    assert profiling._on_cache_read in \
        monitoring.get_event_duration_listeners()
    monkeypatch.setenv("SHIFU_TPU_COMPILE_CACHE_DIR", "off")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    for _ in range(3):
        assert profiling.enable_compile_cache() is None
        profiling.register_build_listeners()
        with obs_trace.span("train.job", family="nn", rows=1, steps=1,
                            bags=1):
            pass
    assert counts == (len(monitoring.get_event_listeners()),
                      len(monitoring.get_event_duration_listeners()),
                      len(monitoring.get_event_time_span_listeners()))


def test_nested_build_events_are_booked_with_their_self_time(
        hand_made_events):
    """jax's events nest and close inside out: a helper traced inside
    another function's trace raises its own event first. Booked whole,
    a job's parts would add up to more than the job."""
    t = hand_made_events
    with obs_trace.span("train.job", family="nn", rows=1, steps=1, bags=1):
        profiling._on_build_span(TRACE_EVENT, t + 1.0, t + 2.0,
                                 fun_name="helper")
        profiling._on_build_span(TRACE_EVENT, t + 2.5, t + 3.0,
                                 fun_name="helper")
        profiling._on_build_span(TRACE_EVENT, t + 0.5, t + 4.0,
                                 fun_name="program")
        profiling._on_build_span(TRACE_EVENT, t + 4.5, t + 4.75,
                                 fun_name="threefry")
        profiling._on_build_span(LOWER_EVENT, t + 4.25, t + 5.25,
                                 fun_name="jit(program)")
        profiling._on_build_span(COMPILE_EVENT, t + 5.5, t + 6.0,
                                 fun_name="jit(program)")
    builds = _newest()["builds"]
    assert builds["trace_s"] == pytest.approx(3.75)
    assert builds["lower_s"] == pytest.approx(0.75)
    assert builds["compile_s"] == pytest.approx(0.5)
    assert builds["load_s"] == 0
    # one program: the helpers and what the lowering traced are part of it
    assert (builds["traced"], builds["loaded"], builds["compiled"]) == \
        (1, 0, 1)
    functions = {f["fun"]: f for f in builds["functions"]}
    assert functions["program"]["trace_s"] == pytest.approx(2.0)
    assert functions["program"]["lower_s"] == pytest.approx(0.75)
    assert functions["helper"]["trace_s"] == pytest.approx(1.5)
    assert functions["threefry"]["trace_s"] == pytest.approx(0.25)


def test_a_build_request_the_cache_answered_is_load_and_no_compile(
        hand_made_events):
    """jax times the cache's lookup and the compiler as one event; the
    cache's own event, raised inside it, says which of the two it was."""
    t = hand_made_events
    with obs_trace.span("train.job", family="nn", rows=1, steps=1, bags=1):
        profiling._on_cache_read(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        profiling._on_build_span(COMPILE_EVENT, t, t + 0.25,
                                 fun_name="jit(read)")
        profiling._on_build_span(COMPILE_EVENT, t + 1, t + 3,
                                 fun_name="jit(made)")
    builds = _newest()["builds"]
    assert builds["load_s"] == pytest.approx(0.25)
    assert builds["compile_s"] == pytest.approx(2.0)
    assert (builds["loaded"], builds["compiled"]) == (1, 1)


CHILD = textwrap.dedent("""
    import json, sys
    import numpy as np
    from shifu_tpu import profiling
    from shifu_tpu.config.model_config import ModelTrainConf
    from shifu_tpu.data import pipeline
    from shifu_tpu.obs import trace as obs_trace
    from shifu_tpu.train import trainer
    profiling.enable_compile_cache()
    rng = np.random.default_rng(3)
    x = rng.normal(size=(300, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    conf = ModelTrainConf.from_dict(
        {"numTrainEpochs": 2, "baggingNum": 1, "validSetRate": 0.25,
         "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [5],
                    "ActivationFunc": ["tanh"], "Propagation": "ADAM",
                    "LearningRate": 0.05}})
    trainer.train_nn(conf, x, y, np.ones(300, np.float32), seed=1)
    print(json.dumps({"job": obs_trace.job_records()[0],
                      "stages": pipeline.peek_stage_timers()}))
""")


def test_cold_cache_compiles_and_a_warm_one_reads_back(tmp_path):
    """Two processes, one placed cache directory: the first finds it
    empty and every program goes to the compiler; the second reads every
    executable back, so `compile_s` is 0 and `load_s` is not, in the job
    record and in the stage timers alike."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
                "SHIFU_TPU_COMPILE_CACHE_MIN_S": "0"})
    env.pop("SHIFU_TPU_TRACE", None)
    runs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", CHILD],
                           capture_output=True, text=True, timeout=600,
                           env=env, cwd=str(tmp_path))
        assert r.returncode == 0, r.stderr[-3000:]
        runs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    cold, warm = (r["job"]["builds"] for r in runs)
    # (a cold process reads back what it wrote itself where two of its
    # small eager programs are the same module)
    assert cold["compile_s"] > cold["load_s"]
    assert cold["compiled"] > cold["loaded"]
    assert warm["compile_s"] == 0 and warm["compiled"] == 0
    assert warm["load_s"] > 0
    assert warm["loaded"] == cold["compiled"] + cold["loaded"]
    assert warm["traced"] == cold["traced"] > 0
    for builds, run in zip((cold, warm), runs):
        assert run["job"]["start_s"] is None or run["job"]["start_s"] > 0
        assert _parts(builds) <= run["job"]["seconds"]
    cold_stages, warm_stages = (r["stages"] for r in runs)
    assert cold_stages["compile_s"] > 0
    assert cold_stages["compile_s"] > \
        cold_stages.get("compile_cache_read_s", 0)
    assert "compile_s" not in warm_stages
    assert warm_stages["compile_cache_read_s"] > 0
    assert warm_stages["compile_cache_hits"] > 0
    assert warm_stages["trace_s"] > 0 and warm_stages["lower_s"] > 0


def test_build_stage_lands_in_the_ring_under_its_program_span(
        tmp_path, monkeypatch, hand_made_events):
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    monkeypatch.delenv("SHIFU_TPU_TRACE_DIR", raising=False)
    t = hand_made_events
    with obs_trace.trace_run(str(tmp_path / "set"), "train") as run:
        with obs_trace.span("train.job", family="nn", rows=7, steps=1,
                            bags=1):
            with obs_trace.span("train.program", steps=1):
                profiling._on_build_span(TRACE_EVENT, t + 0.25, t + 0.5,
                                         fun_name="helper")
                profiling._on_build_span(TRACE_EVENT, t, t + 1.0,
                                         fun_name="program")
                profiling._on_build_span(LOWER_EVENT, t + 1.0, t + 1.5,
                                         fun_name="jit(program)")
        spans = run.tracer.spans()
    monkeypatch.delenv("SHIFU_TPU_TRACE_DIR", raising=False)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    program = by_name["train.program"][0]
    assert program["parent"] == by_name["train.job"][0]["id"]
    builds = {(s["args"]["stage"], s["args"]["fun"]): s
              for s in by_name["train.build"]}
    assert set(builds) == {("trace", "helper"), ("trace", "program"),
                           ("lower", "program")}
    outer = builds["trace", "program"]
    assert outer["parent"] == program["id"]
    assert builds["lower", "program"]["parent"] == program["id"]
    # the helper closed first and was recorded first: the event that
    # encloses it took it over
    assert builds["trace", "helper"]["parent"] == outer["id"]
    assert outer["dur"] == pytest.approx(1.0)
    assert outer["ts"] == pytest.approx(t, abs=1e-3)
    # and the job record holds the same three events
    assert _newest()["builds"]["trace_s"] == pytest.approx(1.0)


def test_without_the_knob_a_build_touches_no_ring(monkeypatch,
                                                  hand_made_events):
    monkeypatch.delenv("SHIFU_TPU_TRACE", raising=False)

    def refuse(*a, **k):
        raise AssertionError("the ring buffer was touched with tracing off")

    monkeypatch.setattr(obs_trace, "record_span", refuse)
    t = hand_made_events
    with obs_trace.span("train.job", family="nn", rows=1, steps=1, bags=1):
        profiling._on_build_span(LOWER_EVENT, t, t + 1.0, fun_name="jit(f)")
    assert _newest()["builds"]["traced"] == 1


def test_span_registry_holds_train_build_and_every_stage_is_emitted():
    from shifu_tpu.analysis import engine
    assert obs_trace.span_registered("train.build")
    report = engine.run([os.path.join(REPO, "shifu_tpu")],
                        rules=["unregistered-span"])
    assert not report.findings, \
        "\n".join(f.format() for f in report.findings)
