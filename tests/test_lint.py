"""Static-analysis gate + rule unit tests (tier-1).

Two layers:

1. ``test_package_is_clean`` — the acceptance check from ISSUE 4
   (extended by ISSUE 19): the analyzer over the whole package (plus
   tools/ and tests/synth.py) reports ZERO findings across all
   sixteen rules — including the whole-program
   concurrency/atomicity four — within a documented inline-suppression
   budget where every entry carries a ``-- reason``.
2. Per-rule fixtures — positive (a known violation is flagged),
   negative (the clean twin is not), suppressed (the violation with an
   inline ``# lint: disable=`` is silenced but counted) — plus unit
   tests for the runtime lock-order detector (including the deliberate
   A->B / B->A inversion that MUST raise), the whole-program
   call-graph model, and a cross-module thread-mutation fixture a
   per-file engine provably cannot catch.
"""

import ast
import os
import subprocess
import sys
import textwrap
import threading

import pytest

from shifu_tpu.analysis import engine, lockcheck
from shifu_tpu.analysis.lockcheck import CheckedLock, LockOrderError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def lint_source(tmp_path, source, name="fixture.py", rules=None):
    """Run the engine on one fixture snippet; return the Report."""
    path = tmp_path / name
    path.write_text(textwrap.dedent(source), encoding="utf-8")
    return engine.run([str(path)], rules=rules)


def rule_names(report):
    return [f.rule for f in report.findings]


# ---------------------------------------------------------------------------
# the acceptance gate
# ---------------------------------------------------------------------------

def test_package_is_clean():
    report = engine.run([os.path.join(REPO, "shifu_tpu"),
                         os.path.join(REPO, "tools"),
                         os.path.join(REPO, "tests", "synth.py")])
    msgs = "\n".join(f.format() for f in report.findings)
    assert not report.findings, f"lint findings:\n{msgs}"
    assert report.files > 60, "walker found suspiciously few files"
    # Suppression budget (every entry carries a `-- reason` inline):
    #   5 non-atomic-write        2 live-tailed subprocess/node logs,
    #                             the drilled ckpt tmp+rename publish
    #                             seam, 2 dot-prefixed eval scratch
    #                             sidecars
    #   3 thread-shared-mutation  resilience._rules_cache idempotent
    #                             memo (deliberately lock-free), 2
    #                             consumer-thread-confined batcher
    #                             carry-overs
    #   2 jit-in-loop             aot warm/compile loops (cached jits)
    assert len(report.suppressed) <= 10, (
        "suppression budget exceeded — justify or fix: "
        + "\n".join(f.format() for f in report.suppressed))


def test_module_entrypoint_exit_codes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    bad = tmp_path / "bad.py"
    bad.write_text("import os\n"
                   "x = os.environ.get('SHIFU_TPU_NOT_A_KNOB')\n",
                   encoding="utf-8")
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.analysis", str(bad)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "undeclared-knob" in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.analysis", "--knobs-md"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 0
    assert "SHIFU_TPU_LOCKCHECK" in r.stdout


# ---------------------------------------------------------------------------
# host-sync-in-hot-loop
# ---------------------------------------------------------------------------

HOT_SYNC_POSITIVE = """
    import jax.numpy as jnp
    import numpy as np

    def run(xs):
        total = 0.0
        for x in xs:
            y = jnp.sum(x)
            total += float(y)
        return total
"""

HOT_SYNC_NEGATIVE = """
    import jax.numpy as jnp
    import numpy as np
    from shifu_tpu.data.pipeline import host_fetch

    def run(xs):
        parts = []
        for x in xs:
            parts.append(jnp.sum(x))       # stays on device
            z = np.asarray(np.ones(3))     # numpy-only: no sync
        return float(host_fetch(jnp.stack(parts)).sum())
"""


def test_host_sync_positive(tmp_path):
    report = lint_source(tmp_path, HOT_SYNC_POSITIVE)
    assert "host-sync-in-hot-loop" in rule_names(report)


def test_host_sync_negative(tmp_path):
    report = lint_source(tmp_path, HOT_SYNC_NEGATIVE)
    assert "host-sync-in-hot-loop" not in rule_names(report)


def test_host_sync_suppressed(tmp_path):
    src = HOT_SYNC_POSITIVE.replace(
        "total += float(y)",
        "total += float(y)  # lint: disable=host-sync-in-hot-loop -- why")
    report = lint_source(tmp_path, src)
    assert "host-sync-in-hot-loop" not in rule_names(report)
    assert any(f.rule == "host-sync-in-hot-loop"
               for f in report.suppressed)


def test_host_sync_item_and_asarray(tmp_path):
    src = """
        import jax.numpy as jnp
        import numpy as np

        def run(xs):
            out = []
            while xs:
                v = jnp.dot(xs.pop(), xs.pop())
                out.append(np.asarray(v))
                s = v.item()
            return out, s
    """
    report = lint_source(tmp_path, src)
    assert rule_names(report).count("host-sync-in-hot-loop") == 2


def test_host_sync_sees_through_local_device_fn(tmp_path):
    # the streaming.py shape: a closure whose return value is the
    # product of a jax.jit-compiled callable
    src = """
        import jax
        import numpy as np

        _jits = {}

        def run(chunks, step):
            def update(s, c):
                f = _jits.get("k")
                if f is None:
                    f = jax.jit(step)
                    _jits["k"] = f
                return f(s, c)

            s, acc = None, 0.0
            for c in chunks:
                s, loss = update(s, c)
                acc += float(loss)
            return s, acc
    """
    report = lint_source(tmp_path, src)
    assert "host-sync-in-hot-loop" in rule_names(report)


# ---------------------------------------------------------------------------
# jit-in-loop
# ---------------------------------------------------------------------------

def test_jit_in_loop_positive(tmp_path):
    src = """
        import jax

        def run(xs, f):
            out = []
            for x in xs:
                out.append(jax.jit(f)(x))
            return out
    """
    report = lint_source(tmp_path, src)
    assert "jit-in-loop" in rule_names(report)


def test_jit_in_loop_negative_hoisted_and_vmap(tmp_path):
    src = """
        import jax

        def run(xs, f):
            jf = jax.jit(f)                  # hoisted: fine
            out = []
            for x in xs:
                out.append(jf(x))
                g = jax.vmap(f)(x)           # vmap is a cheap wrapper
            return out, g
    """
    report = lint_source(tmp_path, src)
    assert "jit-in-loop" not in rule_names(report)


def test_jit_in_loop_suppressed(tmp_path):
    src = """
        import jax

        def run(xs, f):
            out = []
            for x in xs:
                out.append(jax.jit(f)(x))  # lint: disable=jit-in-loop
            return out
    """
    report = lint_source(tmp_path, src)
    assert "jit-in-loop" not in rule_names(report)
    assert any(f.rule == "jit-in-loop" for f in report.suppressed)


# ---------------------------------------------------------------------------
# donation-aliasing
# ---------------------------------------------------------------------------

def test_donation_aliasing_positive(tmp_path):
    src = """
        import jax

        def run(step, state, batch):
            f = jax.jit(step, donate_argnums=(0,))
            out = f(state, batch)
            return state.sum(), out   # reads the donated buffer
    """
    report = lint_source(tmp_path, src)
    assert "donation-aliasing" in rule_names(report)


def test_donation_aliasing_negative_rebound(tmp_path):
    src = """
        import jax

        def run(step, state, batch):
            f = jax.jit(step, donate_argnums=(0,))
            state = f(state, batch)   # rebinding kills the old buffer
            return state.sum()
    """
    report = lint_source(tmp_path, src)
    assert "donation-aliasing" not in rule_names(report)


def test_donation_aliasing_suppressed(tmp_path):
    src = """
        import jax

        def run(step, state, batch):
            f = jax.jit(step, donate_argnums=(0,))
            out = f(state, batch)
            return state.sum(), out  # lint: disable=donation-aliasing
    """
    report = lint_source(tmp_path, src)
    assert "donation-aliasing" not in rule_names(report)
    assert any(f.rule == "donation-aliasing" for f in report.suppressed)


# ---------------------------------------------------------------------------
# undeclared-knob
# ---------------------------------------------------------------------------

def test_undeclared_knob_positive(tmp_path):
    src = """
        import os
        x = os.environ.get("SHIFU_TPU_TOTALLY_NEW_KNOB", "1")
        y = os.getenv("SHIFU_TPU_ANOTHER_ONE")
        z = os.environ["SHIFU_TPU_THIRD"]
    """
    report = lint_source(tmp_path, src, rules=["undeclared-knob"])
    undeclared = [f for f in report.findings
                  if "not declared" in f.message]
    assert len(undeclared) == 3


def test_declared_knob_raw_read_flagged(tmp_path):
    src = """
        import os
        x = os.environ.get("SHIFU_TPU_PREFETCH_DEPTH", "2")
    """
    report = lint_source(tmp_path, src, rules=["undeclared-knob"])
    assert any("knob_int" in f.message for f in report.findings)


def test_registry_accessor_read_clean(tmp_path):
    src = """
        from shifu_tpu.config.environment import knob_int
        x = knob_int("SHIFU_TPU_PREFETCH_DEPTH")
    """
    report = lint_source(tmp_path, src, rules=["undeclared-knob"])
    per_file = [f for f in report.findings if "dead registry" not in
                f.message]
    assert not per_file


def test_knob_accessors_round_trip(monkeypatch):
    from shifu_tpu.config import environment as env
    monkeypatch.setenv("SHIFU_TPU_PREFETCH_DEPTH", "5")
    assert env.knob_int("SHIFU_TPU_PREFETCH_DEPTH") == 5
    monkeypatch.setenv("SHIFU_TPU_PREFETCH_DEPTH", "garbage")
    assert env.knob_int("SHIFU_TPU_PREFETCH_DEPTH") == 2  # registry dflt
    monkeypatch.delenv("SHIFU_TPU_PREFETCH_DEPTH")
    assert env.knob_int("SHIFU_TPU_PREFETCH_DEPTH") == 2
    monkeypatch.setenv("SHIFU_TPU_HIST_SUBTRACT", "0")
    assert env.knob_bool("SHIFU_TPU_HIST_SUBTRACT") is False
    monkeypatch.setenv("SHIFU_TPU_HIST_SUBTRACT", "yes")
    assert env.knob_bool("SHIFU_TPU_HIST_SUBTRACT") is True
    with pytest.raises(KeyError):
        env.knob_int("SHIFU_TPU_NOT_DECLARED_ANYWHERE")
    rows = env.knobs_rows()
    names = {r["name"] for r in rows}
    assert "SHIFU_TPU_LOCKCHECK" in names
    assert len(names) >= 35
    md = env.knobs_markdown()
    for n in names:
        assert n in md


def test_knobs_all_referenced_in_package():
    """Reverse direction of the rule at package scope: every registry
    entry is read inside `shifu_tpu/` itself — the dead-entry sweep
    exempts none (the finalize hook reports them)."""
    report = engine.run([os.path.join(REPO, "shifu_tpu")],
                        rules=["undeclared-knob"])
    dead = [f for f in report.findings if "dead registry" in f.message]
    assert not dead, "\n".join(f.format() for f in dead)


def test_knobs_command_lists_every_knob(capsys):
    """`shifu knobs` prints one row a registered knob, set or not, and
    `--markdown` is KNOBS.md as checked in."""
    from shifu_tpu.cli import main as cli_main
    from shifu_tpu.config.environment import KNOBS
    assert cli_main(["knobs"]) == 0
    rows = capsys.readouterr().out.splitlines()[1:]
    assert [r.split()[0] for r in rows] == sorted(KNOBS)
    assert cli_main(["knobs", "--markdown"]) == 0
    with open(os.path.join(REPO, "KNOBS.md"), encoding="utf-8") as f:
        assert capsys.readouterr().out == f.read()


def test_every_package_getenv_is_declared():
    """Acceptance: every literal SHIFU_TPU_* string in the package is a
    declared knob (the analyzer enforces read sites; this sweeps ALL
    literals so even exotic read paths can't smuggle one in)."""
    import re
    from shifu_tpu.config.environment import KNOBS
    knob_shape = re.compile(r"^SHIFU_TPU_[A-Z0-9_]+$")
    bad = []
    pkg = os.path.join(REPO, "shifu_tpu")
    for root, dirs, files in os.walk(pkg):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fn in files:
            if not fn.endswith(".py"):
                continue
            p = os.path.join(root, fn)
            tree = ast.parse(open(p, encoding="utf-8").read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Constant) and \
                        isinstance(node.value, str) and \
                        knob_shape.match(node.value):
                    if node.value in KNOBS:
                        continue
                    bad.append(f"{p}:{node.lineno}: {node.value}")
    assert not bad, "undeclared SHIFU_TPU_* literals:\n" + "\n".join(bad)


# ---------------------------------------------------------------------------
# unregistered-fault-site
# ---------------------------------------------------------------------------

def test_fault_site_positive(tmp_path):
    src = """
        from shifu_tpu.resilience import fault_point

        def go():
            fault_point("pipeline.nonexistent_site")
    """
    report = lint_source(tmp_path, src,
                         rules=["unregistered-fault-site"])
    assert any("pipeline.nonexistent_site" in f.message
               for f in report.findings)


def test_fault_site_negative_registered_and_dynamic(tmp_path):
    src = """
        from shifu_tpu.resilience import fault_point

        def go(step):
            fault_point("pipeline.fetch")
            fault_point(f"step.{step}")
    """
    report = lint_source(tmp_path, src,
                         rules=["unregistered-fault-site"])
    per_file = [f for f in report.findings if f.line > 0]
    assert not per_file


def test_fault_site_dynamic_outside_namespace_flagged(tmp_path):
    src = """
        from shifu_tpu.resilience import fault_point

        def go(x):
            fault_point(f"mystery.{x}")
    """
    report = lint_source(tmp_path, src,
                         rules=["unregistered-fault-site"])
    assert any("namespace" in f.message for f in report.findings)


def test_fault_sites_all_referenced_in_package():
    """Reverse direction of the rule at package scope: no stale
    FAULT_SITES rows (the finalize hook reports them)."""
    report = engine.run([os.path.join(REPO, "shifu_tpu")],
                        rules=["unregistered-fault-site"])
    stale = [f for f in report.findings if "never referenced" in
             f.message]
    assert not stale, "\n".join(f.format() for f in stale)


# ---------------------------------------------------------------------------
# unregistered-dag-step
# ---------------------------------------------------------------------------

def test_dag_step_positive(tmp_path):
    src = """
        from shifu_tpu.processor.base import step_guard

        def go(ctx):
            with step_guard(ctx, "mysterystep") as ok:
                pass
    """
    report = lint_source(tmp_path, src,
                         rules=["unregistered-dag-step"])
    assert any("mysterystep" in f.message for f in report.findings)


def test_dag_step_negative_registered_and_family(tmp_path):
    src = """
        from shifu_tpu.processor.base import step_guard

        def go(ctx, name):
            with step_guard(ctx, "train") as ok:
                pass
            with step_guard(ctx, f"eval.{name}") as ok:
                pass
    """
    report = lint_source(tmp_path, src,
                         rules=["unregistered-dag-step"])
    per_file = [f for f in report.findings if f.line > 0]
    assert not per_file


def test_dag_step_dynamic_outside_family_flagged(tmp_path):
    src = """
        from shifu_tpu.processor.base import step_guard

        def go(ctx, x):
            with step_guard(ctx, f"mystery.{x}") as ok:
                pass
    """
    report = lint_source(tmp_path, src,
                         rules=["unregistered-dag-step"])
    assert any("family prefix" in f.message for f in report.findings)


def test_dag_step_dotted_nonfamily_flagged(tmp_path):
    src = """
        from shifu_tpu.processor.base import step_guard

        def go(ctx):
            with step_guard(ctx, "train.fancy") as ok:
                pass
    """
    report = lint_source(tmp_path, src,
                         rules=["unregistered-dag-step"])
    assert any("train.fancy" in f.message for f in report.findings)


def test_dag_registry_all_guarded_in_package():
    """Reverse direction at package scope: every STEP_REGISTRY entry
    with manifest=True has a live step_guard call site (the finalize
    hook reports stale rows)."""
    report = engine.run([os.path.join(REPO, "shifu_tpu")],
                        rules=["unregistered-dag-step"])
    stale = [f for f in report.findings if "stale entry" in f.message]
    assert not stale, "\n".join(f.format() for f in stale)


# ---------------------------------------------------------------------------
# unregistered-span
# ---------------------------------------------------------------------------

def test_span_positive(tmp_path):
    src = """
        from shifu_tpu.obs.trace import span

        def go():
            with span("mystery.stage"):
                pass
    """
    report = lint_source(tmp_path, src, rules=["unregistered-span"])
    assert any("mystery.stage" in f.message for f in report.findings)


def test_span_negative_registered_and_dynamic(tmp_path):
    src = """
        from shifu_tpu.obs import trace as obs_trace

        def go(node, t0, t1):
            with obs_trace.span("dag.node", node=node):
                pass
            obs_trace.record_span(f"serve.{node}", t0, t1)
    """
    report = lint_source(tmp_path, src, rules=["unregistered-span"])
    per_file = [f for f in report.findings if f.line > 0]
    assert not per_file


def test_span_dynamic_outside_family_flagged(tmp_path):
    src = """
        from shifu_tpu.obs.trace import record_span

        def go(x, t0, t1):
            record_span(f"mystery.{x}", t0, t1)
    """
    report = lint_source(tmp_path, src, rules=["unregistered-span"])
    assert any("prefix" in f.message for f in report.findings)


def test_span_numeric_local_named_span_clean(tmp_path):
    # the stats kernels use `span` as a numeric local (bin widths);
    # only calls whose first argument is a string literal are span
    # emissions
    src = """
        import numpy as np

        def go(hi, lo, span):
            width = np.maximum(hi - lo, 1e-9)
            return span(width)
    """
    report = lint_source(tmp_path, src, rules=["unregistered-span"])
    assert not report.findings


def test_span_suppressed(tmp_path):
    src = """
        from shifu_tpu.obs.trace import span

        def go():
            with span("mystery.stage"):  # lint: disable=unregistered-span -- fixture
                pass
    """
    report = lint_source(tmp_path, src, rules=["unregistered-span"])
    assert not report.findings
    assert any(f.rule == "unregistered-span" for f in report.suppressed)


def test_span_registry_all_emitted_in_package():
    """Reverse direction at package scope: every SPAN_FAMILIES entry
    has a live span()/record_span() call site (the finalize hook
    reports dead vocabulary rows)."""
    report = engine.run([os.path.join(REPO, "shifu_tpu")],
                        rules=["unregistered-span"])
    dead = [f for f in report.findings if "never emitted" in f.message]
    assert not dead, "\n".join(f.format() for f in dead)


# ---------------------------------------------------------------------------
# unwatched-collective
# ---------------------------------------------------------------------------

def test_unwatched_collective_positive(tmp_path):
    src = """
        from jax.experimental import multihost_utils
        import jax

        def merge(tree):
            return multihost_utils.process_allgather(tree)

        def assemble(mesh, spec, arrs):
            return jax.make_array_from_process_local_data(spec, arrs)

        def reduce_host(x):
            return jax.lax.psum(x, "data")
    """
    report = lint_source(tmp_path, src,
                         rules=["unwatched-collective"])
    assert len(report.findings) == 3, rule_names(report)
    assert all("watched dist wrapper" in f.message
               for f in report.findings)


def test_unwatched_collective_negative_compiled_and_wrapped(tmp_path):
    src = """
        import functools
        import jax
        from jax.experimental.shard_map import shard_map

        from shifu_tpu.parallel import dist

        @jax.jit
        def device_sum(x):
            return jax.lax.psum(x, "data")

        @functools.partial(shard_map, mesh=None,
                           in_specs=None, out_specs=None)
        def mapped(x):
            return jax.lax.pmean(x, "data")

        def merge(tree):
            return dist.allreduce_tree("fixture.merge", tree)
    """
    report = lint_source(tmp_path, src,
                         rules=["unwatched-collective"])
    assert not report.findings, rule_names(report)


def test_unwatched_collective_dist_module_exempt(tmp_path):
    (tmp_path / "shifu_tpu" / "parallel").mkdir(parents=True)
    src = """
        from jax.experimental import multihost_utils

        def _gather(tree):
            return multihost_utils.process_allgather(tree)
    """
    report = lint_source(tmp_path, src,
                         name="shifu_tpu/parallel/dist.py",
                         rules=["unwatched-collective"])
    assert not report.findings, rule_names(report)


def test_unwatched_collective_suppressed(tmp_path):
    src = """
        from jax.experimental import multihost_utils

        def merge(tree):
            return multihost_utils.process_allgather(tree)  # lint: disable=unwatched-collective -- fixture
    """
    report = lint_source(tmp_path, src,
                         rules=["unwatched-collective"])
    assert not report.findings
    assert any(f.rule == "unwatched-collective"
               for f in report.suppressed)


# ---------------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------------

def test_blocking_under_lock_positive(tmp_path):
    src = """
        import threading
        import time

        _lock = threading.Lock()

        def go(work_queue):
            with _lock:
                time.sleep(1.0)
                item = work_queue.get()
            return item
    """
    report = lint_source(tmp_path, src, rules=["blocking-under-lock"])
    assert rule_names(report).count("blocking-under-lock") == 2


def test_blocking_under_lock_negative(tmp_path):
    src = """
        import threading
        import time

        _lock = threading.Lock()

        def go(work_queue, d):
            with _lock:
                v = d.get("key")          # dict.get: not blocking
                snapshot = list(d)
            time.sleep(0.1)               # outside the lock: fine
            item = work_queue.get()       # outside the lock: fine
            return v, snapshot, item
    """
    report = lint_source(tmp_path, src, rules=["blocking-under-lock"])
    assert "blocking-under-lock" not in rule_names(report)


def test_blocking_under_lock_nested_function_exempt(tmp_path):
    src = """
        import threading
        import time

        _lock = threading.Lock()

        def go():
            with _lock:
                def later():
                    time.sleep(5)      # runs after release
                return later
    """
    report = lint_source(tmp_path, src, rules=["blocking-under-lock"])
    assert "blocking-under-lock" not in rule_names(report)


# ---------------------------------------------------------------------------
# runtime lock-order detector
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _fresh_lock_graph():
    lockcheck.reset()
    yield
    lockcheck.reset()


def test_lock_inversion_detected():
    """Deliberate A->B / B->A inversion MUST raise LockOrderError."""
    a, b = CheckedLock("A"), CheckedLock("B")

    def t1():
        with a:
            with b:
                pass

    th = threading.Thread(target=t1)
    th.start()
    th.join()

    with pytest.raises(LockOrderError, match="cycle"):
        with b:
            with a:
                pass


def test_consistent_order_passes():
    a, b, c = CheckedLock("A"), CheckedLock("B"), CheckedLock("C")
    errors = []

    def worker():
        try:
            for _ in range(50):
                with a:
                    with b:
                        with c:
                            pass
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors


def test_reacquire_same_lock_raises():
    a = CheckedLock("A")
    with a:
        with pytest.raises(LockOrderError, match="re-acquired"):
            a.acquire()


def test_transitive_cycle_detected():
    a, b, c = CheckedLock("A"), CheckedLock("B"), CheckedLock("C")
    for first, second in ((a, b), (b, c)):
        def run(x=first, y=second):
            with x:
                with y:
                    pass
        th = threading.Thread(target=run)
        th.start()
        th.join()
    # A->B and B->C recorded; C->A closes the cycle transitively
    with pytest.raises(LockOrderError, match="cycle"):
        with c:
            with a:
                pass


def test_make_lock_plain_by_default(monkeypatch):
    monkeypatch.delenv("SHIFU_TPU_LOCKCHECK", raising=False)
    lk = lockcheck.make_lock("plain")
    assert not isinstance(lk, CheckedLock)
    monkeypatch.setenv("SHIFU_TPU_LOCKCHECK", "1")
    lk = lockcheck.make_lock("checked")
    assert isinstance(lk, CheckedLock)
    with lk:
        assert lk.locked()
    assert not lk.locked()


def test_runtime_modules_use_the_shim(monkeypatch):
    """resilience/pipeline/dist locks run instrumented under
    SHIFU_TPU_LOCKCHECK=1: exercise the real lock sites in-process and
    assert edges/state stay coherent (no LockOrderError)."""
    monkeypatch.setenv("SHIFU_TPU_LOCKCHECK", "1")
    import importlib
    from shifu_tpu import resilience as res
    from shifu_tpu.data import pipeline as pipe
    from shifu_tpu.parallel import dist
    for mod in (res, pipe, dist):
        importlib.reload(mod)
    try:
        assert isinstance(pipe._timers_lock, CheckedLock)
        assert isinstance(res._retry_lock, CheckedLock)
        assert isinstance(res._events_lock, CheckedLock)
        assert isinstance(dist._inflight_lock, CheckedLock)
        pipe.add_stage_time("host_parse_s", 0.01)
        pipe.drain_stage_timers()
        res.note_event({"kind": "test"})
        res.drain_events()
        assert dist.inflight_collectives() == {}
    finally:
        monkeypatch.delenv("SHIFU_TPU_LOCKCHECK")
        for mod in (res, pipe, dist):
            importlib.reload(mod)


# ---------------------------------------------------------------------------
# unsharded-device-put
# ---------------------------------------------------------------------------

def test_unsharded_device_put_positive(tmp_path):
    src = """
        import jax

        def run(mesh, chunk):
            return jax.device_put(chunk)
    """
    report = lint_source(tmp_path, src, rules=["unsharded-device-put"])
    assert "unsharded-device-put" in rule_names(report)


def test_unsharded_device_put_negative(tmp_path):
    src = """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def run(mesh, chunk, params, shardings):
            a = jax.device_put(chunk, NamedSharding(mesh, P("data")))
            b = jax.device_put(chunk, device=jax.devices()[0])
            # a function REFERENCE is not a call missing its sharding
            c = jax.tree.map(jax.device_put, params, shardings)
            return a, b, c
    """
    report = lint_source(tmp_path, src, rules=["unsharded-device-put"])
    assert "unsharded-device-put" not in rule_names(report)


def test_unsharded_device_put_suppressed(tmp_path):
    src = """
        import jax

        def run(chunk):
            return jax.device_put(chunk)  # lint: disable=unsharded-device-put -- scalar
    """
    report = lint_source(tmp_path, src, rules=["unsharded-device-put"])
    assert "unsharded-device-put" not in rule_names(report)
    assert any(f.rule == "unsharded-device-put" for f in report.suppressed)


# ---------------------------------------------------------------------------
# ungated-device-grab
# ---------------------------------------------------------------------------

def test_ungated_device_grab_positive(tmp_path):
    src = """
        import jax

        def place(x):
            first = jax.devices()[0]
            mine = jax.local_devices()
            return first, mine
    """
    report = lint_source(tmp_path, src, rules=["ungated-device-grab"])
    assert rule_names(report).count("ungated-device-grab") == 2


def test_ungated_device_grab_negative(tmp_path):
    src = """
        import jax
        from shifu_tpu.parallel import mesh as mesh_mod

        def place(x):
            devs = mesh_mod.leased_devices()
            mine = mesh_mod.leased_local_devices()
            n = mesh_mod.device_inventory()
            k = jax.local_device_count()     # a count, not a grab
            ref = jax.devices                # reference, never called
            return devs, mine, n, k, ref
    """
    report = lint_source(tmp_path, src, rules=["ungated-device-grab"])
    assert "ungated-device-grab" not in rule_names(report)


def test_ungated_device_grab_exempts_mesh_module(tmp_path):
    """parallel/mesh.py IS the lease seam — its own jax.devices() calls
    are the one place the whole pool may be read."""
    (tmp_path / "parallel").mkdir()
    src = """
        import jax

        def leased_devices():
            return jax.devices()
    """
    report = lint_source(tmp_path, src, name="parallel/mesh.py",
                         rules=["ungated-device-grab"])
    assert "ungated-device-grab" not in rule_names(report)


def test_ungated_device_grab_suppressed(tmp_path):
    src = """
        import jax

        def probe():
            return jax.devices()  # lint: disable=ungated-device-grab -- diag
    """
    report = lint_source(tmp_path, src, rules=["ungated-device-grab"])
    assert "ungated-device-grab" not in rule_names(report)
    assert any(f.rule == "ungated-device-grab" for f in report.suppressed)


# ---------------------------------------------------------------------------
# lockcheck held-time histograms
# ---------------------------------------------------------------------------

def test_held_time_stats_recorded_per_site():
    lk = CheckedLock("histo")
    for _ in range(5):
        with lk:
            pass
    stats = lockcheck.held_time_stats()
    assert "histo" in stats
    (site, st), = stats["histo"].items()
    assert "test_lint.py:" in site
    assert st["count"] == 5
    assert st["max_s"] >= 0
    assert st["total_s"] >= st["max_s"]
    rep = lockcheck.report()
    assert rep["held"] == stats
    lockcheck.reset()
    assert lockcheck.held_time_stats() == {}


def test_ckpt_writer_lock_holds_are_submillisecond(tmp_path, monkeypatch):
    """ISSUE-5 satellite: the async-checkpoint writer lock guards only
    pointer swaps — instrumented, every hold must be far under a
    millisecond even while real saves run."""
    monkeypatch.setenv("SHIFU_TPU_CKPT_ASYNC", "1")
    import numpy as np
    from shifu_tpu.train import checkpoint as ckpt
    w = ckpt.AsyncCheckpointWriter()
    monkeypatch.setattr(w, "_lock", CheckedLock("ckpt.writer"))
    state = {"w": np.zeros((256, 256), np.float32)}
    for step in range(1, 4):
        w.save(str(tmp_path / "ck"), step, state)
    w.flush()
    stats = lockcheck.held_time_stats()
    assert "ckpt.writer" in stats
    for site, st in stats["ckpt.writer"].items():
        # sub-ms by design; 5ms ceiling absorbs CI scheduler noise
        assert st["max_s"] < 0.005, (site, st)


def test_lockcheck_atexit_dump_lists_graph_and_held(tmp_path):
    """A LOCKCHECK=1 process must end with the lock graph AND the
    held-time histogram on stderr."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SHIFU_TPU_LOCKCHECK="1",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    prog = ("from shifu_tpu.analysis.lockcheck import make_lock\n"
            "a = make_lock('outer'); b = make_lock('inner')\n"
            "with a:\n"
            "    with b:\n"
            "        pass\n")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "outer -> inner" in r.stderr
    assert "held-time per acquisition site" in r.stderr
    assert "outer @" in r.stderr and "inner @" in r.stderr


# ---------------------------------------------------------------------------
# java-property-key
# ---------------------------------------------------------------------------

def test_javaprop_positive(tmp_path):
    src = """
        def chunk_rows(props):
            return int(props.get("shifu.foo.chunkRows", 0))
    """
    report = lint_source(tmp_path, src, rules=["java-property-key"])
    assert rule_names(report) == ["java-property-key"]
    assert "shifu.foo.chunkRows" in report.findings[0].message


def test_javaprop_negative(tmp_path):
    src = """
        def chunk_rows(props):
            # a declared key is fine anywhere; one-segment dotted
            # strings (module paths, filenames) never match
            a = props.get("shifu.norm.chunkRows")
            b = "shifu.config"
            c = "not.a.shifu.key"
            return a, b, c
    """
    report = lint_source(tmp_path, src, rules=["java-property-key"])
    assert "java-property-key" not in rule_names(report)


def test_javaprop_docstring_mention_clean(tmp_path):
    src = '''
        def helper():
            """Prose mentioning shifu.bogus.key is documentation,
            not a reference."""
            return "shifu.bogus.key"
    '''
    report = lint_source(tmp_path, src, rules=["java-property-key"])
    # the docstring is skipped; the return-value literal IS flagged
    assert len(report.findings) == 1
    assert report.findings[0].line > 4


def test_javaprop_config_dir_exempt(tmp_path):
    cfg = tmp_path / "config"
    cfg.mkdir()
    path = cfg / "props.py"
    path.write_text('KEY = "shifu.anything.goes"\n', encoding="utf-8")
    report = engine.run([str(path)], rules=["java-property-key"])
    assert not report.findings


def test_javaprop_suppressed(tmp_path):
    src = """
        def chunk_rows(props):
            return props.get("shifu.foo.chunkRows")  # lint: disable=java-property-key -- fixture
    """
    report = lint_source(tmp_path, src, rules=["java-property-key"])
    assert not report.findings
    assert any(f.rule == "java-property-key" for f in report.suppressed)


def test_javaprop_registry_entries_all_referenced():
    """The dead-entry sweep over the real package: every JAVA_PROPS key
    has a live read site (subset of test_package_is_clean, kept
    separate so a dead entry names this invariant directly)."""
    report = engine.run([os.path.join(REPO, "shifu_tpu")],
                        rules=["java-property-key"])
    dead = [f for f in report.findings if "dead JAVA_PROPS" in f.message]
    assert not dead, "\n".join(f.format() for f in dead)


# ---------------------------------------------------------------------------
# raw-lock
# ---------------------------------------------------------------------------

def test_raw_lock_positive(tmp_path):
    src = """
        import threading

        _lock = threading.Lock()
        _rlock = threading.RLock()
    """
    report = lint_source(tmp_path, src, rules=["raw-lock"])
    assert rule_names(report).count("raw-lock") == 2
    # the RLock variant must point at make_lock's reentrant spelling
    assert any("reentrant=True" in f.message for f in report.findings)


def test_raw_lock_from_import_positive(tmp_path):
    src = """
        from threading import Lock

        _lock = Lock()
    """
    report = lint_source(tmp_path, src, rules=["raw-lock"])
    assert rule_names(report) == ["raw-lock"]


def test_raw_lock_negative(tmp_path):
    src = """
        import threading

        from shifu_tpu.resilience import make_lock

        _lock = make_lock("fixture.lock")
        _rlock = make_lock("fixture.rlock", reentrant=True)
        _stop = threading.Event()        # not a lock
        _cond = threading.Condition()    # not in ordering scope


        class Lock:                      # local class, not threading's
            pass


        _fake = Lock()
    """
    report = lint_source(tmp_path, src, rules=["raw-lock"])
    assert "raw-lock" not in rule_names(report)


def test_raw_lock_lockcheck_module_exempt(tmp_path):
    (tmp_path / "shifu_tpu" / "analysis").mkdir(parents=True)
    src = """
        import threading

        _graph_lock = threading.Lock()
    """
    report = lint_source(tmp_path, src,
                         name="shifu_tpu/analysis/lockcheck.py",
                         rules=["raw-lock"])
    assert not report.findings


def test_raw_lock_suppressed(tmp_path):
    src = """
        import threading

        _lock = threading.Lock()  # lint: disable=raw-lock -- fixture
    """
    report = lint_source(tmp_path, src, rules=["raw-lock"])
    assert not report.findings
    assert any(f.rule == "raw-lock" for f in report.suppressed)


# ---------------------------------------------------------------------------
# thread-shared-mutation
# ---------------------------------------------------------------------------

THREAD_SHARE_POSITIVE = """
    import threading

    from shifu_tpu.resilience import make_lock


    class Worker:
        def __init__(self):
            self.count = 0           # __init__ writes are exempt
            self.lock = make_lock("fixture.worker")

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            self.count += 1
"""


def test_thread_share_positive_with_witness(tmp_path):
    report = lint_source(tmp_path, THREAD_SHARE_POSITIVE,
                         rules=["thread-shared-mutation"])
    assert rule_names(report) == ["thread-shared-mutation"]
    f = report.findings[0]
    assert "self.count" in f.message
    # the message carries the entry-point witness, not just a claim
    assert "Thread@fixture.py" in f.message and "via" in f.message


def test_thread_share_negative_locked_write(tmp_path):
    src = THREAD_SHARE_POSITIVE.replace(
        "            self.count += 1",
        "            with self.lock:\n"
        "                self.count += 1")
    report = lint_source(tmp_path, src,
                         rules=["thread-shared-mutation"])
    assert "thread-shared-mutation" not in rule_names(report)


def test_thread_share_negative_unreached_writer(tmp_path):
    src = """
        class Plain:
            def bump(self):
                self.n = 1    # no thread entry reaches this
    """
    report = lint_source(tmp_path, src,
                         rules=["thread-shared-mutation"])
    assert not report.findings


def test_thread_share_suppressed(tmp_path):
    src = THREAD_SHARE_POSITIVE.replace(
        "self.count += 1",
        "self.count += 1  # lint: disable=thread-shared-mutation -- fixture")
    report = lint_source(tmp_path, src,
                         rules=["thread-shared-mutation"])
    assert not report.findings
    assert any(f.rule == "thread-shared-mutation"
               for f in report.suppressed)


CROSS_WORKER = """
    counter = 0


    def run_loop():
        global counter
        counter += 1
"""

CROSS_STARTER = """
    import threading

    from xworker import run_loop


    def go():
        t = threading.Thread(target=run_loop, daemon=True)
        t.start()
        return t
"""


def test_thread_share_cross_module_needs_whole_program(tmp_path):
    """The ISSUE-19 acceptance fixture: the thread start lives in one
    module, the unlocked shared write in another. Each file alone is
    provably clean under per-file analysis (no entry / no write); only
    the call-graph pass connects them."""
    w = tmp_path / "xworker.py"
    w.write_text(textwrap.dedent(CROSS_WORKER), encoding="utf-8")
    s = tmp_path / "xstarter.py"
    s.write_text(textwrap.dedent(CROSS_STARTER), encoding="utf-8")
    assert not engine.run([str(w)],
                          rules=["thread-shared-mutation"]).findings
    assert not engine.run([str(s)],
                          rules=["thread-shared-mutation"]).findings
    report = engine.run([str(w), str(s)],
                        rules=["thread-shared-mutation"])
    assert rule_names(report) == ["thread-shared-mutation"]
    f = report.findings[0]
    assert f.path.endswith("xworker.py")
    assert "global counter" in f.message
    assert "Thread@xstarter.py" in f.message


# ---------------------------------------------------------------------------
# non-atomic-write
# ---------------------------------------------------------------------------

def test_non_atomic_write_positive(tmp_path):
    src = """
        import json
        import os


        def save(path, rows, tmp):
            with open(path, "w", encoding="utf-8") as f:
                f.write("hello")
            os.replace(tmp, path)
            os.rename(tmp, path + ".2")
    """
    report = lint_source(tmp_path, src, rules=["non-atomic-write"])
    assert rule_names(report).count("non-atomic-write") == 3


def test_non_atomic_write_negative(tmp_path):
    src = """
        from shifu_tpu.resilience import atomic_path, atomic_write


        def save(path, log_path):
            with atomic_write(path, "w", encoding="utf-8") as f:
                f.write("hello")
            with atomic_path(path) as tmp:
                # staging into the atomic context's temp is the seam
                with open(tmp, "w", encoding="utf-8") as f:
                    f.write("staged")
            with open(log_path, "a", encoding="utf-8") as f:
                f.write("line")        # append: torn tail at worst
            with open(path, encoding="utf-8") as f:
                return f.read()        # reads are never flagged
    """
    report = lint_source(tmp_path, src, rules=["non-atomic-write"])
    assert "non-atomic-write" not in rule_names(report)


def test_non_atomic_write_sanctioned_module_exempt(tmp_path):
    (tmp_path / "shifu_tpu" / "data").mkdir(parents=True)
    src = """
        import os


        def _commit(tmp, path):
            os.replace(tmp, path)    # fs.py IS the atomic seam
    """
    report = lint_source(tmp_path, src,
                         name="shifu_tpu/data/fs.py",
                         rules=["non-atomic-write"])
    assert not report.findings


def test_non_atomic_write_suppressed(tmp_path):
    src = """
        def save(path):
            with open(path, "w") as f:  # lint: disable=non-atomic-write -- fixture
                f.write("x")
    """
    report = lint_source(tmp_path, src, rules=["non-atomic-write"])
    assert not report.findings
    assert any(f.rule == "non-atomic-write" for f in report.suppressed)


# ---------------------------------------------------------------------------
# swallowed-exception
# ---------------------------------------------------------------------------

def test_swallowed_exception_positive(tmp_path):
    src = '''
        def lossy(fn):
            try:
                return fn()
            except Exception:
                pass


        def lossy2(fn):
            try:
                return fn()
            except:
                "docstring-shaped silence"
    '''
    report = lint_source(tmp_path, src, rules=["swallowed-exception"])
    assert rule_names(report).count("swallowed-exception") == 2


def test_swallowed_exception_negative(tmp_path):
    src = """
        import logging
        import queue

        log = logging.getLogger(__name__)


        def ok(fn, q):
            try:
                return fn()
            except ValueError:
                log.warning("fell back")    # log line: evidence
            try:
                return q.get_nowait()
            except queue.Empty:
                pass                        # absence IS the answer
            try:
                return fn()
            except RuntimeError:
                raise                       # re-raise: evidence
            try:
                return fn()
            except OSError:
                fallback = None             # recorded fallback
                return fallback
    """
    report = lint_source(tmp_path, src, rules=["swallowed-exception"])
    assert "swallowed-exception" not in rule_names(report)


def test_swallowed_exception_absorbed_helper_is_evidence(tmp_path):
    src = """
        from shifu_tpu.resilience import absorbed


        def ok(fn):
            try:
                return fn()
            except Exception as e:
                absorbed("fixture.site", e)
    """
    report = lint_source(tmp_path, src, rules=["swallowed-exception"])
    assert not report.findings


def test_swallowed_exception_suppressed(tmp_path):
    src = """
        def lossy(fn):
            try:
                return fn()
            except Exception:  # lint: disable=swallowed-exception -- fixture
                pass
    """
    report = lint_source(tmp_path, src, rules=["swallowed-exception"])
    assert not report.findings
    assert any(f.rule == "swallowed-exception"
               for f in report.suppressed)


def test_absorbed_counter_runtime():
    """The sanctioned-absorb helper leaves the monitoring evidence the
    rule's message promises: a per-site counter snapshot."""
    from shifu_tpu import resilience as res
    before = res.absorb_counts().get("lint.fixture", 0)
    try:
        raise ValueError("boom")
    except ValueError as e:
        res.absorbed("lint.fixture", e)
    assert res.absorb_counts()["lint.fixture"] == before + 1


# ---------------------------------------------------------------------------
# whole-program model (pass 1): call graph, thread entries, lock scopes
# ---------------------------------------------------------------------------

def build_program(tmp_path, files):
    """Assemble a Program from {name: source} the way engine pass 1
    does."""
    from shifu_tpu.analysis import program as program_mod
    parsed = []
    for name, src in files.items():
        p = tmp_path / name
        p.write_text(textwrap.dedent(src), encoding="utf-8")
        parsed.append((str(p),
                       ast.parse(p.read_text(encoding="utf-8"))))
    return program_mod.build(parsed)


def test_program_thread_and_submit_entries(tmp_path):
    prog = build_program(tmp_path, {
        "w.py": """
            def job():
                return 1


            def other():
                return 2
        """,
        "s.py": """
            import threading

            from w import job, other


            def go(pool):
                threading.Thread(target=job, daemon=True).start()
                pool.submit(other)
        """,
    })
    got = {(e.qname, e.via) for e in prog.entries}
    assert ("w.job", "Thread") in got
    assert ("w.other", "submit") in got


def test_program_lock_scope_attribution(tmp_path):
    prog = build_program(tmp_path, {"m.py": """
        class C:
            def bump(self):
                with self._lock:
                    self.a = 1
                self.b = 2
                with self._cond:   # Condition holds its lock too
                    self.c = 3
    """})
    writes = {w.target: w.locked
              for w in prog.functions["m.C.bump"].writes}
    assert writes == {"self.a": True, "self.b": False, "self.c": True}


def test_program_locked_call_edges_gate_reachability(tmp_path):
    prog = build_program(tmp_path, {"m.py": """
        import threading


        class C:
            def start(self):
                threading.Thread(target=self.run).start()

            def run(self):
                with self._lock:
                    self.guarded()
                self.open_call()

            def guarded(self):
                self.x = 1

            def open_call(self):
                self.y = 2
    """})
    reach = prog.reachable_from_threads()
    assert reach["m.C.run"] is True
    # only ever entered through a locked call site: writes inside are
    # attributed to the caller's lock
    assert reach["m.C.guarded"] is False
    assert reach["m.C.open_call"] is True
    witness = prog.thread_witness("m.C.open_call")
    assert witness.startswith("Thread@m.py:")
    assert "C.run" in witness and "C.open_call" in witness


def test_program_unresolvable_call_has_no_edge(tmp_path):
    """Precision bias: a call the resolver cannot place produces no
    edge — never false reachability."""
    prog = build_program(tmp_path, {"m.py": """
        import threading


        def run(cb):
            cb()                  # opaque callable: no edge


        def go():
            threading.Thread(target=run).start()
    """})
    edges = prog.edges()
    assert edges.get("m.run", []) == []
    assert prog.reachable_from_threads() == {"m.run": True}


# ---------------------------------------------------------------------------
# the converted make_lock sites in the LOCKCHECK=1 DAG report
# ---------------------------------------------------------------------------

def test_converted_locks_in_lockcheck_graph(tmp_path):
    """ISSUE-19 acceptance: the five former raw-lock sites
    (service.schema, fleet.arm, fleet.registry, fleet.lat,
    native.init) plus the locks this PR introduced (batcher.stats,
    resilience.absorb) all construct through make_lock, import clean
    under SHIFU_TPU_LOCKCHECK=1, and show up in the DAG report once
    exercised."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SHIFU_TPU_LOCKCHECK="1",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    prog = textwrap.dedent("""\
        import json, os
        import numpy as np

        # minimal published registry: FleetService reads manifests
        # only; model residency stays lazy
        os.makedirs("reg/models/m1/v001", exist_ok=True)
        with open("reg/models/m1/v001/manifest.json", "w") as f:
            json.dump({"family": "NN"}, f)
        with open("reg/models/m1/HEAD", "w") as f:
            f.write("v001")
        from shifu_tpu.models.spec import save_model
        save_model("model0.npz", "lr", {"n_in": 3},
                   {"w": np.zeros(3, np.float32),
                    "b": np.zeros(1, np.float32)})

        from shifu_tpu.analysis import lockcheck
        from shifu_tpu import native, resilience
        from shifu_tpu.serve import batcher, fleet, service

        with native._lock:
            pass
        resilience.absorbed("lockcheck.fixture", None)
        batcher.MicroBatcher(lambda b: None, max_rows=8).stats()
        arm = fleet._ArmState("m", "v", "d", 0.1, 0.05, 16, 4)
        with arm._lock:
            pass
        fl = fleet.FleetService("reg", hbm_budget_mb=0)
        with fl._lock:
            with fl._lock:      # fleet.registry is reentrant: legal
                pass
        with fl._lat_lock:
            pass
        svc = service.ScorerService(model_paths=["model0.npz"],
                                    aot_compile=False)
        with svc._schema_lock:
            pass
        print("HELD:" + ",".join(sorted(lockcheck.report()["held"])))
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, cwd=str(tmp_path), timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    held = set(r.stdout.split("HELD:")[1].strip().split(","))
    assert {"service.schema", "fleet.arm", "fleet.registry",
            "fleet.lat", "native.init", "batcher.stats",
            "resilience.absorb"} <= held, held
