"""Subprocess worker for the 2-process DCN scale-out tests
(tests/test_multihost.py). Not a test module.

Each process: jax.distributed.initialize over localhost (gloo CPU
collectives = the test-rig stand-in for DCN), then one of two modes:

- ``--mode train`` (default): build the SAME synthetic table
  deterministically, run the streaming trainer end-to-end (each
  process serves only its own slice of every chunk —
  train/streaming.py put()), and have process 0 dump the result. The
  single-process reference run uses the identical script with
  --nproc 1 so both sides share one code path and one device count.
- ``--mode barrier-kill``: the dead-peer drill. Both processes meet at
  a first barrier; process 1 then SIGKILLs itself and process 0 walks
  into a second barrier its peer will never reach. With
  SHIFU_TPU_BARRIER_TIMEOUT_S set, the survivor must exit — rc 17 for
  the watchdog's DistTimeout, rc 18 for any other fast failure (e.g.
  the collective itself erroring on the dead connection) — instead of
  hanging. Exits via os._exit: the distributed runtime's atexit
  teardown would itself block on the dead peer.
- ``--mode barrier-stall``: the stuck-peer drill. Process 1 stays
  ALIVE (sockets open, nothing errors) but never enters the second
  barrier — the case only the watchdog can catch: the survivor's
  collective blocks indefinitely until the SHIFU_TPU_BARRIER_TIMEOUT_S
  deadline dumps thread stacks and raises DistTimeout (rc 17).
- ``--mode preempt-drill``: the cluster-wide preemption-consensus
  drill. Both processes run a checkpointed barrier loop under
  `graceful_shutdown`; the test SIGTERMs process 0, whose handler
  publishes the ``preempt.marker``. Process 0 exits the loop at the
  next boundary (checkpoint + rc 75); process 1 OBSERVES the marker
  from inside its watched barrier and takes the same path — BOTH
  processes must exit rc 75, neither via barrier timeout.
- ``--mode preempt-resume``: the elastic restart after the drill —
  run with --nproc 1 --local-devices 1 (a SMALLER mesh than the
  drill's 2×2), it clears the stale marker the way step_guard does and
  `restore_resharded`s the drill's checkpoint onto the 1-device mesh,
  verifying the values bitwise.
- ``--mode stats``: the pod-scale data-plane drill. --out is a
  ModelSet root (already ``shifu init``-ed); every process runs
  ``shifu stats`` over it. With SHIFU_TPU_DATA_SHARD=auto each host
  reads only its shard and the partials merge through the watched
  collectives; ColumnConfig.json must come out bitwise identical to a
  1-process run.
- ``--mode stats-kill``: same, but process 1 arms
  SHIFU_TPU_FAULT=dist.allreduce_tree:kill:1 and SIGKILLs itself at
  the first watched merge. The survivor must exit rc 17 (DistTimeout)
  or rc 18 (fast collective failure) instead of hanging.
- ``--mode corr``: like ``stats`` but runs ``shifu stats
  -correlation`` over an already stats-filled ModelSet. The sharded
  streaming path computes per-chunk Pearson moments on the host-LOCAL
  mesh and replays them through the striped merge; correlation.csv
  must come out bitwise identical to a 1-process run.
- ``--mode ingest``: the sharded streaming-ingest drill. --out is a
  pre-created row-log root (data/ingest.py); with
  SHIFU_TPU_DATA_SHARD=auto each process owns the partitions
  ``k % nproc == pid`` (the PR-14 chunk-ownership idiom) and appends
  only the rows routed to its partitions (row j → partition j % P), so
  a 2-process log must merge-read identical to a 1-process one. Each
  process prints its owned set for the disjointness assertion.

Usage: python multihost_worker.py --port P --nproc N --pid I --out F
"""

import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--port", type=int, required=True)
ap.add_argument("--nproc", type=int, required=True)
ap.add_argument("--pid", type=int, required=True)
ap.add_argument("--out", required=True)
ap.add_argument("--local-devices", type=int, default=2)
ap.add_argument("--mode",
                choices=("train", "barrier-kill", "barrier-stall",
                         "preempt-drill", "preempt-resume",
                         "stats", "stats-kill", "corr", "ingest"),
                default="train")
args = ap.parse_args()

# environment must be set before jax import
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           f"{args.local_devices}")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
if args.nproc > 1:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"localhost:{args.port}",
        num_processes=args.nproc, process_id=args.pid)

if args.mode in ("barrier-kill", "barrier-stall"):
    import signal
    import time

    from shifu_tpu.parallel import dist

    dist.writer_barrier("chaos-ready")   # both processes fully up
    if args.pid == 1:
        if args.mode == "barrier-kill":
            print("victim: SIGKILL self", file=sys.stderr, flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
        print("victim: stalling (alive, never reaching the barrier)",
              file=sys.stderr, flush=True)
        time.sleep(300)   # the test kills us once the survivor exits
        os._exit(0)
    t0 = time.monotonic()
    try:
        dist.writer_barrier("chaos-after-kill")
    except dist.DistTimeout as e:
        print(f"DIST_TIMEOUT after {time.monotonic() - t0:.1f}s: {e}",
              file=sys.stderr, flush=True)
        os._exit(17)
    except BaseException as e:  # noqa: BLE001 — any fast failure is a pass
        print(f"DIST_FAIL after {time.monotonic() - t0:.1f}s "
              f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
        os._exit(18)
    print("barrier with a dead peer unexpectedly succeeded",
          file=sys.stderr, flush=True)
    os._exit(19)

if args.mode in ("preempt-drill", "preempt-resume"):
    import time

    import numpy as np

    from shifu_tpu import resilience
    from shifu_tpu.parallel import dist, mesh as mesh_mod
    from shifu_tpu.train import checkpoint as ckpt_mod

    workdir = os.path.dirname(os.path.abspath(args.out))
    ckpt_dir = os.path.join(workdir, "ckpt")
    resilience.set_abort_scope(os.path.join(workdir, "tmp"))
    # deterministic device-sharded state over THIS process's local
    # devices (fully addressable, so the snapshot/restore path is the
    # single-host one regardless of nproc)
    local_mesh = mesh_mod.make_mesh(devices=jax.local_devices())
    w_host = np.arange(16, dtype=np.float32).reshape(4, 4)
    state = {"w": jax.device_put(
        w_host, jax.sharding.NamedSharding(
            local_mesh, jax.sharding.PartitionSpec("data")))}

    if args.mode == "preempt-resume":
        # a fresh run invalidates the drill's marker (step_guard analog)
        resilience.clear_preempt_marker()
        restored = ckpt_mod.restore_resharded(
            ckpt_dir, {"w": w_host}, mesh=local_mesh)
        assert restored is not None, f"nothing restorable in {ckpt_dir}"
        step, st = restored
        got = np.asarray(st["w"])
        assert np.array_equal(got, w_host), (got, w_host)
        print(f"RESUMED step={step} on a {local_mesh.devices.size}-device "
              "mesh", file=sys.stderr, flush=True)
        os._exit(0)

    with resilience.graceful_shutdown("preempt-drill"):
        try:
            for i in range(600):
                if resilience.preempt_requested():
                    if dist.is_writer():
                        ckpt_mod.save_checkpoint(ckpt_dir, i + 1, state)
                        ckpt_mod.flush_saves()
                    raise resilience.Preempted(
                        f"drill preempted at boundary {i}")
                dist.writer_barrier(f"drill-{i}")
                if i == 0 and args.pid == 0:
                    with open(os.path.join(workdir, "drill.ready"),
                              "w") as f:
                        f.write("1")
                time.sleep(0.25)
        except resilience.Preempted as e:
            # peers exit first, coordinator last (its death tears down
            # the coordination service and SIGABRTs blocked peers)
            resilience.preempt_exit_sync()
            print(f"PREEMPT_EXIT {e}", file=sys.stderr, flush=True)
            os._exit(resilience.PREEMPT_RC)
    print("drill loop exhausted without preemption", file=sys.stderr,
          flush=True)
    os._exit(20)

if args.mode in ("stats", "stats-kill", "corr"):
    from shifu_tpu.cli import main as cli_main  # noqa: E402
    from shifu_tpu.parallel import dist  # noqa: E402

    if args.mode == "stats-kill" and args.pid == 1:
        # die at the FIRST watched merge collective of the run — the
        # mid-merge SIGKILL drill; the survivor must exit through the
        # watchdog/poison machinery, never hang
        os.environ["SHIFU_TPU_FAULT"] = "dist.allreduce_tree:kill:1"
    cmd = ["--dir", args.out, "stats"]
    if args.mode == "corr":
        cmd.append("-correlation")
    try:
        rc = cli_main(cmd)
    except dist.DistTimeout as e:
        print(f"DIST_TIMEOUT: {e}", file=sys.stderr, flush=True)
        os._exit(17)
    except BaseException as e:  # noqa: BLE001 — any fast failure
        print(f"DIST_FAIL {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        os._exit(18)
    print(f"STATS_DONE rc={rc}", file=sys.stderr, flush=True)
    # os._exit: the distributed runtime's atexit teardown could block
    # if a peer already exited
    os._exit(int(rc or 0))

if args.mode == "ingest":
    from shifu_tpu.data.ingest import RowLog  # noqa: E402

    lg = RowLog(args.out)   # pre-created by the test; header in log.json
    owned = lg.owned_partitions()
    print(f"OWNED {args.pid} {sorted(owned)}", flush=True)
    n_rows = 240
    for j in range(n_rows):
        part = j % lg.partitions
        if part not in owned:
            continue   # a peer's partition — never written from here
        lg.append([f"{j}|row{j}"], part=part)
    lg.seal_all()
    print(f"INGEST_DONE {args.pid}", file=sys.stderr, flush=True)
    # os._exit: the distributed runtime's atexit teardown could block
    # if a peer already exited
    os._exit(0)

import numpy as np  # noqa: E402

from shifu_tpu.config.model_config import ModelTrainConf  # noqa: E402
from shifu_tpu.train.streaming import train_nn_streaming  # noqa: E402

N_ROWS, DIM = 2048, 8
rng = np.random.default_rng(20260730)
beta = rng.normal(0, 1, DIM).astype(np.float32)
x = rng.normal(0, 1, (N_ROWS, DIM)).astype(np.float32)
y = (x @ beta + rng.normal(0, 0.5, N_ROWS) > 0).astype(np.float32)
w = np.ones(N_ROWS, np.float32)

conf = ModelTrainConf()
conf.params = {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
               "ActivationFunc": ["tanh"], "Propagation": "ADAM",
               "LearningRate": 0.05}
conf.numTrainEpochs = 5
conf.baggingNum = 2
conf.baggingSampleRate = 1.0
conf.baggingWithReplacement = False
conf.validSetRate = 0.25
conf.earlyStoppingRounds = 0
conf.convergenceThreshold = 0.0

res = train_nn_streaming(
    conf, lambda a, b: (x[a:b], y[a:b], w[a:b]),
    n_rows=N_ROWS, input_dim=DIM, seed=7, chunk_rows=256)

# resident-path placement must also work multi-host: device_put with a
# global NamedSharding slices each process's addressable shards from
# the (identical) full host array — prove it executes and reduces to
# the right value
from shifu_tpu.parallel import mesh as mesh_mod  # noqa: E402

mesh = mesh_mod.default_mesh()
sharded = mesh_mod.shard_axis(mesh, x, axis=0)
row_sum = float(jax.jit(lambda a: a.sum())(sharded))

if args.pid == 0:
    flat = np.concatenate(
        [np.asarray(p).ravel()
         for layer in res.params_per_bag[0] for p in layer.values()])
    np.savez(args.out, params0=flat,
             val_errors=res.val_errors, train_errors=res.train_errors,
             best_val=res.best_val, row_sum=row_sum,
             n_global_devices=len(jax.devices()))
print(f"proc {args.pid}/{args.nproc} done", file=sys.stderr)
