"""The first job's account: what `setup_s` is made of, read from inside
the program.

Since PR 35 every `shifu_tpu.obs.trace.span("train.job")` leaves a job
record when it closes (`shifu_tpu.obs.trace.job_records()`, the process's
first kept for good): the span's `attrs`, `start_s` (the job's start in
seconds since the OS started the process, from `/proc/self/stat`),
`seconds`, and `builds`: what jax built inside the job as its own
`jax.monitoring` events report it on the building thread, self seconds by
stage (`trace_s`, `lower_s`, `load_s`, `compile_s`), programs counted
(`traced`, `loaded`, `compiled`) and the functions that took most.

The first `train.job` of a `run.py` process is the harness's warm-up call,
so its record splits `setup_s` (process start to the window's start) in two:

| counter | metric (`layer_metrics/<name>.py`) |
| --- | --- |
| `job_records()[0]["start_s"]` | `before_first_job_s`: imports, the runtime's start, the data; nothing of the trainers' |
| `job_records()[0]["seconds"]` | `first_job_s`: the warm-up job, start to end |
| `...["builds"]["trace_s"]` | `first_job_trace_s`: jax tracing the program's Python |
| `...["builds"]["lower_s"]` | `first_job_lower_s`: jaxpr to MLIR |
| `...["builds"]["load_s"]` | `first_job_load_s`: executables read back from the persistent cache (0 on a cold cache) |
| `...["builds"]["compile_s"]` | `first_job_compile_s`: the compiler (0 on a warm cache) |
| `...["builds"]["traced"]` | `first_job_programs`: programs traced |

The four build parts add up to no more than `first_job_s`; the rest is the
job's own run and its host phases. The readers run in the run's own process
after the window and read counters, not the trace. Each returns None where
the checkout's `shifu_tpu.obs.trace` has no `job_records` (a parent from
before PR 35) or no job has closed, and the result line leaves the metric out.
"""


def first_record():
    """The process's first job record, or None."""
    try:
        from shifu_tpu.obs import trace
    except ImportError:
        return None
    job_records = getattr(trace, "job_records", None)
    if job_records is None:
        return None
    records = job_records()
    return records[0] if records else None


def of_job(key: str):
    """`key` of the first job record (None where it holds none)."""
    record = first_record()
    return None if record is None else record.get(key)


def of_builds(key: str):
    """`key` of the first job record's `builds`."""
    builds = of_job("builds")
    return None if builds is None else builds.get(key)
