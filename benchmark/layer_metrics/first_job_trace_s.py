"""Seconds jax spent tracing the program's Python inside the first job
(self time of `/jax/core/compile/jaxpr_trace_duration`, booked by the
program to the job open on the building thread: `benchmark/first_job.py`)."""

from benchmark import first_job


def read(context):
    return first_job.of_builds("trace_s")
