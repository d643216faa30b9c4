"""Seconds of the process's first `train.job`, start to end: the warm-up
call's job, with whatever jax built for it (`benchmark/first_job.py`).
None where the program keeps no job records."""

from benchmark import first_job


def read(context):
    return first_job.of_job("seconds")
