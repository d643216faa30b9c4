"""Seconds the first job spent on build requests that went to the compiler
(`benchmark/first_job.py`). 0 on a warm cache."""

from benchmark import first_job


def read(context):
    return first_job.of_builds("compile_s")
