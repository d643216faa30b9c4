"""Seconds the first job spent on build requests the persistent cache
answered: key, read and deserialisation of each executable
(`benchmark/first_job.py`). 0 on a cold cache."""

from benchmark import first_job


def read(context):
    return first_job.of_builds("load_s")
