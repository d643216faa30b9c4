"""Programs traced inside the first job: functions jax traced at the top,
a jitted helper traced inside another being part of that program
(`benchmark/first_job.py`)."""

from benchmark import first_job


def read(context):
    return first_job.of_builds("traced")
