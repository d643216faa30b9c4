"""Seconds from the moment the OS started the process to the start of its
first `train.job` (the harness's warm-up call): imports, the runtime's
start, the data made on the device; nothing of the trainers'. With
`first_job_s` it comes to the run's `setup_s` (`benchmark/first_job.py`).
None where the program keeps no job records or the host has no `/proc`."""

from benchmark import first_job


def read(context):
    return first_job.of_job("start_s")
