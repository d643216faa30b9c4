"""Seconds jax spent lowering jaxprs to MLIR inside the first job (self time
of `/jax/core/compile/jaxpr_to_mlir_module_duration`, the Mosaic lowering
of a Pallas kernel included: `benchmark/first_job.py`)."""

from benchmark import first_job


def read(context):
    return first_job.of_builds("lower_s")
