"""Seconds JAX spent lowering jaxprs to MLIR modules inside the first call:
its ``jaxpr_to_mlir_module_duration`` events."""

from process_record import first_call_xla


def read(reading):
    return first_call_xla(reading, "lower_s")
