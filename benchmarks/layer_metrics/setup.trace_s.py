"""Seconds JAX spent tracing jitted functions to jaxprs inside the first
call: its ``jaxpr_trace_duration`` events, each less the traces inside it."""

from process_record import first_call_xla


def read(reading):
    return first_call_xla(reading, "trace_s")
