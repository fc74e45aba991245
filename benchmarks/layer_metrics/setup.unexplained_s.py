"""What the first call costs beyond a call that no event of JAX's compile
path holds: ``setup.first_call_excess_s`` less tracing, lowering and the
compiles or cache loads (uploading executables, Python-side cache misses, the
first transfer's set-up)."""

from process_record import first_call_excess, first_call_xla


def read(reading):
    excess = first_call_excess(reading)
    path = first_call_xla(reading, "trace_s", "lower_s", "compile_s",
                          "cache_load_s")
    return None if excess is None or path is None else excess - path
