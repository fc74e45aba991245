"""Seconds from the first ``ComputeContext`` to the first train call:
making the data, the algorithm and its ``PreparedData``."""

from process_record import between


def read(reading):
    return between(reading, "context_built", "first_call_enter")
