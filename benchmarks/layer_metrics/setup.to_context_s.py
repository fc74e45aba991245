"""Seconds from the first import of ``pio_tpu`` to the first
``ComputeContext``: importing JAX and the program's modules, and asking for
the devices (reaching the chip)."""

from process_record import between


def read(reading):
    return between(reading, "pio_tpu_imported", "context_built")
