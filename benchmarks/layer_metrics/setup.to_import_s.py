"""Seconds from the OS's start of the process to the first import of
``pio_tpu``: the interpreter, the harness's own files, numpy. The harness's
``setup_s`` starts inside this interval, at ``run.py``'s first line."""

from process_record import between


def read(reading):
    return between(reading, "process_start", "pio_tpu_imported")
