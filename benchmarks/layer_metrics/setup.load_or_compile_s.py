"""Seconds inside the first call in XLA's compiler or loading executables
from the persistent cache: the backend-compile events, a cache hit included.
(``setup.compile_s``, ``seq.compile_s`` and ``nem.compile_s`` read the same
events over the process up to the stats call's end.)"""

from process_record import first_call_xla


def read(reading):
    return first_call_xla(reading, "compile_s", "cache_load_s")
