"""Seconds the process spent in XLA's compiler or loading executables from
the persistent cache, up to the end of the stats call (the program's own
count): the warm call's share of ``setup_s``."""

from scope_stats import xla_counts


def read(reading):
    xla = xla_counts(reading)
    return None if xla is None else xla["compile_s"] + xla["cache_load_s"]
