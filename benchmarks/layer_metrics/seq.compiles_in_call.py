"""Programs compiled or loaded from the cache during the stats call, a
call made after the warm one: the steady state is 0 (``seqrec._programs``
keeps the jitted steppers between calls)."""

from scope_stats import xla_counts


def read(reading):
    xla = xla_counts(reading)
    if xla is None:
        return None
    return xla["in_call"]["compiles"] + xla["in_call"]["cache_loads"]
