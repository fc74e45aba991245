"""Device seconds of one call's per-chunk segment sums and their dense
adds into ``A`` and ``b`` (``als.normal_eq/segment_sum``), both half-steps,
from the program's scopes."""

from scope_stats import scope_seconds


def read(reading):
    return scope_seconds(reading, "als.normal_eq", "segment_sum")
