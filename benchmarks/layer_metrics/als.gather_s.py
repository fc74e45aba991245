"""Device seconds of one call's factor-row gathers
(``als.normal_eq/gather``), both half-steps, from the program's scopes."""

from scope_stats import scope_seconds


def read(reading):
    return scope_seconds(reading, "als.normal_eq", "gather")
