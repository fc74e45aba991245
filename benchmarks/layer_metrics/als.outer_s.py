"""Device seconds of one call's blocked outer products, the two einsums of
``als.normal_eq/outer``, both half-steps, from the program's scopes."""

from scope_stats import scope_seconds


def read(reading):
    return scope_seconds(reading, "als.normal_eq", "outer")
