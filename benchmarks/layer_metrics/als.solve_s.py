"""Device seconds of one call's solves (``als.solve`` and below: the
regulariser add, the CG set-up and its sweeps), both half-steps, from the
program's own scopes (``stats["device_scope_s"]``)."""

from scope_stats import scope_seconds


def read(reading):
    return scope_seconds(reading, "als.solve")
