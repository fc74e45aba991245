"""Device seconds of one call's wire decode and blocked-layout packs
(``als.decode`` + ``als.pack``), from the program's scopes."""

from scope_stats import scope_seconds


def read(reading):
    pack = scope_seconds(reading, "als.pack")
    return None if pack is None else pack + scope_seconds(reading, "als.decode")
