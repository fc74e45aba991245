"""Device seconds of one call's routing: router, top-k, sort by expert and
the weighted combine (``seq.moe/route``), the main model's layers."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.moe", "route", mtp=False)
