"""Device seconds of one call's routing in the expert layers: the 128-wide
router, top-k, the sort by expert and the weighted combine (``seq.moe/route``)."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.moe", "route") or None  # never 0: no such scope
