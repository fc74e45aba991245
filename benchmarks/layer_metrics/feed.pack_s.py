"""Host sort, pack and wire encode of one call: the program's own ``pack_s``
from a call made with ``stats={}`` (phases serialised, outside any window)."""


def read(reading):
    return reading["stats"].get("pack_s")
