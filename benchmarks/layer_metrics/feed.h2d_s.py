"""Host-to-device transfer of one call's wire: the program's own ``h2d_s``
from the call made with ``stats={}``."""


def read(reading):
    return reading["stats"].get("h2d_s")
