"""Device seconds of one call's Adam update and selection-bias step
(``seq.opt``)."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.opt")
