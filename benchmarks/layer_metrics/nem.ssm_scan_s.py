"""Device seconds of one call's selective recurrence in the Mamba-2 layers
(``seq.ssm/ssd``): the decays, the chunks' products, the carried states and
``D x``, forward and backward, from the program's scopes."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.ssm", "ssd") or None  # never 0: no such scope
