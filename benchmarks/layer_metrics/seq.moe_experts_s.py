"""Device seconds of one call's grouped expert matmuls and their row gather
(``seq.moe/experts``), the main model's layers."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.moe", "experts", mtp=False)
