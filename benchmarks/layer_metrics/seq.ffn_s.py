"""Device seconds of one call's dense SwiGLU layers and shared experts
(``seq.ffn``), the main model's layers."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.ffn", mtp=False)
