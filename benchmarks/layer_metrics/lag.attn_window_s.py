"""Device seconds of one call's blocked attention on the window layers
(``seq.gqa/attn/window``), forward and backward, from the program's scopes:
the key tiles the window leaves a query block, and no others."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.gqa", "attn", "window") or None  # never 0: no such scope
