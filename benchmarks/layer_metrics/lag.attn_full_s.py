"""Device seconds of one call's blocked causal attention on the full layers
(``seq.gqa/attn/full``), forward and backward, from the program's scopes."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.gqa", "attn", "full") or None  # never 0: no such scope
