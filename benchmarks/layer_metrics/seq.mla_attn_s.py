"""Device seconds of one call's blocked causal attention (``seq.mla/attn``),
forward and backward, the main model's layers, from the program's scopes."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.mla", "attn", mtp=False)
