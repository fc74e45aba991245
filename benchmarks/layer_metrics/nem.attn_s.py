"""Device seconds of one call's blocked causal attention in the attention
layers (``seq.gqa/attn/full``: no position encoding here, 16 query heads
folded into a KV head's tile), forward and backward."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.gqa", "attn", "full") or None  # never 0: no such scope
