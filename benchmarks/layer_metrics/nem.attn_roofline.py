"""Share of the roofline of the attention layers' causal scores and ``P v``,
forward and backward (``nemotron_cost``: lower triangle, ``k`` and ``v`` once
a KV head, nothing recomputed), over the device seconds of
``seq.gqa/attn/full``."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_attn", "seq.gqa", "attn", "full")
