"""Share of the roofline of the full layers' attention: the least time the
chip could take for their causal scores and P v, forward and backward
(``laguna_cost``: lower triangle, k and v once a KV head, nothing
recomputed), over the device seconds of ``seq.gqa/attn/full``."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_attn_full", "seq.gqa", "attn", "full")
