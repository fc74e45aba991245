"""Share of the roofline of the blocked attention: the least time the chip
could take for the call's causal scores and P v, forward and backward
(``seq_cost``: lower triangle, nothing recomputed), over the device seconds
of every ``seq.mla/attn`` scope."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_attn", "seq.mla", "attn")
