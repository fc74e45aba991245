"""Share of the roofline of the grouped expert matmuls: the least time the
chip could take for the routed pairs the reference counted, over the device
seconds of every ``seq.moe/experts`` scope."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_experts", "seq.moe", "experts")
