"""Share of the roofline of the dense SwiGLU mixers: the least time the chip
could take for their three matmuls, forward and backward (``granite_cost``:
ten mixers of 2,048 x 8,192, the weights read in 2 B three times and their
float32 gradient written, a token's input read and output written), over the
device seconds of ``seq.ffn``."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_ffn", "seq.ffn")
