"""Share of the roofline of the attention over the selection: the least
time the chip could take for the **selected** pairs, forward and backward
(``keye_cost``: ``sum_t min(t + 1, 2048)`` a head and row), over the device
seconds of ``seq.gqa/attn/sparse``. It counts the same work whatever
implements it: tiles masked by the selection read at most the selected share
of the causal pairs (23.44% at 16,384 events) of it."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_sparse_attn", "seq.gqa", "attn", "sparse")
