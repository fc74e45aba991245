"""Device seconds of one call's selection in the sparse-attention cell
(``seq.dsa/select``): each query's top 2,048 of its index scores, the tie
rule, the mask's packing into bits and the active key blocks, from the
program's scopes. A top-k over up to 16,384 candidates counts no flops, so
this reads its whole cost."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.dsa", "select") or None  # never 0: no such scope
