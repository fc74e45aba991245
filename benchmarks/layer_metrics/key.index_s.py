"""Device seconds of one call's lightning indexer in the sparse-attention
cell (``seq.dsa/index``): its projections of the detached input, and its
causal scores ``sum_j w_j relu(q_j . k)`` wherever they are computed (for the
selection, for the indexer's loss, and their backward), from the program's
scopes."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.dsa", "index") or None  # never 0: no such scope
