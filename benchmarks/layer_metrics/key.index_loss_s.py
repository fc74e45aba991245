"""Device seconds of one call's indexer loss in the sparse-attention cell
(``seq.dsa/kl``): the target (the main attention's probabilities over the
selection, summed over its 32 heads, recomputed from q, k and the tiles'
log-sum-exp), the KL against the indexer's softmax and its gradient, forward
and backward, from the program's scopes."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.dsa", "kl") or None  # never 0: no such scope
