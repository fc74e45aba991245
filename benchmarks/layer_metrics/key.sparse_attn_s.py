"""Device seconds of one call's attention over the selected keys in the
sparse-attention cell (``seq.gqa/attn/sparse``), forward and backward: the
tiles of the key blocks some query of a block selected, masked by the
selection, from the program's scopes."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.gqa", "attn", "sparse") or None  # never 0: no such scope
