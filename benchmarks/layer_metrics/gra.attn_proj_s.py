"""Device seconds of the attention mixer's four projections and its norm
(``seq.gqa/proj``: ``q`` 2,048 x 2,048, ``k`` and ``v`` 2,048 x 512, ``W_o``; no
rotation and no gate in this block), forward and backward, in the cell without
experts."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.gqa", "proj") or None  # never 0: no such scope
