"""Device seconds of what stands around the scores in every attention layer:
the q, k, v and output projections with RoPE (``seq.gqa/proj``) and the
per-head gate (``seq.gqa/gate``), forward and backward."""

from seq_scopes import seconds


def read(reading):
    proj = seconds(reading, "seq.gqa", "proj")
    if not proj:
        return None  # never 0: no such scope
    return proj + seconds(reading, "seq.gqa", "gate")
