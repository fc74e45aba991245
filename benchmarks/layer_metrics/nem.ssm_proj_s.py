"""Device seconds of the Mamba-2 layers' two projections, ``in_proj`` with the
layer's norm and ``out_proj`` (``seq.ssm/proj``), forward and backward."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.ssm", "proj") or None  # never 0: no such scope
