"""Device seconds of one call's latent-attention projections, norms and RoPE
(``seq.mla/proj``), the main model's layers, from the program's scopes."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.mla", "proj", mtp=False)
