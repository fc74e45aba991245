"""Device seconds of one call's multi-token-prediction module, everything
under ``seq.mtp``: its projection, expert layer, head and loss."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.mtp", mtp=True)
