"""Device seconds of one call's final norm, untied head and chunked
cross-entropy of the main model (``seq.head``)."""

from seq_scopes import seconds


def read(reading):
    return seconds(reading, "seq.head", mtp=False)
