"""Peak bytes in use on the fullest chip, read after the window."""


def read(reading):
    peak = reading.get("memory_peak_bytes")
    return None if not peak else peak / 2 ** 30
