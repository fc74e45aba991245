"""Share of the steps' device-busy time in operations outside every ``seq.``
scope (embedding gather, loop carries, batch slices). ``None``, never 0,
when the program reported neither number."""


def read(reading):
    stats = reading["stats"]
    unscoped, busy = stats.get("device_unscoped_s"), stats.get("device_busy_s")
    if unscoped is None or not busy:
        return None
    return 100.0 * unscoped / busy
