"""Share of the device's busy time in operations outside every ``als.``
scope (loop counters, copies of loop carries): how much of the device time
the scope metrics cannot name. ``None``, never 0, when the program reported
neither number."""


def read(reading):
    stats = reading["stats"]
    unscoped, busy = stats.get("device_unscoped_s"), stats.get("device_busy_s")
    if unscoped is None or not busy:
        return None
    return 100.0 * unscoped / busy
