"""Share of the traced call in which no operation ran on the device."""


def read(reading):
    trace = reading.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
