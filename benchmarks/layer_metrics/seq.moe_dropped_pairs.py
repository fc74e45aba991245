"""(token, held expert) pairs the call did not compute: the program's own
counter over every step and expert layer; dropless means 0."""


def read(reading):
    counters = reading["stats"].get("counters")
    return None if not counters else counters.get("dropped_pairs")
