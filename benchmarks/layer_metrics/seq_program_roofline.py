"""Share of the roofline of the sequence trainer's device programs: the
least time the chip could take for one call's work (``seq_cost``) over the
summed device time of the programs the configuration names
(``device_programs``) in the traced call."""


def read(reading):
    trace = reading.get("trace")
    if not trace or "least" not in reading:
        return None
    names = tuple(reading["config"]["device_programs"])
    took = sum(s for name, s in trace["module_s"].items() if name.startswith(names))
    if took <= 0:
        return None
    return 100.0 * reading["least"]["seconds"] / took
