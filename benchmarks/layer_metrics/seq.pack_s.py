"""Host seconds of packing the histories into rows, targets and masks
before the first step (the program's own ``seq.pack`` phase of the stats
call)."""


def read(reading):
    return reading["stats"].get("pack_s") if reading.get("trace") else None
