"""Host seconds of bringing the trained parameters back after the last step
(the program's own ``seq.readback`` phase of the stats call): the idle gap
after the last device operation."""


def read(reading):
    return reading["stats"].get("readback_s") if reading.get("trace") else None
