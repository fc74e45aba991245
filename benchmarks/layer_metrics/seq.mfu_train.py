"""The whole call's share of the chip's peak FLOP/s: the operations the
model needs for the traced call (``seq_cost``) over its wall time, host work,
init, readback and idle gaps included."""


def read(reading):
    trace = reading.get("trace")
    if not trace or trace["window_s"] <= 0 or "cost" not in reading:
        return None
    peak = reading["peak"]["flops_per_s"] * reading["chips"]
    return 100.0 * reading["cost"]["flops"] / (trace["window_s"] * peak)
