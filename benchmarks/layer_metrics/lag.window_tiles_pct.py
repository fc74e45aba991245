"""Key tiles the window layers' attention visited, as a share of the tiles a
causal layer of the same length visits: the program's counters
``window_tiles`` over ``causal_tiles``, which ``ring.attention_partial`` sums
from the bounds it hands its loops (forward; the backward walks the same
bounds). 63 of 528 at 16,384 positions, blocks and window of 512: 11.93. It
reads 100 once the skip is a mask. ``None`` without the counters."""


def read(reading):
    counters = reading["stats"].get("counters") or {}
    visited, causal = counters.get("window_tiles"), counters.get("causal_tiles")
    if visited is None or not causal:
        return None
    return 100.0 * visited / causal
