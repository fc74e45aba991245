"""Share of the roofline of the window layers' attention: the least time the
chip could take for the pairs the window shows (``laguna_cost``:
``sum_t min(t + 1, window)`` a head and row; the masked halves of the two
tiles a query block visits are the implementation's cost, not the model's),
over the device seconds of ``seq.gqa/attn/window``."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_attn_window", "seq.gqa", "attn", "window")
