"""Share of the roofline of the indexer's causal scores: the least time the
chip could take for them, forward and backward (``keye_cost``: every earlier
key of every query, ``2 Hi di`` flop a pair, nothing recomputed), over the
device seconds of ``seq.dsa/index`` (its projections and every computation
of the scores included)."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_index", "seq.dsa", "index")
