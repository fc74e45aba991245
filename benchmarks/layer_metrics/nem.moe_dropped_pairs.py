"""(token, held expert) pairs the call did not compute in the cell of single
mixers: the program's own counter over every step and expert layer; dropless
means 0. Read as the mla/moe cell's."""

from run import load_module

read = load_module("layer_metrics", "seq.moe_dropped_pairs").read
