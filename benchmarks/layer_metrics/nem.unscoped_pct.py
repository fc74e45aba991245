"""Share of the steps' device-busy time outside every ``seq.`` scope in the
cell of single mixers: the embedding gather and its scatter back, residual
adds, the layers' slices of their stacks and their gradients' way back. Read as the mla/moe cell's
``seq.unscoped_pct``."""

from run import load_module

read = load_module("layer_metrics", "seq.unscoped_pct").read
