"""Share of the steps' device-busy time outside every ``seq.`` scope in the
cell without experts: the embedding gather, its multiplier and its scatter
back, the mamba and attention mixers' scaled residual adds, the mixers' slices
of their stacks and their gradients' way back. Read as the mla/moe cell's
``seq.unscoped_pct``."""

from run import load_module

read = load_module("layer_metrics", "seq.unscoped_pct").read
