"""Share of the steps' device-busy time outside every ``seq.`` scope in the
sparse-attention cell: the embedding gather and its scatter back, the
residual adds, the layers' slices of their stacks. Read as the mla/moe
cell's ``seq.unscoped_pct``."""

from run import load_module

read = load_module("layer_metrics", "seq.unscoped_pct").read
