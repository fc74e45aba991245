"""Programs compiled or loaded from the cache during the stats call in the
sparse-attention cell: the steady state is 0. Read as the mla/moe cell's
``seq.compiles_in_call``."""

from run import load_module

read = load_module("layer_metrics", "seq.compiles_in_call").read
