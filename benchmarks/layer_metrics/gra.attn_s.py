"""Device seconds of one call's blocked causal attention in the one attention
mixer of the cell without experts (``seq.gqa/attn/full``: no position
encoding, four query heads of width 64 folded into a KV head's tile; XLA's
tiles, ``stats["attn_impl"]``), forward and backward. Read as the Nemotron
cell's ``nem.attn_s``."""

from run import load_module

read = load_module("layer_metrics", "nem.attn_s").read
