"""Device seconds of one call's shared experts (``seq.ffn``) in the cell of
single mixers: four relu2 experts of width 3,712. Read as the mla/moe cell's
``seq.ffn_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.ffn_s").read
