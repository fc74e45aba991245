"""Device seconds of one call's Adam update and gradient norms (``seq.opt``)
in the sparse-attention cell. Read as the mla/moe cell's ``seq.opt_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.opt_s").read
