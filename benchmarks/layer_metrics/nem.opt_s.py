"""Device seconds of one call's Adam update, gradient norms and selection-bias
step (``seq.opt``) in the cell of single mixers. Read as the mla/moe cell's
``seq.opt_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.opt_s").read
