"""Device seconds of one call's grouped expert matmuls and their row gather
(``seq.moe/experts``) in the cell of single mixers. Read as the mla/moe cell's
``seq.moe_experts_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.moe_experts_s").read
