"""Device seconds of one call's final norm, the logits read from the tied
table and divided by ``logits_scaling``, and the chunked cross-entropy
(``seq.head``) in the cell without experts. Read as the mla/moe cell's
``seq.head_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.head_s").read
