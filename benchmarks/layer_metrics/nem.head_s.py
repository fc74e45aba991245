"""Device seconds of one call's final norm, untied head and chunked
cross-entropy (``seq.head``) in the cell of single mixers. Read as the mla/moe cell's
``seq.head_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.head_s").read
