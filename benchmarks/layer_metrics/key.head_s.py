"""Device seconds of one call's final norm, logits over the 18,992 rows held
and chunked cross-entropy (``seq.head``) in the sparse-attention cell. Read
as the mla/moe cell's ``seq.head_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.head_s").read
