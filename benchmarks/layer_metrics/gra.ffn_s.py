"""Device seconds of one call's dense SwiGLU mixers (``seq.ffn``) in the cell
without experts: ten MLP mixers of width 8,192, each a layer of its own with
its norm and residual, 2,048 tokens at a time. Read as the mla/moe cell's
``seq.ffn_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.ffn_s").read
