"""Host seconds of bringing the trained parameters back after the last step
(the stats call's ``seq.readback`` phase) in the sparse-attention cell: 2.6
GB a call. Read as the mla/moe cell's ``seq.readback_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.readback_s").read
