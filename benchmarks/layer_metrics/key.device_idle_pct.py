"""Share of the traced call in which no operation ran on the device, in the
sparse-attention cell. Read as the mla/moe cell's ``seq.device_idle_pct``."""

from run import load_module

read = load_module("layer_metrics", "seq.device_idle_pct").read
