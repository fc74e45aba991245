"""Peak bytes in use on the chip, reserved region included, read after the
window in the sparse-attention cell (659 M parameters at 16 B, 9.8 GiB,
and the step's temporaries). Read as the mla/moe cell's
``seq.device_peak_gib``."""

from run import load_module

read = load_module("layer_metrics", "seq.device_peak_gib").read
