"""Peak bytes in use on the chip, reserved region included, read after the
window in the cell without experts (772 M parameters at 16 B, 11.5 GiB, and
the step's temporaries). Read as the mla/moe cell's
``seq.device_peak_gib``."""

from run import load_module

read = load_module("layer_metrics", "seq.device_peak_gib").read
