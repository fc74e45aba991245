"""Peak bytes in use on the chip, reserved region included, read after the
window in the cell of single mixers (the step stands within a GiB of what
the TPU compiler takes). Read as the mla/moe cell's
``seq.device_peak_gib``."""

from run import load_module

read = load_module("layer_metrics", "seq.device_peak_gib").read
