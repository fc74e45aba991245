"""Share of the roofline of the grouped matmuls of the two-matrix experts: the
least time the chip could take for the routed pairs the reference counted
(``nemotron_cost``), over the device seconds of ``seq.moe/experts`` (the
Pallas kernel's among them, under ``seq.moe/experts/gmm``) **and of a kernel
the TPU compiler renamed** and so took out of that scope (``ragged_dot``'s:
``nem.moe_kernel_s``): without it the time would leave out four fifths of
the work."""

from run import load_module
from seq_scopes import seconds

renamed = load_module("layer_metrics", "nem.moe_kernel_s").renamed


def read(reading):
    around = seconds(reading, "seq.moe", "experts")
    if not around or "least_moe_experts" not in reading:
        return None
    took = around + (renamed(reading) or 0.0)
    return 100.0 * reading["least_moe_experts"]["seconds"] / took
