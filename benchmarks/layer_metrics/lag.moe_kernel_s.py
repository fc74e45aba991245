"""Device seconds of one call's grouped expert matmuls themselves in the
gqa/moe cell (``ragged-dot-none``, outside every scope: ``nem.moe_kernel_s``
says why), which ``lag.moe_experts_roofline``'s scope seconds leave out."""

from run import load_module

read = load_module("layer_metrics", "nem.moe_kernel_s").read
