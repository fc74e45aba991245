"""Device seconds of one call's grouped expert matmuls themselves in the
mla/moe cell, where ``jax.lax.ragged_dot`` runs them: XLA renames the kernel
``ragged-dot-none`` and drops its ``seq.moe/experts`` scope, so
``seq.moe_experts_s`` leaves it out. Read as ``nem.moe_kernel_s``."""

from run import load_module

read = load_module("layer_metrics", "nem.moe_kernel_s").read
