"""Device seconds of one call's grouped expert matmuls themselves in the
sparse-attention cell (the Pallas kernel under ``seq.moe/experts/gmm``).
Read as the Nemotron cell's ``nem.moe_kernel_s``."""

from run import load_module

read = load_module("layer_metrics", "nem.moe_kernel_s").read
