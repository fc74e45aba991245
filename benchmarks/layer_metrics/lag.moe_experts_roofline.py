"""Share of the roofline of the grouped expert matmuls in the gqa/moe cell:
the least time the chip could take for the routed pairs the reference
counted (``laguna_cost``), over the device seconds of ``seq.moe/experts``.
The scope and the reading's key are the mla/moe cell's, so its reader reads
it; this cell's entry stands beside it because a metric's ``workloads`` are
its own."""

from run import load_module

read = load_module("layer_metrics", "seq.moe_experts_roofline").read
