"""Share of the roofline of the trainer's device programs in the
sparse-attention cell: the least time the chip could take for one call's
work (``keye_cost``) over the summed device time of the programs the
configuration names (``device_programs``). Read as the mla/moe cell's."""

from run import load_module

read = load_module("layer_metrics", "seq_program_roofline").read
