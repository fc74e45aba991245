"""The whole call's share of the chip's peak FLOP/s in the sparse-attention
cell: the operations the model needs for the traced call (``keye_cost``:
33.5 Tflop a step, the indexer's causal scores and the selected pairs
among them) over its wall time, host work, init, readback and idle gaps
included. Read as the mla/moe cell's."""

from run import load_module

read = load_module("layer_metrics", "seq.mfu_train").read
