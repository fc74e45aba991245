"""The whole call's share of the chip's peak FLOP/s in the cell without
experts: the operations the model needs for the traced call (``granite_cost``:
39.7 Tflop a step) over its wall time, host work, init, readback and idle gaps
included. Read as the mla/moe cell's."""

from run import load_module

read = load_module("layer_metrics", "seq.mfu_train").read
