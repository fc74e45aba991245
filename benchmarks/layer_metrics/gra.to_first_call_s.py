"""Seconds from the first ``ComputeContext`` to the first train call (the
data, the algorithm, its ``PreparedData``), in the cell without experts. Read as the accepted
``setup.to_first_call_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.to_first_call_s").read
