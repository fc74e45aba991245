"""Device seconds of the Mamba-2 mixers' two projections, ``in_proj`` 2,048 x
12,608 with the mixer's norm and ``out_proj`` (``seq.ssm/proj``), forward and
backward, in the cell without experts. Read as the Nemotron cell's
``nem.ssm_proj_s``."""

from run import load_module

read = load_module("layer_metrics", "nem.ssm_proj_s").read
