"""Seconds in XLA's compiler or loading executables from the persistent cache
up to the end of the stats call, in the sparse-attention cell: the warm
call's share of ``setup_s``. Read as the mla/moe cell's ``seq.compile_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.compile_s").read
