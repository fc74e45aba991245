"""Seconds of the first train call of the process, the harness's warm call,
in the cell without experts: with the three ``gra.to_*`` it tiles
``setup_s`` plus the interpreter's own start. Read as the accepted
``setup.first_call_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.first_call_s").read
