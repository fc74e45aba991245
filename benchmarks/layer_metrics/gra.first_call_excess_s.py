"""The first train call less the second, in the cell without experts: what
the warm call costs beyond a call (twenty mixers, none scanned, traced,
lowered and compiled or loaded one by one). Read as the accepted
``setup.first_call_excess_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.first_call_excess_s").read
