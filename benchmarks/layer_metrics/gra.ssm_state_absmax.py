"""Largest magnitude a Mamba-2 mixer's carried state reached in the call, in
the cell without experts (the program's counter ``ssm_state_absmax``). Read as
the Nemotron cell's ``nem.ssm_state_absmax``."""

from run import load_module

read = load_module("layer_metrics", "nem.ssm_state_absmax").read
