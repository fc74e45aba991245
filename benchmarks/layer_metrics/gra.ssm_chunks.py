"""Chunks the Mamba-2 mixers' carrying loops ran in the call, forward, in the
cell without experts (the program's counter ``ssm_chunks``). ``granite_cost``
counts 2,304: 32 chunks of 256 events, nine layers, eight steps. Read as the
Nemotron cell's ``nem.ssm_chunks``."""

from run import load_module

read = load_module("layer_metrics", "nem.ssm_chunks").read
