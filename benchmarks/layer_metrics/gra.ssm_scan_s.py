"""Device seconds of one call's selective recurrence in the Mamba-2 mixers of
the cell without experts (``seq.ssm/ssd``): nine layers, one B/C group, chunks
of 256, the heads mapped a block at a time; forward and backward. Read as the
Nemotron cell's ``nem.ssm_scan_s``."""

from run import load_module

read = load_module("layer_metrics", "nem.ssm_scan_s").read
