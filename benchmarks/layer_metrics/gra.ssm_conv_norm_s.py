"""Device seconds of what stands around the recurrence in the Mamba-2 mixers
of the cell without experts: the causal depthwise convolution
(``seq.ssm/conv``) and the gate with its norm over all 4,096 channels
(``seq.ssm/norm``), forward and backward. Read as the Nemotron cell's
``nem.ssm_conv_norm_s``."""

from run import load_module

read = load_module("layer_metrics", "nem.ssm_conv_norm_s").read
