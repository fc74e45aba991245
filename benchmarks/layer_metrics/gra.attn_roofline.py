"""Share of the roofline of the attention mixer's causal scores and ``P v``,
forward and backward (``granite_cost``: lower triangle, ``k`` and ``v`` once a
KV head, nothing recomputed), over the device seconds of ``seq.gqa/attn/full``
in the cell without experts. Read as the Nemotron cell's ``nem.attn_roofline``
(``least_attn``)."""

from run import load_module

read = load_module("layer_metrics", "nem.attn_roofline").read
