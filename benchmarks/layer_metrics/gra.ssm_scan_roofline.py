"""Share of the roofline of the Mamba-2 mixers' recurrence in the cell without
experts: the least time the chip could take for it (``granite_cost``: the
chunked form's operations at chunk 256 with ``C B^T`` once a group, ``x``,
``B``, ``C``, ``dt``, ``z`` read once and ``y`` written once, three times over a
training step, whatever implements it) over the device seconds of
``seq.ssm/ssd``. Read as the Nemotron cell's ``nem.ssm_scan_roofline``
(``least_ssm_scan``)."""

from run import load_module

read = load_module("layer_metrics", "nem.ssm_scan_roofline").read
