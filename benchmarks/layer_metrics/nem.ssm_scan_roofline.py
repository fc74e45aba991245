"""Share of the roofline of the Mamba-2 layers' recurrence: the least time the
chip could take for it (``nemotron_cost``: the chunked form's operations at
the published chunk, ``x``, ``B``, ``C``, ``dt``, ``z`` read once and ``y``
written once, three times over a training step, whatever implements it) over
the device seconds of ``seq.ssm/ssd``."""

from seq_scopes import roofline_pct


def read(reading):
    return roofline_pct(reading, "least_ssm_scan", "seq.ssm", "ssd")
