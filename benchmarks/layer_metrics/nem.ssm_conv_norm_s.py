"""Device seconds of what stands around the recurrence in the Mamba-2 layers:
the causal depthwise convolution (``seq.ssm/conv``) and the gate with its
group norm (``seq.ssm/norm``), forward and backward."""

from seq_scopes import seconds


def read(reading):
    conv = seconds(reading, "seq.ssm", "conv")
    if not conv:
        return None  # never 0: no such scope
    return conv + seconds(reading, "seq.ssm", "norm")
