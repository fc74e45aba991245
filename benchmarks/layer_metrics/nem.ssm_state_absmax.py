"""Largest magnitude a Mamba-2 layer's carried state reached in the call
(the program's counter ``ssm_state_absmax``, float32, over every chunk
boundary, head, layer and step). The carried state is cast to the compute
dtype as a matmul operand against ``C``: bfloat16 keeps 8 bits of it, so a
reading that grows by orders of magnitude over a training run says the decay
``exp(dt A)`` no longer bounds the state and the chunked form is losing what
the recurrence keeps (``docs/templates.md``). ``None`` without the counter."""


def read(reading):
    counters = reading["stats"].get("counters") or {}
    return counters.get("ssm_state_absmax")
