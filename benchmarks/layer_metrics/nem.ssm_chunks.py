"""Chunks the Mamba-2 layers' carrying loops ran in the call, forward: the
program's counter ``ssm_chunks``, which ``seq_layers.ssd_scan`` reads from the
length of the loop that carries the state from chunk to chunk (rows x chunks,
every mamba layer and step). ``nemotron_cost`` counts 4,096 for the cell: 128
chunks of 128 events, four layers, eight steps. ``None`` without the counter."""


def read(reading):
    counters = reading["stats"].get("counters") or {}
    return counters.get("ssm_chunks")
