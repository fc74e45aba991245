"""Turns of the chunked scan's map in the call, forward: the program's counter
``ssm_head_blocks``, which ``seq_layers.ssd_scan`` reads from the length of the
map over blocks of heads (every mamba mixer and step). With
``seq_layers.SSM_HEAD_BLOCK`` heads a turn the cell's one group of 64 heads
takes ``64 / SSM_HEAD_BLOCK`` turns a mixer: 288 a call at 16 (four turns, nine
mixers, eight steps; 576 at 8). ``None`` without the counter."""


def read(reading):
    counters = reading["stats"].get("counters") or {}
    return counters.get("ssm_head_blocks")
