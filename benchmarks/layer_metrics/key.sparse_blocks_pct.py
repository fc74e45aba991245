"""Key blocks the sparse layers' attention loops ran, as a share of those a
causal layer of the same length runs: the program's counters
``sparse_key_blocks`` over ``causal_key_blocks``, which
``ring.attention_partial`` sums from the bounds it hands its loops (a query
block's active key blocks: those in which some query of it selected a key).
``None`` without the counters."""


def read(reading):
    counters = reading["stats"].get("counters") or {}
    ran, causal = counters.get("sparse_key_blocks"), counters.get("causal_key_blocks")
    if ran is None or not causal:
        return None
    return 100.0 * ran / causal
