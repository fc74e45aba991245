"""Streamed host→device feed executor — ONE streaming discipline in-tree.

Generalizes the double-buffered shipment loop that ALS grew privately
(``models/als.py _run_streamed``): an epoch is sliced into chunks, each
chunk is encoded on host (quantize/pack/slice), its ``device_put``s are
queued on the transfer stream, and the per-chunk compute program is
dispatched so it waits only on its *own* inputs — chunk k's compute runs
while chunk k+1 is still crossing the link. The same loop now feeds the
two-tower and seqrec trainers (per-step minibatch spans instead of a
staged epoch) and the ALS normal-equation accumulators.

Two scheduling modes:

- **queue-ahead** (``lookahead=0``, the ALS discipline): every chunk's
  ``device_put`` is issued up front — they drain in order on the
  transfer stream — then the chunk programs are chained. Right when all
  chunks together fit on device (ALS retains the wire chunks for its
  finalize program anyway).
- **double-buffered** (``lookahead=k``): at most ``k`` chunks are
  encoded/shipped ahead of the chunk whose compute the host last
  synced, bounding device residency to ~``k+1`` chunks — the training
  feed, where the whole epoch deliberately does NOT fit under
  ``PIO_TPU_DEVICE_BUDGET_BYTES``. The host blocks on chunk
  ``i-lookahead``'s carry before shipping further, which keeps the pipe
  full (the next ``k`` chunks are already queued) without ever staging
  the epoch.

With a ``stats`` dict the phases are *serialized* (encode all → ship
all + block → dispatch all + block) so each is measurable — overlap
off, exactly ALS's profiling contract: ``h2d_s`` (transfer),
``device_s`` (compute), the encode time under ``encode_stat_key``
(ALS maps it onto its ``pack_s``), plus ``h2d_bytes``. Overlap itself
is proven by comparing a profiled run's ``h2d_s + device_s`` against an
overlapped run's wall time — :func:`record_overlap_ratio` computes the
ratio and publishes the gauge. With an active trainwatch recorder
(a real ``pio train``), overlapped runs self-measure: chunk 0 runs
phase-serialized as a probe (extra blocks only — the math stays
bit-exact) and the remaining chunks' wall time yields the ratio, so
``pio_tpu_train_stream_overlap_ratio`` reports from real runs, not
just bench.

Failpoints: ``stream.encode`` / ``stream.put`` / ``stream.dispatch``
fire per chunk per phase (fault-injection surface for the feed loop).
The same three names, ``stream.init`` (the initial carry) and
``stream.finalize``, are leaf host spans
(:func:`pio_tpu.obs.active_span`): they tile the loop, land on the
active trace, on the process timeline and, in a JAX profiler trace, on
the Python thread's line, where they name the device's idle gaps. The
once-per-run extra shipment is ``stream.put_extra``; a ``stats`` run's
two waits for the device stand there and in ``stream.finalize``.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, ContextManager, Optional, Sequence

from pio_tpu.obs import REGISTRY
from pio_tpu.utils import knobs

#: host→device bytes shipped by the streamed training feed (all
#: stream_feed callers: two-tower/seqrec batch spans, ALS wire chunks)
_H2D_BYTES = REGISTRY.counter(
    "pio_tpu_train_h2d_bytes_total",
    "Host-to-device bytes shipped by the streamed training feed",
)

#: transfer time hidden behind compute, from the last profiled pair
_OVERLAP = REGISTRY.gauge(
    "pio_tpu_train_stream_overlap_ratio",
    "Fraction of streamed-feed transfer time hidden behind compute "
    "(profiled h2d_s + device_s vs overlapped wall time)",
)


def n_stream_chunks(n_bytes: int, env_var: str, default: str = "8",
                    cap: int = 8) -> int:
    """Chunk count for a streamed host→device shipment: ``ceil(bytes /
    chunk_mb)`` capped at ``cap``; 1 (streaming off) when the env knob
    is ≤ 0. THE sizing rule for every streamed wire (ALS edges, logreg
    features, training batch spans) so the threshold semantics can't
    drift — ``utils.numutil.n_stream_chunks`` delegates here.

    Registered knobs take their default from the canonical registry
    (``pio_tpu.utils.knobs``); ``default`` applies only to scratch env
    names tests invent."""
    mb = knobs.knob_float(env_var, fallback=float(default))
    if mb <= 0:
        return 1
    return int(min(cap, -(-n_bytes // max(1, int(mb * 2 ** 20)))))


def span_bounds(n_batches: int, n_stream: int) -> list:
    """``n_stream`` near-even contiguous span boundaries over an epoch
    of ``n_batches`` batches (``n_stream`` ≤ ``n_batches`` — strictly
    increasing by construction)."""
    n_stream = max(1, min(n_batches, n_stream))
    return [n_batches * c // n_stream for c in range(n_stream + 1)]


def epoch_spans(step0: int, n_steps: int, n_batches: int,
                bounds: Sequence[int]) -> list:
    """Batch spans covering steps ``[step0, step0 + n_steps)`` of a
    wrapped epoch schedule (step ``s`` consumes batch ``s % n_batches``)
    as ``(b0, b1)`` ranges — each a contiguous run of batches inside one
    span of ``bounds``, clipped to the step range per epoch pass. The
    streamed feed replays EXACTLY the staged batch order, which is what
    makes streamed-vs-staged training parity bit-exact."""
    import bisect

    work = []
    s, end = step0, step0 + n_steps
    while s < end:
        base = (s // n_batches) * n_batches
        b0 = s - base
        c = bisect.bisect_right(bounds, b0) - 1
        b1 = min(bounds[c + 1], end - base)
        work.append((b0, b1))
        s = base + b1
    return work


def _tree_nbytes(tree: Any) -> int:
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def stream_feed(
    chunks: Sequence[Any],
    *,
    encode: Callable[[Any], Any],
    dispatch: Callable[[Any, Any, int], Any],
    init_carry: Callable[[], Any],
    put: Optional[Callable[[Any, int], Any]] = None,
    put_extra: Optional[Callable[[], Any]] = None,
    finalize: Optional[Callable[[Any, tuple], Any]] = None,
    lookahead: int = 0,
    stats: Optional[dict] = None,
    encode_stat_key: str = "encode_s",
    device_phase: Optional[ContextManager] = None,
) -> Any:
    """Run the streamed feed over ``chunks``; returns the final carry
    (or ``finalize``'s result).

    Args:
        chunks: opaque per-chunk descriptors (span bounds, slices, …).
        encode: ``chunk → host pytree`` — host-side slice/quantize/pack.
        dispatch: ``(carry, device_chunk, idx) → carry`` — the chunk's
            compute program; must not block (async dispatch is the
            overlap).
        init_carry: builds the initial carry at dispatch-phase start
            (inside ``device_s`` when profiling — ALS's ``init(seed)``).
        put: ``(host_pytree, idx) → device pytree``; default is a
            tree-mapped ``jax.device_put``. Callers supply sharded puts
            (``NamedSharding`` over batch axes) here — the "per-shard"
            in per-shard streaming.
        put_extra: optional once-per-run extra shipment (ALS's
            counts_u/counts_i), issued after every chunk put so it rides
            the same transfer-stream tail; timed inside ``h2d_s``.
        finalize: ``(carry, device_chunks) → result``. When present the
            device chunks are RETAINED and handed over (ALS re-decodes
            the wire for the item side); when absent each chunk is
            dropped right after its dispatch so streamed epochs never
            accumulate on device.
        lookahead: 0 → queue every put up front; k>0 → double-buffer,
            at most k chunks in flight ahead of synced compute.
        stats: phase-serialized profiling (see module docstring) —
            overlap is OFF while measuring.
        encode_stat_key: stats key the encode time accumulates under.
        device_phase: entered around the serialized device phase of a
            ``stats`` run, timing included (ALS's scope capture traces
            exactly what ``device_s`` times); unused without ``stats``.
    """
    import jax

    from pio_tpu.faults import failpoint
    from pio_tpu.obs import active_span, devicewatch, monotonic_s, trainwatch

    if put is None:
        def put(host, _idx):
            return jax.tree_util.tree_map(jax.device_put, host)

    def _encode(i):
        failpoint("stream.encode")
        with active_span("stream.encode"):
            return encode(chunks[i])

    shipped = [0]  # bytes shipped this call (overlap-probe bookkeeping)
    chunk_bytes: dict = {}  # in-flight chunk footprint (device ledger)

    def _put(host, i):
        failpoint("stream.put")
        nbytes = _tree_nbytes(host)
        _H2D_BYTES.inc(nbytes)
        shipped[0] += nbytes
        trainwatch.record_h2d(nbytes)
        chunk_bytes[i] = nbytes
        devicewatch.stream_carry(nbytes)
        if stats is not None:
            stats["h2d_bytes"] = stats.get("h2d_bytes", 0) + nbytes
        with active_span("stream.put"):
            return put(host, i)

    def _dispatch(carry, dev, i):
        failpoint("stream.dispatch")
        # compile attribution: a chunk whose leaf shapes are new to the
        # feed's program cache (typically the first chunk and a ragged
        # tail) pays the trace+compile inside this call
        with active_span("stream.dispatch"), devicewatch.compile_span(
            "stream_dispatch", key=devicewatch.shape_key(dev)
        ):
            out = dispatch(carry, dev, i)
        if not retain:
            # chunk consumed, device buffers released with the refs
            devicewatch.stream_carry(-chunk_bytes.pop(i, 0))
        return out

    def _init():
        with active_span("stream.init"):
            return init_carry()

    def _put_extra(wait_for=None):
        """The extra shipment; a ``stats`` run waits here for every put."""
        if put_extra is None and wait_for is None:
            return
        with active_span("stream.put_extra"):
            extra = put_extra() if put_extra is not None else None
            if wait_for is not None:
                jax.block_until_ready((wait_for, extra))

    def _finalize(carry, devs, wait=False):
        with active_span("stream.finalize"):
            result = finalize(carry, devs) if retain else carry
            if wait:
                jax.block_until_ready(result)
            return result

    n = len(chunks)
    retain = finalize is not None

    if stats is not None:
        # serialized phases: host encode cost must not pollute the
        # transfer measurement, so every chunk encodes first
        t0 = monotonic_s()
        encoded = [_encode(i) for i in range(n)]
        stats[encode_stat_key] = stats.get(encode_stat_key, 0.0) + (
            monotonic_s() - t0
        )
        t0 = monotonic_s()
        devs = [_put(encoded[i], i) for i in range(n)]
        _put_extra(wait_for=devs)
        stats["h2d_s"] = stats.get("h2d_s", 0.0) + (monotonic_s() - t0)
        with device_phase or contextlib.nullcontext():
            t0 = monotonic_s()
            carry = _init()
            for i in range(n):
                carry = _dispatch(carry, devs[i], i)
                if not retain:
                    devs[i] = None
            result = _finalize(carry, tuple(devs), wait=True)
            stats["device_s"] = stats.get("device_s", 0.0) + (
                monotonic_s() - t0
            )
        if chunk_bytes:  # retained chunks freed with finalize's result
            devicewatch.stream_carry(-sum(chunk_bytes.values()))
            chunk_bytes.clear()
        return result

    # overlapped: puts drain on the transfer stream while earlier
    # chunks' (async-dispatched) programs compute
    window = n if lookahead <= 0 else lookahead
    devs: dict = {}
    put_idx = 0
    extra_done = put_extra is None
    synced: list = []  # per-chunk carry leaf, for lookahead throttling
    carry = _init()
    probe = None
    start = 0
    rec = trainwatch.active_recorder()
    if rec is not None and lookahead > 0 and n >= 3:
        # overlap probe for REAL runs (the ISSUE-14 proof lived only in
        # bench's profiled/overlapped pair): chunk 0 runs phase-
        # serialized — extra blocks only, bit-exact math — to sample its
        # transfer and compute costs; the remaining chunks run
        # overlapped under a wall clock, and the serialized pair scales
        # by shipped bytes to estimate how much transfer hid.
        host0 = _encode(0)
        bytes0 = _tree_nbytes(host0)
        t0 = monotonic_s()
        devs[0] = _put(host0, 0)
        jax.block_until_ready(devs[0])
        h2d_s0 = monotonic_s() - t0
        t0 = monotonic_s()
        carry = _dispatch(carry, devs[0], 0)
        jax.block_until_ready(jax.tree_util.tree_leaves(carry)[:1])
        device_s0 = monotonic_s() - t0
        if not retain:
            del devs[0]
        put_idx = 1
        start = 1
        synced.append(None)  # chunk 0 already synced
        probe = (bytes0, h2d_s0, device_s0, monotonic_s())
    for i in range(start, n):
        while put_idx < min(n, i + window):
            devs[put_idx] = _put(_encode(put_idx), put_idx)
            put_idx += 1
        if put_idx == n and not extra_done:
            _put_extra()
            extra_done = True
        carry = _dispatch(carry, devs[i], i)
        if not retain:
            del devs[i]
        if lookahead > 0:
            # bound device residency: before shipping chunk i+window,
            # chunk i-lookahead's compute must be done (its carry is
            # ready). The next `lookahead` chunks are already queued,
            # so the device never starves while the host waits here.
            synced.append(jax.tree_util.tree_leaves(carry)[:1])
            j = i - lookahead
            if j >= 0 and synced[j] is not None:
                jax.block_until_ready(synced[j])
                synced[j] = None
    if not extra_done:
        _put_extra()
    if probe is not None:
        jax.block_until_ready(jax.tree_util.tree_leaves(carry)[:1])
        bytes0, h2d_s0, device_s0, t_rest = probe
        wall_rest = monotonic_s() - t_rest
        bytes_rest = shipped[0] - bytes0
        if bytes0 > 0 and bytes_rest > 0:
            scale = bytes_rest / bytes0
            ratio = record_overlap_ratio(
                h2d_s0 * scale, device_s0 * scale, wall_rest
            )
            rec.set_overlap(ratio)
    result = _finalize(carry, tuple(devs[i] for i in range(n))) if retain \
        else carry
    if chunk_bytes:  # retained chunks freed with finalize's result
        devicewatch.stream_carry(-sum(chunk_bytes.values()))
        chunk_bytes.clear()
    return result


def record_overlap_ratio(h2d_s: float, device_s: float,
                         wall_s: float) -> float:
    """Overlap achieved by a (profiled, overlapped) run pair: the
    fraction of the smaller phase hidden inside the larger one —
    ``(h2d_s + device_s - wall_s) / min(h2d_s, device_s)`` clamped to
    [0, 1]. Publishes ``pio_tpu_train_stream_overlap_ratio``."""
    lo = min(h2d_s, device_s)
    ratio = 0.0 if lo <= 0 else max(
        0.0, min(1.0, (h2d_s + device_s - wall_s) / lo)
    )
    _OVERLAP.set(ratio)
    return ratio
