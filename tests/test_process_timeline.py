"""The process timeline (PR 37): marks, numbered train calls and leaf spans
from the OS's start of the process, JAX's compile path whole and by program,
and what a ``stats`` call reports of both."""

import json

import numpy as np
import pytest

from pio_tpu.obs import active_span, devicewatch, monotonic_s, tracing, trainwatch

MARKS = ["process_start", "pio_tpu_imported", "context_built",
         "first_call_enter", "first_call_exit"]


@pytest.fixture()
def timeline(monkeypatch):
    """A timeline of its own in the process's place: whichever test ran a
    trainer first in this worker, the first call here is call 1."""
    fresh = tracing.ProcessTimeline(monotonic_s(), "first_import")
    fresh.mark("pio_tpu_imported")
    monkeypatch.setattr(tracing, "PROCESS", fresh)
    return fresh


def edges(seed, nu=310, ni=190, ne=6100):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, nu, ne).astype(np.int32),
            rng.integers(0, ni, ne).astype(np.int32),
            (rng.integers(1, 11, ne) / 2).astype(np.float32), nu, ni)


def als_call(stats=None, rank=8):
    from pio_tpu.models import als
    from pio_tpu.parallel.context import ComputeContext

    als.train_als(ComputeContext.local(), *edges(5),
                  als.ALSConfig(rank=rank, iterations=2), stats=stats)


def seq_call(stats=None, steps=3):
    from pio_tpu.models import seqrec

    rows = np.random.default_rng(2).integers(1, 41, (16, 10)).astype(np.int32)
    seqrec.train_seqrec(None, rows, 40, seqrec.SeqRecConfig(
        d_model=16, n_heads=2, n_layers=1, ffn=24, max_len=10, steps=steps,
        batch_size=8), stats=stats)


def als_streamed(monkeypatch):
    monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.016")
    return als_call


TRAINERS = {"als": lambda mp: als_call, "als_streamed": als_streamed,
            "seqrec": lambda mp: seq_call}


# -- the origin and the marks -------------------------------------------------

def test_the_origin_is_the_os_start_of_the_process():
    import pio_tpu

    age = tracing._process_age_s()
    assert age is not None and 0.0 < age < 24 * 3600.0
    record = tracing.PROCESS.record()
    assert record["origin"] == "proc_stat"
    marks = record["marks"]
    assert marks["process_start"] == 0.0
    # the interpreter started before the package was imported, and not long
    assert 0.0 < marks["pio_tpu_imported"] < age
    assert marks["pio_tpu_imported"] == pytest.approx(
        pio_tpu.IMPORTED_AT - tracing.PROCESS.origin)


def test_without_proc_the_origin_is_the_first_import(monkeypatch):
    import pio_tpu

    monkeypatch.setattr(tracing, "_process_age_s", lambda: None)
    made = tracing._process_timeline()
    assert made.origin == pio_tpu.IMPORTED_AT
    assert made.record()["origin"] == "first_import"
    assert made.record()["marks"] == {"process_start": 0.0,
                                      "pio_tpu_imported": 0.0}


def test_the_marks_are_set_once_and_in_order(timeline):
    from pio_tpu.parallel.context import ComputeContext

    ComputeContext.local()
    built = timeline.record()["marks"]["context_built"]
    ComputeContext.local()
    als_call()
    first = dict(timeline.record()["marks"])
    assert list(first) == MARKS
    assert [first[m] for m in MARKS] == sorted(first[m] for m in MARKS)
    assert first["context_built"] == built
    als_call()
    seq_call()
    assert timeline.record()["marks"] == first


# -- the calls ----------------------------------------------------------------

def test_the_first_call_is_frozen_and_the_calls_are_the_first_four_and_the_newest(
        timeline):
    from pio_tpu.parallel.context import ComputeContext

    ComputeContext.local()
    assert timeline.record()["first_call"] is None
    als_call(rank=6)  # a rank of its own: this call compiles
    before = json.dumps(timeline.record()["first_call"], sort_keys=True)
    first = json.loads(before)
    assert first["spans"] and {row[3] for row in first["spans"]} == {1}
    assert first["spans_dropped"] == 0
    assert first["xla"]["traces"] >= 1 and first["xla"]["lowers"] >= 1
    assert first["xla"]["compiles"] + first["xla"]["cache_loads"] >= 1
    for _ in range(3):
        als_call(rank=6)
    seq_call()
    stats = {}
    seq_call(stats)
    record = stats["process"]
    assert json.dumps(record["first_call"], sort_keys=True) == before
    assert [c[0] for c in record["calls"]] == [1, 2, 3, 4, 6]
    for (_c, start, end), (_n, next_start, _e) in zip(record["calls"],
                                                     record["calls"][1:]):
        assert start < end <= next_start
    marks = record["marks"]
    assert record["calls"][0][1:] == [marks["first_call_enter"],
                                      marks["first_call_exit"]]
    # the record is the caller's: nothing it does to it reaches the timeline
    record["first_call"]["spans"].clear()
    assert json.dumps(timeline.record()["first_call"], sort_keys=True) == before


def test_a_call_that_raises_is_a_call_all_the_same(timeline):
    from pio_tpu.models import als
    from pio_tpu.parallel.context import ComputeContext

    empty = np.zeros(0, np.int32)
    with pytest.raises(ValueError):
        als.train_als(ComputeContext.local(), empty, empty,
                      empty.astype(np.float32), 3, 3)
    record = timeline.record()
    assert [c[0] for c in record["calls"]] == [1]
    assert "first_call_exit" in record["marks"]


@pytest.mark.parametrize("trainer", sorted(TRAINERS))
def test_a_calls_leaf_spans_tile_it_and_stats_are_read_off_them(
        timeline, monkeypatch, trainer):
    """First call (it compiles), a plain call, a ``stats`` call: the leaf
    spans sum to the call within 2% or 5 ms, none nested, and each phase
    of ``stats`` is its span's seconds."""
    call = TRAINERS[trainer](monkeypatch)
    stats = {}
    call(), call(), call(stats)
    prefix = "seq." if trainer == "seqrec" else ("als.", "stream.")
    for number, start, end in stats["process"]["calls"]:
        spans = sorted(timeline.spans(number), key=lambda row: row[1])
        assert spans and all(row[0].startswith(prefix) for row in spans)
        assert spans[0][1] >= start and spans[-1][2] <= end
        for a, b in zip(spans, spans[1:]):
            assert a[2] <= b[1], (a, b)  # leaves: none inside another
        covered = sum(row[2] - row[1] for row in spans)
        assert end - start - covered <= max(0.02 * (end - start), 0.005), (
            number, end - start, [(r[0], r[2] - r[1]) for r in spans])

    def seconds(*names):
        return sum(e - s for name, s, e, _c in timeline.spans(3)
                   if name in names)

    if trainer == "seqrec":
        names = [row[0] for row in timeline.spans(3)]
        assert names == ["seq.pack", "seq.build", "seq.init", "seq.init",
                         "seq.steps", "seq.readback"]
        assert stats["pack_s"] == pytest.approx(seconds("seq.pack"))
        first_init = next(r for r in timeline.spans(3) if r[0] == "seq.init")
        assert stats["place_s"] == pytest.approx(first_init[2] - first_init[1])
        assert stats["steps_s"] == pytest.approx(seconds("seq.steps"))
        assert stats["readback_s"] == pytest.approx(seconds("seq.readback"))
    elif trainer == "als":
        assert stats["pack_s"] == pytest.approx(seconds("als.sort"))
        assert stats["h2d_s"] == pytest.approx(seconds("als.put"))
        assert stats["device_s"] == pytest.approx(seconds("als.run"))
    else:  # the feed times its serialised phases over their spans
        assert stats["pack_s"] == pytest.approx(
            seconds("als.sort", "stream.encode"), abs=2e-3)
        assert stats["h2d_s"] == pytest.approx(
            seconds("stream.put", "stream.put_extra"), abs=2e-3)
        assert stats["device_s"] == pytest.approx(
            seconds("stream.init", "stream.dispatch", "stream.finalize"),
            abs=2e-3)
    json.dumps(stats)  # JSON-plain, the record included
    assert set(stats["process"]) == {"origin", "marks", "calls", "first_call",
                                     "later_spans", "xla_by_program"}
    assert {row[3] for row in stats["process"]["later_spans"]} == {2, 3}


# -- the buffer ---------------------------------------------------------------

def test_the_buffer_is_bounded_and_the_first_call_is_never_dropped():
    made = tracing.ProcessTimeline(monotonic_s(), "first_import", max_spans=256)

    def spans(n):
        for _ in range(n):
            span = tracing.Span("x.leaf", monotonic_s())
            span.end = monotonic_s()
            made.add_span(span)

    with made.train_call():
        spans(300)
    for _ in range(4):
        with made.train_call():
            spans(2500)
    spans(10)  # outside any call
    assert len(made.spans(1)) == 256
    assert made.record()["first_call"]["spans_dropped"] == 44
    assert len(made._first_spans) + len(made._spans) <= 512
    assert [c[0] for c in made.record()["calls"]] == [1, 2, 3, 4, 5]
    assert len(made.spans(5)) == 246 and len(made.spans(0)) == 10
    assert not made.spans(2)  # the ring moved on


def test_active_span_lands_on_the_timeline_without_a_trace(timeline):
    with active_span("als.sort") as span:
        pass
    assert span.seconds >= 0.0
    (name, start, end, call), = timeline.spans(0)
    assert (name, call) == ("als.sort", 0)
    assert end - start == pytest.approx(span.seconds)


# -- JAX's compile path -------------------------------------------------------

@pytest.fixture()
def compile_path(monkeypatch):
    """The listeners on, over totals and a table of this test's own: what
    the worker compiled before neither fills the table nor is lost."""
    from pio_tpu.parallel.context import ComputeContext

    ComputeContext.local()  # what registers the listeners
    monkeypatch.setattr(devicewatch, "_XLA_TOTALS",
                        dict.fromkeys(devicewatch._XLA_TOTALS, 0))
    monkeypatch.setattr(devicewatch, "_XLA_BY_PROGRAM", {})


def test_a_trace_and_a_lowering_are_counted_once_under_the_programs_name(
        compile_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner_of_pr37(x):
        return jnp.tanh(x) * 1.75

    @jax.jit
    def outer_of_pr37(x):
        return inner_of_pr37(x).sum() + inner_of_pr37(2 * x).sum()

    before = devicewatch.xla_totals()
    outer_of_pr37(jnp.ones(11)).block_until_ready()
    first = devicewatch.xla_totals()
    by_program = devicewatch.xla_by_program()
    outer, inner = by_program["jit_outer_of_pr37"], by_program[
        "jit_inner_of_pr37"]
    assert outer["n"] == 1 and inner["n"] >= 1
    assert outer["trace_s"] > 0 and outer["lower_s"] > 0
    assert outer["compile_s"] + outer["cache_load_s"] > 0
    # traced inside the outer, never lowered or compiled on its own
    assert inner["trace_s"] > 0 and inner["lower_s"] == 0.0
    assert inner["compile_s"] + inner["cache_load_s"] == 0.0
    assert first["lowers"] - before["lowers"] >= 1
    outer_of_pr37(jnp.ones(11)).block_until_ready()
    assert devicewatch.xla_totals() == first
    assert devicewatch.xla_by_program() == by_program
    for column in ("trace_s", "lower_s", "compile_s", "cache_load_s"):
        assert sum(row[column] for row in by_program.values()) == (
            pytest.approx(first[column]))


def test_a_step_is_charged_its_own_seconds_not_those_inside_it(compile_path):
    trace, lower = devicewatch._TRACE_EVENT, devicewatch._LOWER_EVENT
    before = devicewatch.xla_totals()
    devicewatch._on_xla_begin(trace, 0.0, fun_name="nest_outer")
    devicewatch._on_xla_begin(trace, 0.0, fun_name="nest_inner")
    devicewatch._on_xla_duration(trace, 2.0, fun_name="nest_inner")
    devicewatch._on_xla_begin(lower, 0.0, fun_name="jit(nest_eager)")
    devicewatch._on_xla_duration(lower, 1.0, fun_name="jit(nest_eager)")
    devicewatch._on_xla_duration(trace, 10.0, fun_name="nest_outer")
    after = devicewatch.xla_totals()
    assert after["traces"] == before["traces"] + 2
    assert after["trace_s"] == pytest.approx(before["trace_s"] + 9.0)
    assert after["lower_s"] == pytest.approx(before["lower_s"] + 1.0)
    rows = devicewatch.xla_by_program()
    assert rows["jit_nest_outer"]["trace_s"] == pytest.approx(7.0)
    assert rows["jit_nest_inner"]["trace_s"] == pytest.approx(2.0)
    assert rows["jit_nest_eager"]["lower_s"] == pytest.approx(1.0)


def test_the_table_of_programs_is_bounded_and_keeps_the_costly_names(
        compile_path):
    trace = devicewatch._TRACE_EVENT
    devicewatch._on_xla_duration(trace, 50.0, fun_name="costly_of_pr37")
    for i in range(3 * devicewatch.MAX_PROGRAMS):
        devicewatch._on_xla_duration(trace, 1e-6, fun_name=f"cheap_{i}")
    rows = devicewatch.xla_by_program()
    assert len(rows) <= devicewatch.MAX_PROGRAMS + 1
    assert rows["jit_costly_of_pr37"]["trace_s"] == pytest.approx(50.0)
    assert rows["(other)"]["n"] >= 2 * devicewatch.MAX_PROGRAMS
    assert sum(row["trace_s"] for row in rows.values()) == pytest.approx(
        devicewatch.xla_totals()["trace_s"])


# -- the operator's copy ------------------------------------------------------

def test_the_run_record_lifts_the_compile_paths_seconds():
    def row(run_id, xla):
        return trainwatch.run_record(
            run_id=run_id, engine_id="e", status="COMPLETED",
            train_seconds=30.0, phases={"read": 1.0}, params_hash="h", xla=xla)

    cached = {"traces": 400, "trace_s": 7.25, "lowers": 9, "lower_s": 3.5,
              "compiles": 0, "compile_s": 0.0, "cache_loads": 9,
              "cache_load_s": 4.125}
    warm = row("a", cached)
    assert (warm["xla_trace_s"], warm["xla_lower_s"], warm["xla_compile_s"],
            warm["xla_cache_load_s"]) == (7.25, 3.5, 0.0, 4.125)
    assert not [k for k in row("b", None) if k.startswith("xla_")]
    slower = row("c", dict(cached, trace_s=9.0))
    _lines, regressed = trainwatch.run_delta_table(warm, slower)
    assert regressed == ["xla_trace_s"]
