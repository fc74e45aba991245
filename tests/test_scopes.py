"""The ALS trainer's names (PR 26): device scopes in the lowered programs,
leaf host spans, the scope reducer and its capture, real-compile totals."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from pio_tpu.obs import Tracer, active_span, devicewatch, profile, trainwatch

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "als_scoped_v5e")  # a profiler trace directory

NORMAL_EQ = ("als.normal_eq/gather", "als.normal_eq/outer",
             "als.normal_eq/segment_sum")
#: the issue's table, as scope paths: the same for every trainer
SCOPES = {"als.pack", "als.user/als.solve/cg",
            "als.item/als.solve/cg", "als.user/als.gram", "als.item/als.gram",
            *(f"als.{side}/{p}" for side in ("user", "item") for p in NORMAL_EQ)}


def tiny_edges(seed=0, nu=300, ni=200, ne=6000):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, nu, ne).astype(np.int32),
            rng.integers(0, ni, ne).astype(np.int32),
            (rng.integers(1, 11, ne) / 2).astype(np.float32), nu, ni)


def trainer_scope_paths(monkeypatch, stream_mb, ctx=None):
    """Train once at tiny size with every trainer program's HLO
    recorded; the scope paths of its ``op_name`` metadata."""
    import jax

    from pio_tpu.models import als
    from pio_tpu.parallel.context import ComputeContext

    texts = []

    def recording(fn):
        def call(*args):
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)
            # compiled, not just lowered: the call inliner is what joins a
            # loop body's names onto its caller's
            texts.append(fn.lower(*shapes).compile().as_text())
            return fn(*args)
        return call

    stream, mono = als._build_stream_trainer, als._build_trainer

    def build_stream(*a):
        init, accums, finalize = stream(*a)
        return recording(init), [recording(f) for f in accums], recording(finalize)

    monkeypatch.setattr(als, "_build_stream_trainer", build_stream)
    monkeypatch.setattr(als, "_build_trainer", lambda *a: recording(mono(*a)))
    monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", stream_mb)
    u, i, r, nu, ni = tiny_edges()
    config = als.ALSConfig(rank=8, iterations=2, solver="cg", implicit=True)
    als.train_als(ctx or ComputeContext.local(), u, i, r, nu, ni, config)
    names = set()
    for text in texts:
        names.update(re.findall(r'op_name="([^"]+)"', text))
    paths = {profile.scope_path(n, "als.") for n in names}
    return paths - {None}, len(texts)


@pytest.mark.parametrize("stream_mb,programs", [
    ("0.016", 5),  # init, three accums, finalize
    ("0", 1),
], ids=["streamed", "monolithic"])
def test_every_scope_of_the_table_is_in_the_lowered_trainer(
        monkeypatch, stream_mb, programs):
    paths, n_programs = trainer_scope_paths(monkeypatch, stream_mb)
    assert n_programs == programs
    assert SCOPES <= paths, sorted(SCOPES - paths)
    # nothing but the vocabulary: a path is made of als.* and the four children
    atoms = {seg for p in paths for seg in p.split("/")}
    assert atoms <= {"als.pack", "als.user", "als.item",
                     "als.normal_eq", "als.gram", "als.solve", "gather",
                     "outer", "segment_sum", "cg"}, atoms


def test_the_mesh_trainer_shares_the_scopes(monkeypatch):
    from pio_tpu.parallel.context import ComputeContext

    paths, _n = trainer_scope_paths(monkeypatch, "0", ComputeContext.create())
    assert SCOPES <= paths, sorted(SCOPES - paths)


@pytest.mark.parametrize("op_name,path", [
    ("jit(finalize)/als.item/als.solve/cg/while/body/closed_call/mul:",
     "als.item/als.solve/cg"),
    ("jit(accum)/als.user/als.normal_eq/while/body/closed_call/gather/gather:",
     "als.user/als.normal_eq/gather"),
    ("jit(accum)/als.user/als.normal_eq/while/body/closed_call/outer/"
     "cwk,cwl->ckl/dot_general", "als.user/als.normal_eq/outer"),
    ("jit(accum)/als.pack/jit(searchsorted)/vmap()/while/body/closed_call/gather",
     "als.pack"),
    ("jit(run_packed)/shard_map/als.user/als.solve/cond/branch_1_fun/add",
     "als.user/als.solve"),
    ("jit(finalize)/als.decode/jit(cumsum)/_make_math.<locals>.decode_items",
     "als.decode"),
    ("jit(finalize)/while/body/closed_call/add:", None),
    ("", None),
])
def test_scope_path(op_name, path):
    assert profile.scope_path(op_name, "als.") == path


@pytest.mark.parametrize("op_name, path", [
    # a backward pass: JAX wraps the outer scope whole and keeps the inner
    ("jit(chunk)/while/body/closed_call/transpose(jvp(seq.mtp))/jvp(seq.mtp)/"
     "checkpoint/seq.mla/proj/add_any", "seq.mtp/seq.mla/proj"),
    ("jit(chunk)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/rematted_computation/seq.ffn/dot_general",
     "seq.ffn"),
    ("jit(chunk)/while/body/closed_call/jvp()/while/body/closed_call/"
     "seq.mla/attn/closed_call/broadcast_in_dim", "seq.mla/attn"),
    ("jit(chunk)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/rematted_computation/cond/branch_0_fun/"
     "seq.moe/route/jit(_where)/broadcast_in_dim", "seq.moe/route"),
    ("jit(chunk)/while/body/closed_call/seq.opt/mul", "seq.opt"),
    ("jit(chunk)/while/body/closed_call/transpose(jvp())/add_any", None),
    # a jit's own name is no scope, wrapped or not
    ("jit(seq.thing)/mul", None),
])
def test_scope_path_through_a_backward_pass(op_name, path):
    assert profile.scope_path(op_name, "seq.") == path


# -- the reducer on a trace recorded on the v5e (PR 26) ------------------------

def test_reduce_scopes_on_the_recorded_v5e_trace():
    with open(os.path.join(RECORDED, "by_hand.json")) as f:
        by_hand = json.load(f)
    got = profile.reduce_scopes(RECORDED)
    total = sum(got["scope_s"].values()) + got["unscoped_s"]
    # self times tile the busy time: a ``while`` is not counted again for
    # its body, or the sum would be several times the busy time
    assert total == pytest.approx(got["busy_s"], rel=1e-6)
    assert by_hand["raw_durations_s"] > 1.1 * got["busy_s"]  # loops + bodies
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["busy_s"] == pytest.approx(by_hand["busy_s"], rel=1e-9)
    assert got["unscoped_s"] == pytest.approx(by_hand["unscoped_s"], rel=1e-9)
    assert sum(got["renamed_s"].values()) <= got["unscoped_s"]
    assert 100 * got["unscoped_s"] / got["busy_s"] == pytest.approx(
        by_hand["unscoped_pct"], rel=1e-6)
    for path, sec in by_hand["scope_s"].items():
        assert got["scope_s"][path] == pytest.approx(sec, rel=1e-9)
    assert set(got["program_s"]) == set(by_hand["program_s"])
    assert sum(got["program_s"].values()) >= got["busy_s"] * 0.99
    assert SCOPES <= set(got["scope_s"]), sorted(SCOPES - set(got["scope_s"]))


def test_a_loop_is_not_counted_again_for_its_body():
    events = [("while", 0.0, 10.0), ("body_a", 1.0, 3.0), ("body_b", 5.0, 4.0)]
    assert profile._self_seconds(events) == {
        "while": 3.0, "body_a": 3.0, "body_b": 4.0}


def test_an_operation_xla_renamed_is_unscoped_and_named(monkeypatch):
    """The TPU compiler rewrites ``jax.lax.ragged_dot`` into a custom call
    named ``ragged-dot-none``: no path, so no scope. Its seconds stay
    unscoped, and ``renamed_s`` says whose they are; an operation with no
    name at all (a copy XLA adds) or with a path outside every scope is
    unscoped only."""
    names = {1: "jit(step)/while/body/closed_call/seq.moe/experts/gather:",
             2: "ragged-dot-none:", 3: "", 4: "jit(step)/while/body/add:",
             5: "ragged-dot-none:"}
    ops = [(1, 0.0, 1.0), (2, 1.0, 2.0), (3, 3.0, 0.5), (4, 3.5, 0.25),
           (5, 3.75, 0.25)]
    monkeypatch.setattr(profile, "find_xplane", lambda d: d)
    monkeypatch.setattr(profile, "_device_planes",
                        lambda path: iter([(names, {}, ops, [])]))
    got = profile.reduce_scopes("anywhere", prefix="seq.")
    assert got["scope_s"] == {"seq.moe/experts": 1.0}
    assert got["unscoped_s"] == pytest.approx(3.0)
    assert got["renamed_s"] == {"ragged-dot-none": pytest.approx(2.25)}
    assert got["busy_s"] == pytest.approx(4.0)
    stats = profile.device_stats(got)
    assert stats["device_renamed_s"] == got["renamed_s"]
    del got["renamed_s"]  # a capture of before: the key is left out
    assert "device_renamed_s" not in profile.device_stats(got)


def test_reduce_scopes_needs_a_trace_with_a_device(tmp_path):
    with pytest.raises(FileNotFoundError):
        profile.reduce_scopes(str(tmp_path))


# -- the capture ------------------------------------------------------------

def test_the_capture_does_nothing_on_cpu(monkeypatch):
    import jax

    def no_session(*a, **k):
        raise AssertionError("no profiler session without a TPU")

    monkeypatch.setattr(jax.profiler, "start_trace", no_session)
    with profile.ScopeCapture() as cap:
        pass
    assert cap.result is None


def test_the_capture_steps_aside_for_a_running_session(monkeypatch, tmp_path):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.profiler.start_trace(str(tmp_path / "owner"))
    try:
        with profile.ScopeCapture() as cap:
            assert cap._dir is None  # nothing of its own to stop or remove
        assert cap.result is None
    finally:
        jax.profiler.stop_trace()  # still the owner's to stop: would raise
    assert os.path.isdir(tmp_path / "owner")


def test_a_failing_reduction_never_fails_the_block(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with profile.ScopeCapture() as cap:  # a CPU trace has no TPU plane
        kept = cap._dir
    assert cap.result is None and not os.path.exists(kept)


# -- leaf host spans ----------------------------------------------------------

@pytest.mark.parametrize("with_jax", [True, False])
def test_active_span_records_on_the_active_trace(monkeypatch, with_jax):
    import jax

    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    if not with_jax:
        monkeypatch.setitem(sys.modules, "jax", None)
    tracer = Tracer("scopes_test")
    with tracer.trace("train") as tr:
        with active_span("als.sort"):
            pass
    with active_span("als.readback"):  # no active trace: only the annotation
        pass
    spans = tracer.find(tr.trace_id)["spans"]
    assert [s["stage"] for s in spans] == ["als.sort"]
    assert entered == (["als.sort", "als.readback"] if with_jax else [])


def test_the_trainers_leaf_spans_tile_and_never_nest(monkeypatch):
    from pio_tpu.models import als
    from pio_tpu.parallel.context import ComputeContext

    monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.016")
    u, i, r, nu, ni = tiny_edges()
    tracer = Tracer("scopes_test")
    with tracer.trace("train") as tr:
        als.train_als(ComputeContext.local(), u, i, r, nu, ni,
                      als.ALSConfig(rank=8, iterations=2))
    spans = tracer.find(tr.trace_id)["spans"]
    stages = [s["stage"] for s in spans]
    assert stages[0] == "als.sort" and stages[-1] == "als.readback"
    assert stages[-2] == "stream.finalize"
    for name in ("stream.encode", "stream.put", "stream.dispatch"):
        assert stages.count(name) == 3  # one per chunk
    ordered = sorted(spans, key=lambda s: s["startMs"])
    for a, b in zip(ordered, ordered[1:]):
        assert a["startMs"] + a["durMs"] <= b["startMs"] + 0.002, (a, b)


# -- stats of a profiled call -------------------------------------------------

def test_stats_on_cpu_carry_compile_counts_and_no_device_number():
    from pio_tpu.models import als
    from pio_tpu.parallel.context import ComputeContext

    u, i, r, nu, ni = tiny_edges(seed=3)
    ctx, config = ComputeContext.local(), als.ALSConfig(rank=8, iterations=2)
    als.train_als(ctx, u, i, r, nu, ni, config)  # warm
    stats = {}
    als.train_als(ctx, u, i, r, nu, ni, config, stats=stats)
    assert not [k for k in stats if k.startswith("device_") and k != "device_s"]
    # device arrays where the warm call gave numpy miss jit's fast path, and
    # JAX reports the look-up of the kept jaxpr as a trace: microseconds
    in_call = stats["xla"]["in_call"]
    assert in_call.pop("traces") <= 1 and in_call.pop("trace_s") < 0.01
    assert in_call == {
        "lowers": 0, "lower_s": 0.0,
        "compiles": 0, "compile_s": 0.0, "cache_loads": 0, "cache_load_s": 0.0}
    json.dumps(stats)  # JSON-plain


def test_device_stats_sum_the_sides():
    from pio_tpu.models import als

    cap = profile.ScopeCapture()
    cap.result = {"window_s": 9.0, "busy_s": 8.0, "unscoped_s": 0.5,
                  "program_s": {"jit_finalize": 7.75},
                  "scope_s": {"als.user/als.solve/cg": 3.0,
                              "als.item/als.solve/cg": 2.0, "als.pack": 1.0,
                              "als.user": 0.5, "als.item/als.gram": 1.0}}
    stats = {}
    als._fill_device_stats(stats, cap, None)
    assert stats["device_scope_s"] == cap.result["scope_s"]
    assert stats["device_scope_summed_s"] == {
        "als.solve/cg": 5.0, "als.pack": 1.0, "als.user": 0.5, "als.gram": 1.0}
    assert stats["device_unscoped_s"] == 0.5 and stats["device_busy_s"] == 8.0
    assert stats["device_program_s"] == {"jit_finalize": 7.75}
    assert "xla" not in stats  # nothing was listening when the call began


def test_a_trace_without_scopes_is_called_a_stale_cache(caplog):
    from pio_tpu.models import als

    cap = profile.ScopeCapture()
    cap.result = {"window_s": 1.0, "busy_s": 1.0, "unscoped_s": 1.0,
                  "program_s": {}, "scope_s": {}}
    with caplog.at_level("WARNING", logger="pio_tpu.als"):
        als._fill_device_stats({}, cap, None)
    assert "JAX_COMPILATION_CACHE_DIR" in caplog.text


# -- real compiles ------------------------------------------------------------

def test_compile_totals_move_on_a_fresh_jit_and_stay_flat_on_a_repeat():
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel.context import ComputeContext

    ComputeContext.local()  # what registers the listener
    f = jax.jit(lambda x: jnp.tanh(x) * 3.25 + 1.5)
    before = devicewatch.xla_totals()
    f(jnp.ones(7)).block_until_ready()
    first = devicewatch.xla_totals()
    moved = (first["compiles"] - before["compiles"]
             + first["cache_loads"] - before["cache_loads"])
    assert moved >= 1
    assert (first["compile_s"] + first["cache_load_s"]
            > before["compile_s"] + before["cache_load_s"])
    f(jnp.ones(7)).block_until_ready()
    assert devicewatch.xla_totals() == first


def test_a_cache_load_is_not_counted_as_a_compile():
    from pio_tpu.parallel.context import ComputeContext

    ComputeContext.local()
    before = devicewatch.xla_totals()
    devicewatch._on_xla_duration(devicewatch._CACHE_RETRIEVAL_EVENT, 0.25)
    devicewatch._on_xla_duration(devicewatch._BACKEND_COMPILE_EVENT, 0.5)
    devicewatch._on_xla_duration(devicewatch._BACKEND_COMPILE_EVENT, 2.0)
    devicewatch._on_xla_duration("/jax/core/compile/jaxpr_trace_duration", 9.0)
    after = devicewatch.xla_totals()
    assert after["cache_loads"] == before["cache_loads"] + 1
    assert after["compiles"] == before["compiles"] + 1
    assert after["cache_load_s"] == pytest.approx(before["cache_load_s"] + 0.5)
    assert after["compile_s"] == pytest.approx(before["compile_s"] + 2.0)


def test_device_json_shows_the_totals():
    from pio_tpu.parallel.context import ComputeContext

    ComputeContext.local()
    payload = devicewatch.DeviceWatch(stats_fn=lambda: []).payload()
    assert set(payload["xla"]) == {"traces", "trace_s", "lowers", "lower_s",
                                   "compiles", "compile_s", "cache_loads",
                                   "cache_load_s"}


# -- the compile cache never hands a scoped program an unscoped executable ------

_CACHE_PROBE = """
import sys
from pio_tpu.utils.compile_cache import place_compile_cache
place_compile_cache()
import numpy as np
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from jax import monitoring
hits = []
monitoring.register_event_listener(
    lambda event, **kw: hits.append(event.endswith("/cache_hits")))
def solve(x):
    if sys.argv[1] == "scoped":
        with jax.named_scope("als.solve"):
            return jnp.sin(x) @ x
    return jnp.sin(x) @ x
x = np.ones((64, 64), np.float32)  # no helper program beside ``solve``
text = jax.jit(solve).lower(x).compile().as_text()
print("RESULT", "als.solve" in text, any(hits))
"""


def test_a_scoped_program_does_not_load_an_unscoped_executable(tmp_path):
    """JAX's default cache key leaves op metadata out, so the scoped
    trainer would find what an older version cached and run without its
    scopes; ``place_compile_cache`` keys the entries with metadata."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    for name in ("JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY",
                 "JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX"):
        env.pop(name, None)
    script = tmp_path / "probe.py"
    script.write_text(_CACHE_PROBE)
    got = []
    for variant in ("plain", "scoped", "scoped"):
        out = subprocess.run([sys.executable, str(script), variant], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        got.append(out.stdout.strip().splitlines()[-1])
    assert got == ["RESULT False False",  # compiled, cached without a scope
                   "RESULT True False",   # not found again under the new name
                   "RESULT True True"]    # found, and the scope is still there


# -- the run record -----------------------------------------------------------

def reduced(solve):
    return {"window_s": 20.0, "busy_s": 19.0, "unscoped_s": 0.5,
            "program_s": {}, "scope_s": {"als.item/als.solve/cg": solve,
                                         "als.pack": 0.5}}


def run_row(run_id, solve):
    return trainwatch.run_record(
        run_id=run_id, engine_id="e", status="COMPLETED", train_seconds=30.0,
        phases={"read": 1.0}, params_hash="h", device_scopes=reduced(solve))


def test_the_run_record_lifts_the_scope_seconds():
    row = run_row("a", 6.0)
    assert row["scope_als.item/als.solve/cg_s"] == 6.0
    assert row["scope_als.pack_s"] == 0.5
    assert row["device_busy_s"] == 19.0 and row["device_idle_pct"] == 5.0
    plain = trainwatch.run_record(
        run_id="b", engine_id="e", status="COMPLETED", train_seconds=30.0,
        phases={}, params_hash="h")
    assert not [k for k in plain if k.startswith(("scope_", "device_busy",
                                                  "device_idle"))]


def test_runs_diff_flags_a_solve_that_got_slower():
    lines, regressed = trainwatch.run_delta_table(run_row("a", 6.0),
                                                  run_row("b", 7.0))
    assert regressed == ["scope_als.item/als.solve/cg_s"]
    assert any("scope_als.pack_s" in line for line in lines)
