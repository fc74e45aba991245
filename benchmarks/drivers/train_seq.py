"""Driver for the sequence template's training cells: whole
``SeqRecAlgorithm.train`` calls back to back on a ``PreparedData`` of user
histories built in memory from ``--seed``.

The entry the window drives is the template's algorithm, what ``pio train``
calls, on a ``ComputeContext`` over the cell's chips. The event store,
``read`` and ``prepare`` are not in the window. One edge is one event of a
history consumed by an optimizer step: rows x events x steps a call.

``correct``: every call of the window (or the traced call) hands back its
model: the per-step losses and routed-pair counts it saw and its final
parameters. Once the window has closed, the peak memory has been read and
the calls' device state is gone (executables too), the plain reference
(``seq_reference``) trains on the same histories from the same weights, and
``compare_call`` holds each call to the limits in the configuration.

What the window and the trace share with the ALS driver (``sized``, the look
for a chip, ``peak_bytes``, the link rate) is that driver's own code.
"""

from __future__ import annotations

import functools
import importlib
import json
import math
import os
import shutil
import sys
import time
from unittest import mock

import numpy as np

from run import load_module

_train = load_module("drivers", "train")
sized, find_devices, peak_bytes = (_train.sized, _train.find_devices,
                                   _train.peak_bytes)
BENCH = _train.BENCH

#: the configuration's published keys -> ``SeqRecParams`` fields
PARAM_OF = {
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_hidden_layers": "n_layers", "intermediate_size": "ffn",
    "first_k_dense_replace": "dense_layers", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "n_routed_experts": "experts_held", "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_ffn", "n_shared_experts": "shared_experts",
    "routed_scaling_factor": "routed_scale",
    "num_nextn_predict_layers": "mtp_depth", "mtp_weight": "mtp_weight",
    "bias_update_rate": "bias_update_rate",
}


def reference_model(config: dict) -> dict:
    """The dict ``seq_reference`` reads: the published keys, the
    deployment's share, the assumed sizes and the ``init`` rule's numbers
    (the program's are its block's own constants; a test holds the file to
    them)."""
    m = {k: config[k] for k in PARAM_OF}
    m.update(
        vocab_size=config["vocab_size"],
        router_width=config["deployment"]["router_width"],
        experts_first=config["deployment"]["experts_first"],
        **{k: config["init"][k]
           for k in ("init_std", "embed_init_std", "bias_init_std")},
        learning_rate=config["algorithm_params"]["learning_rate"],
    )
    return m


def algorithm_params(config: dict, seed: int) -> dict:
    params = {field: config[key] for key, field in PARAM_OF.items()}
    params.update(config["algorithm_params"])
    params.update(
        attention_kind="mla", ffn_kind="moe",
        n_experts=config["deployment"]["router_width"],
        experts_first=config["deployment"]["experts_first"],
        seed=seed,
    )
    return params


def histories(data: dict, seed: int) -> np.ndarray:
    """``[n_histories, history_len]`` item ids 1..n_items, Zipf popularity
    (id = popularity rank), full rows, from ``seed``."""
    n_items = int(data["n_items"])
    p = np.arange(1, n_items + 1, dtype=np.float64) ** -float(data["zipf_exponent"])
    rng = np.random.default_rng(seed)
    ids = rng.choice(n_items, size=(int(data["n_histories"]),
                                    int(data["history_len"])), p=p / p.sum())
    return (ids + 1).astype(np.int32)


def flat_params(params: dict) -> dict:
    """The model's two-deep tree as ``{"group/name": array}``."""
    out = {}
    for group, value in params.items():
        if isinstance(value, dict):
            out.update({f"{group}/{k}": v for k, v in value.items()})
        else:
            out[group] = value
    return out


def _sum_squares_diff(a: np.ndarray, b: np.ndarray) -> float:
    """``sum((a - b) ** 2)`` of two float32 arrays, 4,096 entries at a
    time: float32 products summed in float64 across the blocks (numpy's own
    float32 sum of 1e8 squares is a hundredth off), no copy of a 400 MB leaf."""
    a, b, total, k = a.ravel(), b.ravel(), 0.0, 1 << 12
    for i in range(0, a.size, k):
        d = a[i:i + k] - b[i:i + k]
        total += float(np.dot(d, d))
    return total


def _moved(ref: dict) -> dict:
    """``{group: ||reference final - init||_F ** 2}``, computed once a
    reference and kept on it."""
    import seq_reference

    if "_moved" not in ref:
        moved = dict.fromkeys(seq_reference.GROUPS, 0.0)
        for path, final in ref["final"].items():
            moved[seq_reference.group_of(path)] += _sum_squares_diff(
                final, ref["init"][path])
        ref["_moved"] = moved
    return ref["_moved"]


def compare_call(got, ref: dict, limits: dict, reported=()) -> dict:
    """One call's numbers against the reference's, each beside its limit:
    ``{name: {"value", "limit"}}``. ``loss.*``: the largest relative
    difference over the steps. ``grad.<group>`` (relative difference of the
    group's gradient norm) and ``pairs`` (the expert layers' summed absolute
    differences in routed pairs over the reference's pairs of the step) are
    read at the first step, where both sides hold the same weights, and as
    ``later.grad.<group>`` / ``later.pairs``, the largest over the steps
    after it, where each side stands on its own trajectory. ``update.<group>``:
    relative Frobenius error of ``final - init``. ``got`` is ``{"trace",
    "params"}`` as the call handed them back, or ``None`` for a call that
    failed. A number named in ``reported`` is read and left out (the
    configuration says why no limit holds there); any other number without a
    limit raises."""
    import seq_reference

    def rel(a, b):
        """Largest difference relative to the reference's own size."""
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if a.shape != b.shape or not np.isfinite(a).all():
            return math.inf
        return float(np.max(np.abs(a - b) / np.where(b == 0, 1.0, np.abs(b))))

    values = {}

    def first_and_later(name, per_step):
        """A per-step number at the first step, and its largest after it."""
        values[name] = per_step[0] if len(per_step) else math.inf
        if len(per_step) > 1:
            values["later." + name] = max(per_step[1:])

    trace = (got or {}).get("trace") or {}
    values["loss.main"] = rel(trace.get("l_main", ()), ref["l_main"])
    values["loss.mtp"] = rel(trace.get("l_mtp", ()), ref["l_mtp"])
    # a layer none of whose held experts the tokens chose counts 0 or a
    # handful: the step's layers are taken together
    pairs = np.asarray(trace.get("pairs", ()), np.float64)
    same = pairs.shape == ref["pairs"].shape and np.isfinite(pairs).all()
    first_and_later("pairs", (
        np.abs(pairs - ref["pairs"]).sum(axis=1)
        / np.maximum(ref["pairs"].sum(axis=1), 1.0)).tolist() if same else ())
    norms = np.asarray(trace.get("grad_norm", ()), np.float64)
    same = norms.shape == ref["grad_norm"].shape
    for col, group in enumerate(seq_reference.GROUPS):
        if not ref["grad_norm"][:, col].any():
            continue  # a group the model does not have
        first_and_later(f"grad.{group}", [
            rel(a, b) for a, b in zip(norms[:, col], ref["grad_norm"][:, col])
        ] if same else ())
    dropped = trace.get("dropped")
    values["dropped_pairs"] = (math.inf if dropped is None
                               else float(np.sum(dropped)))
    if "final" in ref:  # a reference cut short has no final state to hold
        # a non-finite entry makes its group's sum non-finite
        diff = dict.fromkeys(seq_reference.GROUPS, 0.0)
        moved = _moved(ref)
        params = flat_params((got or {}).get("params") or {})
        for path, final in ref["final"].items():
            group = seq_reference.group_of(path)
            have = params.get(path)
            if have is None or have.shape != final.shape:
                diff[group] = math.inf
                continue
            d2 = _sum_squares_diff(have, final)
            diff[group] += d2 if math.isfinite(d2) else math.inf
        for group in seq_reference.GROUPS:
            if moved[group] > 0 or diff[group] > 0:
                values[f"update.{group}"] = math.sqrt(diff[group]) / max(
                    math.sqrt(moved[group]), 1e-30)
    compared = {}
    for key, value in values.items():
        if key in limits:
            compared[key] = {"value": value, "limit": float(limits[key])}
        elif key not in reported:
            raise KeyError(f"the configuration sets no limit for {key}")
    return compared


def same_result(a: dict, b: dict) -> bool:
    """Whether two calls handed back the same trace and parameters, bit for
    bit."""
    ta, tb = a["trace"] or {}, b["trace"] or {}
    pa, pb = flat_params(a["params"]), flat_params(b["params"])
    return (ta.keys() == tb.keys() and pa.keys() == pb.keys()
            and all(np.array_equal(ta[k], tb[k]) for k in ta)
            and all(np.array_equal(pa[k], pb[k]) for k in pa))


class Job:
    """One cell's data, algorithm and context: what a window calls and what
    the reference trains on."""

    def __init__(self, config: dict, devices):
        from pio_tpu.parallel.context import ComputeContext, default_mesh

        self.config = config
        self.program = config["program"]
        self.module = importlib.import_module(self.program["module"])
        self.ctx = ComputeContext(mesh=default_mesh(devices=devices))
        self.model = reference_model(config)
        a = config["algorithm_params"]
        self.steps, self.batch = int(a["steps"]), int(a["batch_size"])
        self.edges_per_call = self.batch * int(a["max_len"]) * self.steps

    def set_seed(self, seed: int) -> None:
        from pio_tpu.controller.params import params_from_dict
        from pio_tpu.data.bimap import BiMap

        data, module, program = self.config["data"], self.module, self.program
        self.seed = int(seed) % (1 << 31)
        self.sequences = histories(data, seed)
        self.algo = getattr(module, program["algorithm"])(params_from_dict(
            getattr(module, program["params"]),
            algorithm_params(self.config, self.seed)))
        self.prepared = getattr(module, program["prepared_data"])(
            item_index=BiMap({f"i{i}": i for i in range(int(data["n_items"]))}),
            sequences=self.sequences,
            user_rows={f"u{r}": r for r in range(len(self.sequences))},
        )

    def call(self):
        """One whole train call -> ``(seconds, {"trace", "params"} or None)``."""
        t = time.monotonic()
        try:
            model = self.algo.train(self.ctx, self.prepared).model
            got = {"trace": model.trace, "params": model.params}
        except Exception as e:  # a failed call is counted, not fatal
            print(f"train call raised: {e!r}", file=sys.stderr)
            return time.monotonic() - t, None
        return time.monotonic() - t, got

    def reference(self, quantize=None, fault=None, steps=None,
                  on_step=None) -> dict:
        """The plain reference's result for this seed; ``quantize`` and
        ``fault`` make the witness, the control and the planted faults out
        of it; ``on_step`` sees every step's state and gradients."""
        import seq_reference

        return seq_reference.train(
            self.model, self.sequences, seed=self.seed,
            steps=steps or self.steps, batch=self.batch, quantize=quantize,
            fault=fault, on_step=on_step)


def release_device() -> None:
    """Drop the program's executables (and the memory the runtime reserves
    for their temporaries) before the reference takes the chip."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()


def run(*, cell, config, traffic, args, t_start, e2e, per_layer, load_reader,
        out_dir) -> dict:
    import compare
    import seq_cost
    import trace_reduce
    from als_cost import least_seconds

    config = sized(config, args.rehearse)
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    import jax

    devices = find_devices(cell["chips"], args.rehearse)
    platform, kind = devices[0].platform, devices[0].device_kind
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if kind in peaks:
        peak = peaks[kind]
    elif args.rehearse:
        peak = None
    else:
        raise SystemExit(f"device kind {kind!r} is not in benchmarks/peaks.json")

    job = Job(config, devices)
    job.set_seed(args.seed)
    call, module, program = job.call, job.module, job.program

    warm_s, _ = call()  # compiles, or loads every program from the cache
    setup_s = time.monotonic() - t_start

    results, trace, stats, info = [], None, {}, {}
    if args.trace:
        shutil.rmtree(out_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(traffic["annotation"]):
                results.append(call())
        finally:
            jax.profiler.stop_trace()
        window_s = results[0][0]
        # the program's own phase times: one more call, phases serialised
        hook = getattr(module, program["stats_hook"])
        with mock.patch.object(module, program["stats_hook"],
                               functools.partial(hook, stats=stats)):
            call()
        info = {"host_cores": os.cpu_count(),
                "link_mb_s": _train.link_rate_mb_s()}
    else:
        t_window = time.monotonic()
        while True:
            results.append(call())
            window_s = time.monotonic() - t_window
            if window_s + results[-1][0] > args.seconds:
                break  # the next whole call would not fit
    memory_stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(peak_bytes(m) for m in memory_stats)

    if args.trace and platform == "tpu":
        trace = trace_reduce.reduce(trace_reduce.find_xplane(out_dir),
                                    traffic["annotation"])
    if args.trace:
        shutil.rmtree(out_dir, ignore_errors=True)  # traces are large

    attempted = len(results)
    done = [got for _s, got in results if got is not None]
    failed = attempted - len(done)
    last_trace = done[-1]["trace"] if done else None
    call_s = [s for s, _ in results]
    del results
    # The calls of a run share its seed and data. One that handed back, bit
    # for bit, what an earlier call did is held to the reference through that
    # call, and its 2.8 GB of parameters are let go before the reference
    # trains: the machine's 40 GiB do not hold five models, the reference's
    # weights and moments and its compiler at once.
    n_done, distinct = len(done), []
    for got in done:
        if not any(same_result(got, kept) for kept in distinct):
            distinct.append(got)
    done = got = None

    # the plain reference, after the window and the memory reading
    t_ref = time.monotonic()
    release_device()
    ref = job.reference()
    # what the configuration reports without a limit is read beside the rest
    # (a limit of infinity) and shown under ``info``
    reported = dict.fromkeys(config.get("reported", {}), math.inf)
    per_call = [compare_call(got, ref, {**config["limits"], **reported})
                for got in distinct]
    compared = compare.worst_of(per_call) if per_call else {
        "calls_completed": {"value": math.inf, "limit": 0.0}}
    not_limited = {k: compared.pop(k)["value"] for k in reported
                   if k in compared}
    correct = failed == 0 and all(
        c["value"] <= c["limit"] for c in compared.values())
    reference_s = time.monotonic() - t_ref

    metrics = {}
    if args.trace:
        reading = {
            "trace": trace, "stats": stats, "config": config, "peak": peak,
            "chips": cell["chips"], "memory_peak_bytes": memory_peak,
        }
        if peak is not None:
            cost = seq_cost.seq_cost(job.model, job.batch,
                                     int(config["algorithm_params"]["max_len"]),
                                     job.steps, float(ref["pairs"].sum()))
            reading["cost"] = cost
            reading["least"] = least_seconds(cost, peak)
            reading["least_attn"] = least_seconds(cost["attn"], peak)
            reading["least_experts"] = least_seconds(cost["experts"], peak)
            info["roof"] = reading["least"]["bound"]
            info["cost_share"] = cost["share"]
        for m in per_layer:
            value = load_reader(m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "train_edges_per_s": job.edges_per_call * n_done / window_s,
            "setup_s": setup_s,
        }
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        info["module_s"] = trace["module_s"]
        info["n_device_events"] = trace["n_device_events"]
    info.update(
        warm_call_s=warm_s, call_s=call_s, window_s=window_s,
        reference_s=reference_s, setup_s=setup_s, stats=stats,
        memory_stats=memory_stats[0], seed=args.seed, workload=cell["name"],
        distinct_calls=len(distinct), reported_without_limit=not_limited,
        trace_of_last_call=None if last_trace is None else {
            k: np.asarray(v).tolist() for k, v in last_trace.items()},
        # the reference's per-step numbers beside the call's: a line that
        # reads not correct says at which step, and a limit can be read anew
        trace_of_reference={k: ref[k].tolist() for k in (
            "l_main", "l_mtp", "pairs", "grad_norm")},
    )
    if args.rehearse:  # never under a device metric's name
        result["rehearsal"] = True
        result["metrics"] = {"rehearsal." + k: v for k, v in metrics.items()}
    result["info"] = info
    result["compared"] = compared
    return result
