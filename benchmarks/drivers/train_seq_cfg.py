"""Driver for the sequence template's training cells whose configuration
names its own yardstick: whole ``SeqRecAlgorithm.train`` calls back to back,
as ``drivers/train_seq.py`` runs them for the mla/moe cell, with the model
read from the configuration's ``harness`` block instead of this file:

- ``harness.reference``: the plain reference's module. ``model(config)`` makes
  the flat dict its layers read; ``GROUPS``, ``group_of(path, m)``, ``LOSSES``,
  ``TRACE_KEYS``, ``FAULTS`` and ``train(m, sequences, seed=, steps=, batch=,
  quantize=, fault=, on_step=)`` are what this driver and the readings use.
- ``harness.cost``: ``cost(m, rows, seq_len, steps, pairs)``: operations and
  bytes of one call, with ``kernels``: ``{name: {"flops", "bytes"}}``.
- ``harness.param_of``: that dict's key -> ``SeqRecParams`` field, and
  ``harness.params``: fields set outright (the block's kinds).

So a further configuration of the sequence template adds a configuration
file, a reference and a cost function, and no driver. The window, the trace,
the memory reading and ``correct`` are the mla/moe cell's (``train_seq.py``,
``train.py``); what those drivers export is used unchanged.

``correct``: every distinct call of the window (or the traced call) against
the reference trained on the same histories from the same weights: at the
first step, where both sides hold the same weights, ``loss.<name>``,
``grad.<group>`` and ``pairs``; ``update.<group>`` over the call;
``dropped_pairs``; the later steps' losses, ``grad.*`` and ``pairs`` reported
without a limit (from the second step on each side stands on a trajectory of
its own, and the differences are tail-heavy between seeds).
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
_seq = load_module("drivers", "train_seq")
sized, find_devices, peak_bytes = (_train.sized, _train.find_devices,
                                   _train.peak_bytes)
histories, flat_params, same_result, release_device = (
    _seq.histories, _seq.flat_params, _seq.same_result, _seq.release_device)
_sum_squares_diff = _seq._sum_squares_diff
BENCH = _train.BENCH


def reference_module(config: dict):
    return importlib.import_module(config["harness"]["reference"])


def algorithm_params(config: dict, m: dict, seed: int) -> dict:
    harness = config["harness"]
    params = {field: m[key] for key, field in harness["param_of"].items()}
    params.update(config["algorithm_params"])
    params.update(harness["params"], seed=seed)
    return params


def _moved(ref: dict, reference, m: dict) -> dict:
    """``{group: ||reference final - init||_F ** 2}``, once a reference."""
    if "_moved" not in ref:
        moved = dict.fromkeys(reference.GROUPS, 0.0)
        for path, final in ref["final"].items():
            moved[reference.group_of(path, m)] += _sum_squares_diff(
                final, ref["init"][path])
        ref["_moved"] = moved
    return ref["_moved"]


def compare_call(got, ref: dict, limits: dict, reference, m: dict,
                 reported=()) -> dict:
    """One call's numbers against the reference's, each beside its limit, as
    ``train_seq.compare_call`` reads them, over ``reference.GROUPS`` (the
    program's ``grad_norm`` columns stand in that order: a test holds the two
    lists equal) and ``reference.LOSSES``."""

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if a.shape != b.shape or not np.isfinite(a).all():
            return math.inf
        return float(np.max(np.abs(a - b) / np.where(b == 0, 1.0, np.abs(b))))

    values = {}

    def first_and_later(name, per_step):
        values[name] = per_step[0] if len(per_step) else math.inf
        if len(per_step) > 1:
            values["later." + name] = max(per_step[1:])

    trace = (got or {}).get("trace") or {}
    for loss in reference.LOSSES:
        ours, theirs = trace.get("l_" + loss, ()), ref["l_" + loss]
        first_and_later("loss." + loss, [
            rel(a, b) for a, b in zip(ours, theirs)
        ] if np.shape(ours) == np.shape(theirs) else ())
    pairs = np.asarray(trace.get("pairs", ()), np.float64)
    same = pairs.shape == ref["pairs"].shape and np.isfinite(pairs).all()
    first_and_later("pairs", (
        np.abs(pairs - ref["pairs"]).sum(axis=1)
        / np.maximum(ref["pairs"].sum(axis=1), 1.0)).tolist() if same else ())
    norms = np.asarray(trace.get("grad_norm", ()), np.float64)
    same = norms.shape == ref["grad_norm"].shape
    for col, group in enumerate(reference.GROUPS):
        if not ref["grad_norm"][:, col].any():
            continue  # a group the model does not have
        first_and_later(f"grad.{group}", [
            rel(a, b) for a, b in zip(norms[:, col], ref["grad_norm"][:, col])
        ] if same else ())
    dropped = trace.get("dropped")
    values["dropped_pairs"] = (math.inf if dropped is None
                               else float(np.sum(dropped)))
    if "final" in ref:  # a reference cut short has no final state to hold
        diff = dict.fromkeys(reference.GROUPS, 0.0)
        moved = _moved(ref, reference, m)
        params = flat_params((got or {}).get("params") or {})
        for path, final in ref["final"].items():
            group = reference.group_of(path, m)
            have = params.get(path)
            if have is None or have.shape != final.shape:
                diff[group] = math.inf
                continue
            d2 = _sum_squares_diff(have, final)
            diff[group] += d2 if math.isfinite(d2) else math.inf
        for group in reference.GROUPS:
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


class Job:
    """One cell's data, algorithm and context: what a window calls and what
    the reference trains on."""

    def __init__(self, config: dict, devices):
        from pio_tpu.parallel.context import ComputeContext, default_mesh

        self.config = config
        self.program = config["program"]
        self.module = importlib.import_module(self.program["module"])
        self.ctx = ComputeContext(mesh=default_mesh(devices=devices))
        self.ref_module = reference_module(config)
        self.model = self.ref_module.model(config)
        a = config["algorithm_params"]
        self.steps, self.batch = int(a["steps"]), int(a["batch_size"])
        self.seq_len = int(a["max_len"])
        self.edges_per_call = self.batch * self.seq_len * self.steps

    def set_seed(self, seed: int) -> None:
        from pio_tpu.controller.params import params_from_dict
        from pio_tpu.data.bimap import BiMap

        data, module, program = self.config["data"], self.module, self.program
        self.seed = int(seed) % (1 << 31)
        self.sequences = histories(data, seed)
        self.algo = getattr(module, program["algorithm"])(params_from_dict(
            getattr(module, program["params"]),
            algorithm_params(self.config, self.model, self.seed)))
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
        ``fault`` make the witness, the control and the planted faults."""
        return self.ref_module.train(
            self.model, self.sequences, seed=self.seed,
            steps=steps or self.steps, batch=self.batch, quantize=quantize,
            fault=fault, on_step=on_step)

    def compare(self, got, ref: dict, limits: dict, reported=()) -> dict:
        return compare_call(got, ref, limits, self.ref_module, self.model,
                            reported)


def run(*, cell, config, traffic, args, t_start, e2e, per_layer, load_reader,
        out_dir) -> dict:
    import compare
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
    # calls that handed back the same bits are held to the reference once,
    # and their parameters let go before the reference trains (train_seq.py)
    n_done, distinct = len(done), []
    for got in done:
        if not any(same_result(got, kept) for kept in distinct):
            distinct.append(got)
    done = got = None

    # the plain reference, after the window and the memory reading
    t_ref = time.monotonic()
    release_device()
    ref = job.reference()
    reported = dict.fromkeys(config.get("reported", {}), math.inf)
    per_call = [job.compare(got, ref, {**config["limits"], **reported})
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
            cost = importlib.import_module(config["harness"]["cost"]).cost(
                job.model, job.batch, job.seq_len, job.steps,
                float(ref["pairs"].sum()))
            reading["cost"] = cost
            reading["least"] = least_seconds(cost, peak)
            for name, kernel in cost["kernels"].items():
                reading["least_" + name] = least_seconds(kernel, peak)
            info["roof"] = reading["least"]["bound"]
            info["cost_share"] = cost["share"]
            # what a share of a peak or of a roof is computed from, beside
            # ``info.stats``: the call's operations, and the least seconds
            # of the call and of each kernel
            info["flops"] = cost["flops"]
            info["least_s"] = {k[len("least"):].lstrip("_") or "call":
                               v["seconds"] for k, v in reading.items()
                               if k.startswith("least")}
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
        trace_of_reference={k: ref[k].tolist()
                            for k in job.ref_module.TRACE_KEYS},
    )
    if args.rehearse:  # never under a device metric's name
        result["rehearsal"] = True
        result["metrics"] = {"rehearsal." + k: v for k, v in metrics.items()}
    result["info"] = info
    result["compared"] = compared
    return result
