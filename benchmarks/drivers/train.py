"""Driver for offline-training cells: whole ``Algorithm.train`` calls back to
back on a ``PreparedData`` built in memory from ``--seed``.

The entry the window drives is the template's algorithm, what ``pio train``
calls as ``train.0_als``, on a ``ComputeContext`` over the cell's chips. The
event store, ``read`` and ``prepare`` are not in the window.

``correct``: every call of the window (or the traced call) hands back its
factor tables; once the window has closed, the peak memory has been read and
the calls' state is gone, the plain reference (``als_reference``) trains on
the same data from the same seed, and ``compare`` holds each table to the
limits in the configuration.
"""

from __future__ import annotations

import copy
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

NO_CHIP = 3  # exit code: no accelerator, or fewer chips than the cell asks
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sized(config: dict, rehearse: bool) -> dict:
    if not rehearse:
        return config
    out = copy.deepcopy(config)
    out.update(config["rehearse"])
    return out


def attr_path(obj, path: str):
    return functools.reduce(getattr, path.split("."), obj)


def link_rate_mb_s() -> float:
    """Host-to-device rate of one 32 MiB ``device_put`` (ROADMAP A0)."""
    import jax

    buf = np.ones(32 << 20, np.uint8)
    jax.device_put(buf[: 1 << 20]).block_until_ready()
    t = time.monotonic()
    jax.device_put(buf).block_until_ready()
    return 32.0 / (time.monotonic() - t)


class Job:
    """One cell's data, algorithm and context: what a window calls and what
    the reference trains on. The graph is made once; ``seed`` sets the
    ratings and the trainer's own seed."""

    def __init__(self, config: dict, devices):
        import datagen
        from pio_tpu.data.bimap import BiMap
        from pio_tpu.parallel.context import ComputeContext, default_mesh

        self.config = config
        self.data = config["data"]
        self.n_users = int(self.data["n_users"])
        self.n_items = int(self.data["n_items"])
        self.user_idx, self.item_idx = datagen.graph(self.data)
        self.program = config["program"]
        self.module = importlib.import_module(self.program["module"])
        self.index = (BiMap({f"u{u}": u for u in range(self.n_users)}),
                      BiMap({f"i{i}": i for i in range(self.n_items)}))
        self.ctx = ComputeContext(mesh=default_mesh(devices=devices))
        self.iterations = int(config["algorithm_params"]["num_iterations"])
        by_side = {"user": np.bincount(self.user_idx, minlength=self.n_users),
                   "item": np.bincount(self.item_idx, minlength=self.n_items)}
        self.degrees = {name: by_side[out["side"]]
                        for name, out in config["outputs"].items()}
        self.min_degree = int(config["rowmax_min_degree"])

    def set_seed(self, seed: int) -> None:
        import datagen
        from pio_tpu.controller.params import params_from_dict

        self.rating = datagen.ratings(self.user_idx, self.item_idx, self.data, seed)
        self.params = dict(self.config["algorithm_params"],
                           seed=int(seed) % (1 << 32))
        module, program = self.module, self.program
        self.algo = getattr(module, program["algorithm"])(
            params_from_dict(getattr(module, program["params"]), self.params))
        pd_args = [*self.index, self.user_idx, self.item_idx]
        if program["prepared_data_ratings"]:
            pd_args.append(self.rating)
        self.prepared = getattr(module, program["prepared_data"])(*pd_args)

    def call(self):
        """One whole train call -> ``(seconds, tables or None)``."""
        t = time.monotonic()
        try:
            model = self.algo.train(self.ctx, self.prepared)
            tables = {name: np.asarray(attr_path(model, out["path"]))
                      for name, out in self.config["outputs"].items()}
        except Exception as e:  # a failed call is counted, not fatal
            print(f"train call raised: {e!r}", file=sys.stderr)
            return time.monotonic() - t, None
        return time.monotonic() - t, tables

    def reference(self, quantize=None, iterations=None, keep=None) -> dict:
        """The plain reference's tables for this seed. ``quantize``,
        ``iterations`` and ``keep`` (a mask over the edges) make the control
        and the planted faults out of it."""
        import als_reference
        import compare

        ref = self.config["reference"]
        u, i, r = self.user_idx, self.item_idx, self.rating
        if keep is not None:
            u, i, r = u[keep], i[keep], r[keep]
        P, Q = als_reference.train(
            u, i, r, self.n_users, self.n_items,
            rank=int(self.params["rank"]),
            iterations=self.iterations if iterations is None else iterations,
            reg=float(self.params[ref["reg_key"]]),
            implicit=bool(ref["implicit"]), alpha=float(ref["alpha"]),
            seed=self.params["seed"], quantize=quantize,
        )
        tables = {"user_factors": P, "item_factors": Q,
                  "item_factors_normalized": compare.l2_normalize_rows(Q)}
        return {name: tables[name] for name in self.config["outputs"]}


def peak_bytes(stats: dict) -> int:
    """Peak bytes a chip held: the allocator's ``peak_bytes_in_use`` (arrays:
    arguments, outputs, donated carries) plus ``peak_bytes_reserved``, the
    region the TPU runtime reserves for the loaded programs' temporaries.
    Measured in PR 25: the trainer's arrays peak at 3.18 GB while
    ``compiled.memory_analysis()`` of its ``finalize`` program states 9.3 GB
    of temporaries, and ``peak_bytes_reserved`` reads 9.07 GB; polling
    ``bytes_in_use`` through a call never sees the temporaries."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(
        stats.get("peak_bytes_reserved", 0))


def find_devices(chips: int, rehearse: bool):
    """The cell's chips, or exit: no chip, no result."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not rehearse and (platform != "tpu" or len(devices) < chips):
        print(f"no result: found {len(devices)} {platform} device(s), the cell "
              f"needs {chips} TPU chip(s)", file=sys.stderr)
        sys.exit(NO_CHIP)
    return devices[:chips]


def run(*, cell, config, traffic, args, t_start, e2e, per_layer, load_reader,
        out_dir) -> dict:
    import als_cost
    import compare
    import trace_reduce

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
    n_users, n_items, params = job.n_users, job.n_items, job.params

    warm_s, _ = call()  # compiles, or loads every program from the cache
    setup_s = time.monotonic() - t_start

    results, trace, stats, info = [], None, {}, {}
    n_edges, iterations = len(job.user_idx), job.iterations
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
        info = {"host_cores": os.cpu_count(), "link_mb_s": link_rate_mb_s()}
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
    done = [tables for _s, tables in results if tables is not None]
    failed = attempted - len(done)

    # the plain reference, after the window and the memory reading
    t_ref = time.monotonic()
    ref_tables = job.reference()
    limits = config["limits"]
    if args.rehearse:  # tiny float32 runs agree to rounding; no chip limits
        limits = {f"{n}.{k}": 1e-3 for n in ref_tables for k in ("fro", "rowmax")}
    per_call = [compare.compare(t, ref_tables, limits, job.degrees,
                                job.min_degree)[1] for t in done]
    compared = compare.worst_of(per_call) if per_call else {
        "calls_completed": {"value": math.inf, "limit": 0.0}}
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
            reading["cost"] = als_cost.als_cost(
                n_edges, n_users, n_items, int(params["rank"]), iterations)
            reading["least"] = als_cost.least_seconds(reading["cost"], peak)
            info["roof"] = reading["least"]["bound"]
        for m in per_layer:
            value = load_reader(m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "train_edges_per_s": n_edges * iterations * len(done) / window_s,
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
        warm_call_s=warm_s, call_s=[s for s, _ in results], window_s=window_s,
        reference_s=reference_s, setup_s=setup_s, stats=stats,
        memory_stats=memory_stats[0],
        seed=args.seed, workload=cell["name"],
    )
    if args.rehearse:  # never under a device metric's name
        result["rehearsal"] = True
        result["metrics"] = {"rehearsal." + k: v for k, v in metrics.items()}
    result["info"] = info
    result["compared"] = compared
    return result
