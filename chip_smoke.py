#!/usr/bin/env python3
"""The quickest proof that pio-tpu still starts on the chip.

Drives the README quickstart once, through the entry points a user calls,
with real processes and state flowing only through ``$PIO_TPU_HOME`` and
HTTP: ``pio app new`` → ``import`` → ``train`` → (process exits) →
``deploy`` → ``POST /queries.json`` → ``undeploy``. The model is the
recommendation template at the full width of the headline configuration
(162,541 users × 59,047 items, ``examples/recommendation/engine.json`` as
shipped); the events are synthetic, made from a seed. Further phases
run the device programs that lifecycle cannot reach: the streamed ALS
trainer on 25M edges, the two Pallas kernels under Mosaic (the
embedding bag, and the resident CG solve against the XLA loop) and the
factor-row gather from both table layouts.

This parent never imports jax. Each phase is one child process that owns
the chip and has exited before the next starts. The run fails unless every
device-using child reports ``platform == "tpu"``; a phase that raises
fails the run. ``--rehearse`` runs the same phases at a tiny size on CPU
(kernel in interpret mode) so the command can be debugged without chip
time; without it there is no CPU path.

The last two lines of stdout are JSON objects: the summary of every phase
(``{"ok": true, ..., "claim": null}``), then the result the driver reads,
with exactly these keys and the device as JAX reports it:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
A rehearsal carries ``"rehearsal": true`` in both. Exit code 0 only when
every phase passed; a run that failed prints neither line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ENGINE_JSON = os.path.join("examples", "recommendation", "engine.json")
APP = "myapp"  # the app_name engine.json ships with
SEED = 20260926

#: the whole run, compilation included, must fit the driver's 1200 s
BUDGET_S = 1150.0

FULL = {
    "n_users": 162_541, "n_items": 59_047, "n_events": 1_000_000,
    # 25M edges is the BASELINE headline; below ~4M the trainer takes the
    # monolithic program instead of the streamed one
    "stream_edges": 25_000_000,
    "kernel": {"V": 50_000, "D": 256, "B": 4096, "L": 64},
    "solve": {"n": 40_000, "K": 64},
    # the benchmark cell's two factor tables and one chunk's rows
    "gather": {"rows": [162_541, 59_047], "K": 64, "chunk": 4096, "W": 64,
               "steps": 64},
}
REHEARSAL = {
    "n_users": 400, "n_items": 150, "n_events": 5_000,
    "stream_edges": 20_000,
    "kernel": {"V": 512, "D": 128, "B": 16, "L": 8},
    "solve": {"n": 200, "K": 16},
    "gather": {"rows": [301, 150], "K": 16, "chunk": 8, "W": 4, "steps": 3},
}
QUERY_USERS = (0, 1, 7, 42, 137, 399)
TOP_N = 10

#: Served scores against ``user_factors[u] @ item_factors.T`` in NumPy
#: float32. Not bit equality: the device scorer multiplies f32 factors at
#: the TPU's default matmul precision (one bf16 pass, operands rounded to
#: 8 mantissa bits — about 4e-3 relative per product), so a rank-16 dot of
#: mostly same-signed terms lands within ~1e-2 of the float32 answer. The
#: host route (NumPy / native f32) agrees to ~1e-6 and passes trivially.
#: The run prints the worst error it saw, so PERF.md can say how much of
#: this room the B = 1 dispatches actually use.
SCORE_REL_TOL = 2e-2
#: placements that degrade with a log line each bump one of these; a
#: smoke that passed on a fallback would not have proven the device path
FALLBACK_COUNTERS = ("pio_tpu_shard_gather_fallback_total",
                     "pio_tpu_resident_fallback_total")
#: Pallas kernel vs the XLA lowering (both accumulate in float32)
KERNEL_REL_TOL = 1e-5
#: the resident CG kernel vs the XLA loop: the same K+8 float32 sweeps,
#: another summation order inside each matvec
SOLVE_REL_TOL = 1e-4

RESULT_TAG = "CHIP_SMOKE_RESULT "


class SmokeFailure(RuntimeError):
    pass


# ----------------------------------------------------------------- parent
class Runner:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.size = REHEARSAL if rehearse else FULL
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        self.t0 = time.monotonic()
        self.phase_s: dict = {}
        self.servers: list = []
        env = dict(os.environ)
        env["PIO_TPU_HOME"] = os.path.join(self.work, "home")
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("PIO_TPU_SERVE_DEVICE", None)
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            # one device, like one chip (tier-1 exports an 8-device mesh)
            env.pop("XLA_FLAGS", None)
            # the tiny edge set must still take the streamed trainer
            env["PIO_TPU_ALS_STREAM_MB"] = "0.08"
        self.env = env

    # -- plumbing ----------------------------------------------------------
    def remaining(self) -> float:
        left = BUDGET_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise SmokeFailure("out of time budget")
        return left

    def run(self, name: str, argv: list, env: dict = None) -> str:
        """One child to completion; its wall time is the phase's."""
        print(f"--- {name}: {' '.join(argv)}", flush=True)
        t = time.monotonic()
        try:
            proc = subprocess.run(
                [sys.executable, *argv], cwd=HERE, env=env or self.env,
                capture_output=True, text=True, timeout=self.remaining(),
            )
        except subprocess.TimeoutExpired as e:
            raise SmokeFailure(f"phase {name} ran out of time") from e
        dt = time.monotonic() - t
        self.phase_s[name] = round(self.phase_s.get(name, 0.0) + dt, 2)
        if proc.returncode != 0:
            raise SmokeFailure(
                f"phase {name} exited {proc.returncode}\n"
                f"stdout: {proc.stdout[-3000:]}\nstderr: {proc.stderr[-6000:]}"
            )
        print(f"    {name} ok in {dt:.1f}s", flush=True)
        return proc.stdout

    def phase(self, name: str, env: dict = None) -> dict:
        """A child of this file; returns the JSON it reports."""
        argv = [os.path.abspath(__file__), "--phase", name,
                "--work", self.work]
        if self.rehearse:
            argv.append("--rehearse")
        out = self.run(name, argv, env)
        for line in reversed(out.splitlines()):
            if line.startswith(RESULT_TAG):
                got = json.loads(line[len(RESULT_TAG):])
                print(f"    {json.dumps(got)}", flush=True)
                return got
        raise SmokeFailure(f"phase {name} reported no result:\n{out[-2000:]}")

    def pio(self, name: str, *args: str) -> str:
        return self.run(name, ["-m", "pio_tpu", *args])

    def require_tpu(self, who: str, platform) -> None:
        if not self.rehearse and platform != "tpu":
            raise SmokeFailure(
                f"{who} ran on platform {platform!r}, not 'tpu' "
                "(no accelerator, or it is held by another process)"
            )

    # -- serving -----------------------------------------------------------
    def serve(self, name: str, force_device: bool) -> dict:
        """deploy → a few queries → evidence from outside → undeploy."""
        env = dict(self.env)
        if force_device:
            env["PIO_TPU_SERVE_DEVICE"] = "1"
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        base = f"http://127.0.0.1:{port}"
        log_path = os.path.join(self.work, f"{name}.log")
        t = time.monotonic()
        print(f"--- {name}: pio deploy on :{port}", flush=True)
        with open(log_path, "w") as log:
            server = subprocess.Popen(
                [sys.executable, "-m", "pio_tpu", "deploy",
                 "--engine-json", ENGINE_JSON,
                 "--ip", "127.0.0.1", "--port", str(port)],
                cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        self.servers.append(server)

        def server_log() -> str:
            with open(log_path) as f:
                return f.read()[-6000:]

        while True:
            if server.poll() is not None:
                raise SmokeFailure(
                    f"{name}: deploy exited {server.returncode} before it "
                    f"was ready\n{server_log()}"
                )
            try:
                with urllib.request.urlopen(base + "/readyz", timeout=2) as r:
                    if r.status == 200:
                        break
            except (urllib.error.URLError, OSError):
                pass
            self.remaining()
            time.sleep(0.2)
        ready_s = time.monotonic() - t

        def get(path: str) -> dict:
            with urllib.request.urlopen(base + path, timeout=30) as r:
                return json.loads(r.read())

        served = []
        first_query_s = None
        for u in QUERY_USERS:
            if u >= self.size["n_users"]:
                continue
            body = {"user": f"u{u}", "num": TOP_N}
            req = urllib.request.Request(
                base + "/queries.json", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"}, method="POST",
            )
            tq = time.monotonic()
            with urllib.request.urlopen(req, timeout=120) as r:
                answer = json.loads(r.read())
            if first_query_s is None:
                first_query_s = time.monotonic() - tq
            scores = answer.get("itemScores") or []
            if len(scores) != TOP_N:
                raise SmokeFailure(f"{name}: {body} answered {answer}")
            served.append({"deploy": name, **body, "itemScores": scores})
        stats = get("/stats.json")
        device = get("/device.json")
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
        self.pio(name + ".undeploy", "undeploy", "--ip", "127.0.0.1",
                 "--port", str(port))
        try:
            server.wait(timeout=min(60.0, self.remaining()))
        except subprocess.TimeoutExpired as e:
            raise SmokeFailure(f"{name}: server outlived undeploy") from e
        self.phase_s[name] = round(time.monotonic() - t, 2)

        scorers = stats.get("topnScorers") or []
        if len(scorers) != 1:
            raise SmokeFailure(f"{name}: /stats.json topnScorers = {scorers}")
        if stats.get("errorCount"):
            raise SmokeFailure(f"{name}: server counted errors: {stats}")
        fallbacks = {
            line.split("{")[0]: float(line.rsplit(" ", 1)[1])
            for line in metrics.splitlines()
            if line.startswith(FALLBACK_COUNTERS)
        }
        if set(fallbacks) != set(FALLBACK_COUNTERS) or any(
            fallbacks.values()
        ):
            raise SmokeFailure(
                f"{name}: a placement fell back (or its counter is gone): "
                f"{fallbacks}"
            )
        got = {
            "ready_s": round(ready_s, 2),
            "first_query_s": round(first_query_s, 3),
            "queries": len(served),
            "scorer": scorers[0],
            "devices": device["devices"],
        }
        print(f"    {name} ok in {self.phase_s[name]:.1f}s "
              f"{json.dumps(got)}", flush=True)
        got["served"] = served
        return got

    def check_device_evidence(self, name: str, got: dict) -> None:
        """What the live server said about where its tables are."""
        if self.rehearse:
            return
        tables = 4 * 16 * (self.size["n_users"] + self.size["n_items"])
        dev0 = got["devices"][0]
        if not (dev0["device"].startswith("tpu:")
                and dev0["source"] == "memory_stats"
                and dev0["bytesInUse"] >= tables):
            raise SmokeFailure(
                f"{name}: /device.json does not show the factor tables "
                f"({tables} B) on a tpu device: {got['devices']}"
            )

    # -- the run -----------------------------------------------------------
    def main(self) -> dict:
        env = self.phase("env")
        device = env["device"]
        print(
            f"platform: {device['platform']}  device_kind: {device['kind']}  "
            f"devices: {device['count']}  host cores: {env['host_cores']}  "
            f"compile cache: {env['compile_cache']}", flush=True,
        )
        self.require_tpu("the device probe", device["platform"])

        self.phase("generate")
        self.pio("app_new", "app", "new", APP)
        self.pio("import", "import", "--app", APP, "--input",
                 os.path.join(self.work, "events.jsonl"))
        self.pio("train", "train", "--engine-json", ENGINE_JSON)
        # the trainer has exited; what it did is read from its run record
        runs = json.loads(
            self.pio("runs", "runs", "--engine-json", ENGINE_JSON, "--json")
        )
        run = runs[-1]
        print(f"    run record: {json.dumps(run)}", flush=True)
        if run["status"] != "COMPLETED":
            raise SmokeFailure(f"train run record: {run}")
        self.require_tpu("pio train", run.get("platform"))

        forced = self.serve("deploy_device", force_device=True)
        self.check_device_evidence("deploy_device", forced)
        routes = forced["scorer"]["routes"]
        if not (routes["device"] > 0 and routes["host"] == 0):
            raise SmokeFailure(
                f"PIO_TPU_SERVE_DEVICE=1 deploy answered off the device: "
                f"{forced['scorer']}"
            )
        # the same server as a user gets it: adaptive routing decides
        default = self.serve("deploy_default", force_device=False)
        self.check_device_evidence("deploy_default", default)
        sc = default["scorer"]
        default_route = (
            "device" if sc["routes"]["device"] and not sc["routes"]["host"]
            else "host" if sc["routes"]["host"] and not sc["routes"]["device"]
            else "mixed"
        )
        print(
            f"default B=1 route: {default_route}  "
            f"(rtt {sc['linkRttS']} s, host row {sc['hostRowS']} s, "
            f"min_device_batch {sc['minDeviceBatch']})", flush=True,
        )

        with open(os.path.join(self.work, "served.json"), "w") as f:
            json.dump(forced.pop("served") + default.pop("served"), f)
        # correctness from the persisted model, in a child pinned to CPU
        reference = self.phase(
            "reference", env={**self.env, "JAX_PLATFORMS": "cpu"}
        )

        stream = self.phase("als_stream")
        self.require_tpu("als_stream", stream["device"]["platform"])
        kernel = self.phase("embedding_bag_kernel")
        self.require_tpu("embedding_bag_kernel", kernel["device"]["platform"])
        solve = self.phase("als_solve_kernel")
        self.require_tpu("als_solve_kernel", solve["device"]["platform"])
        gather = self.phase("als_gather")
        self.require_tpu("als_gather", gather["device"]["platform"])

        summary = {"ok": True}
        if self.rehearse:
            summary["rehearsal"] = True
        summary.update({
            "device": device,
            "host_cores": env["host_cores"],
            "versions": env["versions"],
            "compile_cache": env["compile_cache"],
            "native": env["native"],
            "phase_s": self.phase_s,
            "total_s": round(time.monotonic() - self.t0, 1),
            "train": {
                k: v for k, v in run.items()
                if k in ("platform", "device_kind", "num_devices",
                         "train_seconds") or k.startswith("phase_")
            },
            "deploy_device": forced,
            "deploy_default": default,
            "default_b1_route": default_route,
            "reference": reference,
            "als_stream": stream,
            "embedding_bag_kernel": kernel,
            "als_solve_kernel": solve,
            "als_gather": gather,
            "claim": None,
        })
        return summary

    def close(self) -> None:
        for server in self.servers:
            if server.poll() is None:
                try:
                    os.killpg(server.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                server.wait(timeout=10)
        shutil.rmtree(self.work, ignore_errors=True)


# --------------------------------------------------------------- children
def report(result: dict) -> None:
    print(RESULT_TAG + json.dumps(result), flush=True)


def device_summary() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def synth_events(size: dict):
    """Seeded (user, item, rating) triples: at least one per user and per
    item, so both factor tables come out at full height; the rest are
    uniform users over popularity-skewed items. Ratings sit on the
    half-star grid around user and item biases, so a trained model has
    something to beat the predict-the-mean baseline with."""
    import numpy as np

    n, n_users, n_items = size["n_events"], size["n_users"], size["n_items"]
    cover = max(n_users, n_items)
    assert n >= cover
    rng = np.random.default_rng(SEED)
    user = np.concatenate([
        np.arange(cover) % n_users, rng.integers(0, n_users, n - cover),
    ])
    item = np.concatenate([
        np.arange(cover) % n_items,
        (rng.random(n - cover) ** 2 * n_items).astype(np.int64),
    ])
    raw = (3.0 + rng.normal(0, 0.8, n_users)[user]
           + rng.normal(0, 0.8, n_items)[item] + rng.normal(0, 0.5, n))
    rating = np.clip(np.round(raw * 2) / 2, 0.5, 5.0).astype(np.float32)
    return user.astype(np.int32), item.astype(np.int32), rating


def phase_env(size: dict, work: str, rehearse: bool) -> dict:
    from pio_tpu.utils.compile_cache import place_compile_cache

    cache = place_compile_cache()
    import jax
    import jaxlib
    import numpy as np

    from pio_tpu import native

    assert jax.config.jax_compilation_cache_dir == cache
    versions = {"python": sys.version.split()[0], "jax": jax.__version__,
                "jaxlib": jaxlib.__version__, "numpy": np.__version__}
    from importlib import metadata

    try:
        versions["libtpu"] = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        versions["libtpu"] = None
    # a g++ failure on this machine is a finding, not a slower pass: these
    # raise NativeUnavailable instead of falling back to NumPy
    libs = {}
    for name, load in (("als_pack", native.als_pack_lib),
                       ("topn_host", native.topn_host_lib)):
        load()
        libs[name] = "loaded"
    return {
        "device": device_summary(),
        "host_cores": {"cpu_count": os.cpu_count(),
                       "affinity": len(os.sched_getaffinity(0))},
        "versions": versions,
        "compile_cache": cache,
        "native": libs,
    }


def phase_generate(size: dict, work: str, rehearse: bool) -> dict:
    user, item, rating = synth_events(size)
    path = os.path.join(work, "events.jsonl")
    with open(path, "w") as f:
        for k, (u, i, r) in enumerate(
            zip(user.tolist(), item.tolist(), rating.tolist())
        ):
            f.write(
                '{"event":"rate","entityType":"user","entityId":"u%d",'
                '"targetEntityType":"item","targetEntityId":"i%d",'
                '"properties":{"rating":%.1f},'
                '"eventTime":"2026-01-01T%02d:%02d:%02d.000Z"}\n'
                % (u, i, r, k // 3600 % 24, k // 60 % 60, k % 60)
            )
    return {"events": len(user), "users": size["n_users"],
            "items": size["n_items"], "bytes": os.path.getsize(path)}


def phase_reference(size: dict, work: str, rehearse: bool) -> dict:
    """Everything that decides whether the answers were RIGHT, from the
    persisted model, in NumPy float32 on the CPU."""
    import numpy as np

    from pio_tpu.parallel.context import ComputeContext
    from pio_tpu.workflow import (
        build_engine, load_models_for_instance, load_variant,
    )
    from pio_tpu.workflow.deploy_common import resolve_instance_id

    variant = load_variant(ENGINE_JSON)
    engine, params = build_engine(variant)
    (model,) = load_models_for_instance(
        resolve_instance_id(variant, None), engine, params,
        ComputeContext.local(), variant=variant,
    )
    P = np.asarray(model.factors.user_factors, np.float32)
    Q = np.asarray(model.factors.item_factors, np.float32)
    rank = 16  # engine.json as shipped
    assert P.shape == (size["n_users"], rank), P.shape
    assert Q.shape == (size["n_items"], rank), Q.shape
    assert np.isfinite(P).all() and np.isfinite(Q).all()

    user, item, rating = synth_events(size)
    ucode = np.array([model.user_index[f"u{u}"] for u in range(len(P))])
    icode = np.array([model.item_index[f"i{i}"] for i in range(len(Q))])
    pred = np.einsum("ek,ek->e", P[ucode[user]], Q[icode[item]])
    rmse = float(np.sqrt(np.mean((pred - rating) ** 2)))
    baseline = float(np.sqrt(np.mean((rating - rating.mean()) ** 2)))
    assert rmse < baseline, (rmse, baseline)

    with open(os.path.join(work, "served.json")) as f:
        served = json.load(f)
    worst = {}
    for row in served:
        ref = P[model.user_index[row["user"]]] @ Q.T
        floor = np.sort(ref)[-row["num"]]  # the true num-th best score
        for got in row["itemScores"]:
            want = ref[model.item_index[got["item"]]]
            tol = SCORE_REL_TOL * max(abs(want), 1e-3 * np.abs(ref).max())
            err = abs(got["score"] - want)
            assert err <= tol, (row["deploy"], row["user"], got, want)
            # and the item belongs in the top-N, up to the same rounding
            assert want >= floor - tol, (row["deploy"], row["user"], got)
            rel = float(err / max(abs(want), 1e-12))
            worst[row["deploy"]] = max(worst.get(row["deploy"], 0.0), rel)
    return {"train_rmse": round(rmse, 4), "mean_rmse": round(baseline, 4),
            "served_checked": len(served), "score_rel_tol": SCORE_REL_TOL,
            "worst_rel_err": worst}


def phase_als_stream(size: dict, work: str, rehearse: bool) -> dict:
    """``train_als`` under ``ComputeContext.create()`` on the headline
    edge count: one chip takes the streamed trainer (donated accumulators,
    overlapped device_puts), a multi-chip host the sharded edge spans."""
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import numpy as np

    from pio_tpu.models.als import ALSConfig, train_als
    from pio_tpu.obs import trainwatch
    from pio_tpu.parallel.context import ComputeContext

    n_edges, n_users, n_items = (
        size["stream_edges"], size["n_users"], size["n_items"]
    )
    rng = np.random.default_rng(SEED)
    user = rng.integers(0, n_users, n_edges).astype(np.int32)
    item = (rng.random(n_edges) ** 2 * n_items).astype(np.int32)
    rating = (rng.integers(1, 11, n_edges) * 0.5).astype(np.float32)

    ctx = ComputeContext.create()
    recorder = trainwatch.StepRecorder("chip-smoke-als-stream")
    t = time.monotonic()
    with trainwatch.recording(recorder):
        factors = train_als(
            ctx, user, item, rating, n_users, n_items,
            ALSConfig(rank=16, iterations=10, reg=0.1),
        )
    wall = time.monotonic() - t
    assert factors.user_factors.shape == (n_users, 16)
    assert factors.item_factors.shape == (n_items, 16)
    assert np.isfinite(factors.user_factors).all()
    assert np.isfinite(factors.item_factors).all()
    algo = recorder.summary()
    if ctx.num_devices == 1:
        assert algo["streamed"] and algo["stream_chunks"] > 1, algo
    return {"device": device_summary(), "edges": n_edges,
            "streamed": algo["streamed"], "chunks": algo["stream_chunks"],
            "train_als_s_incl_compile": round(wall, 2)}


def phase_embedding_bag_kernel(size: dict, work: str, rehearse: bool) -> dict:
    """The one Pallas kernel, compiled by Mosaic (``interpret=False`` on
    the chip), against the XLA lowering it stands in for."""
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import numpy as np

    from pio_tpu.ops.embedding import (
        _embedding_bag_pallas, _embedding_bag_xla,
    )

    k = size["kernel"]
    rng = np.random.default_rng(SEED)
    table = jax.device_put(
        rng.normal(size=(k["V"], k["D"])).astype(np.float32)
    )
    ids = jax.device_put(
        rng.integers(0, k["V"], (k["B"], k["L"])).astype(np.int32)
    )
    weights = jax.device_put(
        rng.random((k["B"], k["L"])).astype(np.float32)
    )
    t = time.monotonic()
    got = np.asarray(jax.jit(
        lambda *a: _embedding_bag_pallas(*a, interpret=rehearse)
    )(table, ids, weights))
    wall = time.monotonic() - t
    want = np.asarray(jax.jit(_embedding_bag_xla)(table, ids, weights))
    assert got.shape == want.shape == (k["B"], k["D"])
    assert np.isfinite(got).all()
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    assert rel <= KERNEL_REL_TOL, rel
    return {"device": device_summary(), "shape": k, "interpret": rehearse,
            "max_rel_err_vs_xla": rel, "rel_tol": KERNEL_REL_TOL,
            "pallas_s_incl_compile": round(wall, 2)}


def phase_als_solve_kernel(size: dict, work: str, rehearse: bool) -> dict:
    """``solve_block`` on one batch of SPD systems through both CG
    implementations: the VMEM-resident Pallas kernel (what the selection
    rule picks on the chip at this size) and the XLA loop it replaces
    there. Alone it is a 20-second check that the kernel still compiles
    and agrees: ``python chip_smoke.py --phase als_solve_kernel``."""
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from pio_tpu.models import als

    n, K = size["solve"]["n"], size["solve"]["K"]
    platform = jax.default_backend()
    picked = als._solve_impl("cg", n, K, platform)
    assert picked == ("xla_cg" if rehearse else "resident_cg"), picked

    @jax.jit
    def systems(key):
        kw, kb = jax.random.split(key)
        W = jax.random.normal(kw, (n, K, 2 * K), jnp.float32)
        A = jnp.einsum("nkw,nlw->nkl", W, W, precision="highest")
        return A, jax.random.normal(kb, (n, K), jnp.float32)

    A, b = systems(jax.random.PRNGKey(SEED))
    gram = jnp.zeros((K, K), jnp.float32)
    out, seconds = {}, {}
    for impl in ("resident_cg", "xla_cg"):
        # a fresh math per implementation: the rule is read at trace time
        with mock.patch.object(als, "_solve_impl", return_value=impl):
            solve = jax.jit(
                als._make_math(0.1, False, 1.0, "float32", "cg").solve_block
            )
            jax.block_until_ready(solve(A, b, gram))  # compile
            t = time.monotonic()
            out[impl] = jax.block_until_ready(solve(A, b, gram))
            seconds[impl] = round(time.monotonic() - t, 4)
    assert bool(jnp.isfinite(out["resident_cg"]).all())
    rel = float(jnp.abs(out["resident_cg"] - out["xla_cg"]).max()
                / jnp.abs(out["xla_cg"]).max())
    assert rel <= SOLVE_REL_TOL, rel
    return {"device": device_summary(), "shape": [n, K, K],
            "picked": picked, "interpret": platform != "tpu",
            "max_rel_diff": rel, "rel_tol": SOLVE_REL_TOL,
            "solve_s": seconds}


def phase_als_gather(size: dict, work: str, rehearse: bool) -> dict:
    """``partial_normal_eq``'s factor-row gather alone, inside a scan,
    from both table layouts: the table as it is (``plain``) and the
    lane-dense one (``packed``) that the selection rule picks on the chip
    for the larger of the cell's two tables. Seconds and ns a row for
    each table in each layout; the rows must agree to the bit. A small
    program is no witness of where the compiler keeps a table inside the
    trainer (the tier-1 compile test is). Alone:
    ``python chip_smoke.py --phase als_gather``."""
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pio_tpu.models import als

    g = size["gather"]
    K, chunk, W, steps = g["K"], g["chunk"], g["W"], g["steps"]
    picked = {str(n): als._gather_impl(jax.default_backend(), n, K, 2)
              for n in g["rows"]}
    want = "plain" if rehearse else "packed"  # of the larger table alone
    assert list(picked.values()) == [want, "plain"], picked
    layouts = {"plain": lambda t: t, "packed": als._pack_table}

    @jax.jit
    def gathered(table, other):
        """Elementwise maximum over every chunk's gathered rows: exact
        whatever the order, and it reads all of them."""
        def step(acc, oth):
            q = als._gather_rows(table, jnp.maximum(oth, 0), K)
            return jnp.maximum(acc, q.max(axis=0)), None

        acc0 = jnp.full((W, K), -jnp.inf, table.dtype)
        return jax.lax.scan(step, acc0, other)[0]

    seconds, ns_a_row = {}, {}
    for n in g["rows"]:
        kt, ko = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(SEED), n))
        table = jax.random.normal(kt, (n, K), jnp.float32).astype(
            jnp.bfloat16)
        # -1 is a padded slot, as in a packed block
        other = jax.random.randint(ko, (steps, chunk, W), -1, n, jnp.int32)
        out = {}
        for form, layout in layouts.items():
            laid = layout(table)
            jax.block_until_ready(gathered(laid, other))  # compile
            t = time.monotonic()
            out[form] = jax.block_until_ready(gathered(laid, other))
            s = time.monotonic() - t
            seconds[f"{form}_{n}"] = round(s, 4)
            ns_a_row[f"{form}_{n}"] = round(
                1e9 * s / (steps * chunk * W), 3)
        assert np.array_equal(
            np.asarray(out["plain"].astype(jnp.float32)),
            np.asarray(out["packed"].astype(jnp.float32))), n
    return {"device": device_summary(), "picked": picked,
            "shape": {"rows": g["rows"], "K": K,
                      "rows_a_step": chunk * W, "steps": steps},
            "gather_s": seconds, "ns_a_row": ns_a_row}


PHASES = {
    "env": phase_env,
    "generate": phase_generate,
    "reference": phase_reference,
    "als_stream": phase_als_stream,
    "embedding_bag_kernel": phase_embedding_bag_kernel,
    "als_solve_kernel": phase_als_solve_kernel,
    "als_gather": phase_als_gather,
}


def result_line(summary: dict) -> dict:
    """The last line of stdout: ``ok`` and ``device`` and nothing else (a
    rehearsal keeps its label, so it can never pass for a chip run)."""
    device = summary["device"]
    line = {"ok": summary["ok"]}
    if summary.get("rehearsal"):
        line["rehearsal"] = True
    line["device"] = {"platform": str(device["platform"]),
                      "kind": str(device["kind"]),
                      "count": int(device["count"])}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="same phases, tiny size, on CPU; labels itself")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        size = REHEARSAL if args.rehearse else FULL
        report(PHASES[args.phase](size, args.work, args.rehearse))
        return 0
    runner = Runner(args.rehearse)
    try:
        summary = runner.main()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        runner.close()
    print(json.dumps(summary), flush=True)
    print(json.dumps(result_line(summary)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
