"""Benchmark harness — all five BASELINE.json configs.

Headline metric (unchanged since round 1): examples/sec/chip on the
Recommendation (ALS) template at MovieLens-25M scale (25M ratings,
162,541 users, 59,047 items). One "example" = one rating edge processed
through one full ALS iteration (both half-steps). The reference publishes
no numbers (BASELINE.md), so ``vs_baseline`` is measured against our own
single-host XLA-CPU run of the same program — the "Spark-free CPU ALS
reference anchor" from SURVEY.md §6.

``p50_predict_ms`` is measured THROUGH A LIVE QUERY SERVER: the trained
headline model is persisted to the real storage stack, deployed behind
``create_query_server``, and timed over HTTP ``POST /queries.json`` —
JSON binding, plugin hooks, serving.serve and the device scorer all
included. ``p50_inproc_ms`` keeps the round-1 in-process number for
continuity.

``phases`` decomposes the headline run (one extra profiled train, phases
serialized): host pack seconds, wire bytes + host→device seconds, pure
device-compute seconds, the device-only examples/sec that the host link
hides, and achieved GFLOP/s (normal-equation build term).

``serving`` measures the live query server under load: sequential p50,
then 16 concurrent clients (qps/p50/p95), then the same with the
micro-batching aggregator coalescing concurrent queries into batched
device dispatches (PIO_TPU_SERVE_MICROBATCH_US).

``secondary`` covers the remaining BASELINE.json configs — each as
{value, cpu_anchor, vs_baseline} with the headline's own-CPU-anchor
discipline (same program, XLA-CPU device, subsampled workload):
  - classification      LogReg SGD (treeAggregate → psum all-reduce)
  - similarproduct      implicit ALS (MLlib trainImplicit analog)
  - textclassification  Pallas embedding-bag vs plain-XLA lowering
  - twotower            contrastive two-tower retrieval training
plus ``als_rank_sweep`` (rank 16/64/128 MXU scaling),
``eventserver_events_per_sec`` (HTTP ingest into sqlite + native
eventlog backends) and ``ingest.partitioned`` (the hash-partitioned
replicated log at N=1/2/4 partitions, with a replicated pass recording
``repl_lag_p95_ms`` from the send-to-ack histogram).

Output contract (round 5 — the driver records only the LAST 2000 chars
of stdout, and round 4's single fat JSON line was truncated FRONT-first,
losing the headline): the full detail blob
    {"metric": ..., "value": N, ..., "phases": {...},
     "serving": {...}, "secondary": {...}}
is written to ``BENCH_FULL.json`` next to this file, and stdout carries
exactly ONE compact summary line (≤1900 chars, built by
``build_summary``) with the headline value/vs_baseline, link probe,
device rate, pack_s, p50s, concurrent/pool QPS and per-config ratios.

Env knobs (for smoke runs): PIO_TPU_BENCH_EDGES, PIO_TPU_BENCH_ITERS,
PIO_TPU_BENCH_RANK, PIO_TPU_BENCH_CPU_EDGES, PIO_TPU_BENCH_QUERIES,
PIO_TPU_BENCH_SECONDARY=0 (skip the secondary block),
PIO_TPU_BENCH_RANKSWEEP=0 (skip the rank sweep),
PIO_TPU_BENCH_SCALE (0<s≤1 scales every secondary workload).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

# MovieLens-25M shape (ratings, users, movies)
ML25M_EDGES = 25_000_000
ML25M_USERS = 162_541
ML25M_ITEMS = 59_047


def _synth_ratings(n_edges: int, n_users: int, n_items: int, seed: int = 0):
    """Synthetic MovieLens-like COO ratings (zipf-ish item popularity)."""
    rng = np.random.default_rng(seed)
    user_idx = rng.integers(0, n_users, size=n_edges).astype(np.int32)
    # popularity-skewed items: square a uniform to bias toward low ids
    item_idx = (rng.random(n_edges) ** 2 * n_items).astype(np.int32)
    rating = (rng.integers(1, 11, size=n_edges) * 0.5).astype(np.float32)
    return user_idx, item_idx, rating


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


#: stages that raised; the summary line still prints, then the run exits
#: non-zero — a hole in the artifact must not look like a pass
_FAILED_STAGES: list = []


def _stage_failed(stage: str, exc: BaseException) -> None:
    print(f"# {stage} failed: {exc}", file=sys.stderr)
    _FAILED_STAGES.append(stage)


#: a pool whose worker 0 owns the accelerator cannot start under this
#: process, which trained on the chip and still holds it
_NEEDS_CHIP_CHILD = {"skipped": "needs a chip-owning child"}


def _holds_accelerator() -> bool:
    import jax

    return jax.default_backend() != "cpu"


def _best_of(fn, repeats=3):
    """Best-of-``repeats`` wrapper over _timed_runs — used by stages
    where min time is the stable throughput estimate. Returns
    (seconds, last result)."""
    times, out = _timed_runs(fn, repeats)
    return times[0], out


def _timed_runs(fn, repeats=3):
    """Warmup/compile once, then ``repeats`` timed runs. Returns
    (sorted seconds list, last result) — callers report the MEDIAN as
    the headline (robust to run-to-run swings in either direction, where
    min overstates and mean understates) and may quote the best
    alongside."""
    fn()  # warmup/compile
    times, out = [], None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return sorted(times), out


def _probe_link_mb_s(n_bytes: int = 32 << 20) -> float:
    """Same-session host→device bandwidth probe, so every recorded
    headline carries the link speed it was measured under. The buffer is
    incompressible, and a device-side reduction over the data forces the
    upload to actually complete before the clock stops (``device_put``
    returns before the transfer does). 32 MB amortizes dispatch
    latency."""
    import jax
    import jax.numpy as jnp

    buf = np.random.default_rng(0).integers(
        0, 256, n_bytes, dtype=np.uint8
    )
    reduce = jax.jit(lambda x: jnp.max(x))

    def once():
        return float(jax.block_until_ready(reduce(jax.device_put(buf))))

    once()  # warm path + compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        once()
        best = min(best, time.perf_counter() - t0)
    return n_bytes / best / 1e6


def _probe_link_d2h_mb_s(n_bytes: int = 16 << 20) -> float:
    """Device→host companion probe for READBACK-bound stages (the
    upload probe measures the other direction, and a link need not be
    symmetric). The probed array must be a COMPUTATION OUTPUT:
    ``device_get`` of a host-originated ``device_put`` array returns
    jax's retained host copy without touching the wire. XOR with a
    nonzero scalar keeps the bytes incompressible; ``device_get`` is
    synchronous, so the upload probe's early-return trap doesn't
    apply."""
    import jax
    import jax.numpy as jnp

    buf_dev = jax.device_put(np.random.default_rng(1).integers(
        0, 256, n_bytes, dtype=np.uint8
    ))
    scramble = jax.jit(lambda x, s: jnp.bitwise_xor(x, s))

    def fresh(k):
        # a NEW device-only result each time: jax caches the host copy
        # on an Array after its first pull, so re-getting one array
        # measures that cache, not the wire
        dev = scramble(buf_dev, jnp.uint8(k))
        jax.block_until_ready(dev)
        return dev

    jax.device_get(fresh(0))  # warm compile + path
    best = float("inf")
    for k in (1, 2):
        dev = fresh(k)
        t0 = time.perf_counter()
        jax.device_get(dev)
        best = min(best, time.perf_counter() - t0)
    return n_bytes / best / 1e6


def _link_meta(active: bool, d2h: bool = False) -> dict:
    """Same-moment link metadata for a wire-bound stage — empty on the
    CPU-anchor side, where a link probe is meaningless. One helper so
    the probe/round/attach sequence cannot drift between stages."""
    if not active:
        return {}
    if d2h:
        return {"link_d2h_mb_s": round(_probe_link_d2h_mb_s(), 1)}
    return {"link_mb_s": round(_probe_link_mb_s(), 1)}


# --------------------------------------------------------------- headline
def _time_train(ctx, u, i, r, n_users, n_items, cfg, repeats=5):
    """repeats=5 on the headline; the caller reports the MEDIAN with the
    best alongside. Returns (sorted seconds, factors)."""
    from pio_tpu.models.als import train_als

    return _timed_runs(
        lambda: train_als(ctx, u, i, r, n_users, n_items, cfg), repeats
    )


def _predict_p50_inproc_ms(factors, n_users: int, n_queries: int) -> float:
    """Round-1 continuity metric: the serving math in-process (no HTTP).
    Uses the same adaptive scorer the server uses."""
    from pio_tpu.ops.topn import DeviceTopNScorer

    scorer = DeviceTopNScorer(
        factors.user_factors, factors.item_factors, warmup=True
    )
    lat = []
    for q in range(n_queries):
        user = np.asarray([(q * 7919) % n_users], np.int32)
        t0 = time.perf_counter()
        scorer.top_n_batch(user, 10)
        lat.append(time.perf_counter() - t0)
    return float(np.percentile(np.array(lat) * 1000.0, 50))


# ------------------------------------------------- through-server serving
def _bench_server_p50(factors, n_users: int, n_items: int,
                      n_queries: int) -> dict:
    """Deploy the trained factors behind a real query server (storage
    round trip included) and measure HTTP ``POST /queries.json``:

    - sequential p50 (single client — the round-1/2 continuity metric)
    - concurrent load: 16 client threads → ``serving_qps`` + p50/p95
    - the same concurrent load with the micro-batching aggregator on
      (``PIO_TPU_SERVE_MICROBATCH_US``) — concurrent queries coalesce
      into one batched device dispatch (``algo.batch_predict``)
    """
    from pio_tpu.controller import (
        Algorithm, DataSource, Engine, FirstServing, IdentityPreparator,
        register_engine,
    )
    from pio_tpu.controller.engine import EngineParams
    from pio_tpu.controller.params import EmptyParams
    from pio_tpu.data.bimap import BiMap
    from pio_tpu.templates.recommendation import ALSModel, Query
    from pio_tpu.workflow.core_workflow import run_train
    from pio_tpu.workflow.engine_json import variant_from_dict

    class BenchDataSource(DataSource):
        def read_training(self, ctx):
            return None

    class BenchServeAlgorithm(Algorithm):
        """Serves the pre-trained headline factors (train wraps, not fits —
        the server benchmark measures serving, not a second training)."""

        query_class = Query

        def train(self, ctx, pd):
            return ALSModel(
                factors,
                BiMap({f"u{i}": i for i in range(n_users)}),
                BiMap({f"i{i}": i for i in range(n_items)}),
            )

        def predict(self, model, query):
            from pio_tpu.templates.recommendation import predict_user_topn

            return predict_user_topn(
                model, query, model.user_index, model.item_index
            )

        def batch_predict(self, model, indexed_queries):
            from pio_tpu.templates.recommendation import batched_user_topn

            return batched_user_topn(
                self, model, indexed_queries, model.user_index,
                model.item_index, model.scorer,
            )

        def warmup_query(self, model):
            return Query(user="u0")

        def prepare_for_serving(self, model):
            model.scorer(warmup=True)
            return model

    register_engine("bench.recommendation")(
        lambda: Engine(
            BenchDataSource, IdentityPreparator,
            {"als": BenchServeAlgorithm}, FirstServing,
        )
    )
    variant = variant_from_dict({
        "id": "bench-recommendation",
        "version": "1",
        "engineFactory": "bench.recommendation",
        "algorithms": [{"name": "als", "params": {}}],
    })
    engine_params = EngineParams(
        algorithm_params_list=(("als", EmptyParams()),)
    )
    from pio_tpu.workflow.engine_json import build_engine

    engine, _ = build_engine(variant)
    run_train(engine, engine_params, variant)

    out = {}
    server, _service, post = _serve_single(variant, 0)
    out["time_to_ready_s"] = server.time_to_ready_s
    try:
        got = post({"user": "u1", "num": 10})  # warm (compile + route)
        assert got.get("itemScores"), got
        lat = []
        for q in range(n_queries):
            body = {"user": f"u{(q * 7919) % n_users}", "num": 10}
            t0 = time.perf_counter()
            post(body)
            lat.append(time.perf_counter() - t0)
        out["p50_ms"] = round(
            float(np.percentile(np.array(lat) * 1000.0, 50)), 3
        )
        out["concurrent"] = _with_metrics_delta(
            server.port, lambda: _concurrent_stage(server.port, n_users)
        )
        # per-stage latency budget of everything served above: where the
        # e2e milliseconds went (accept→…→write), and how much of the
        # average the stage spans actually attribute (the residual is the
        # instrumentation's blind spot — the acceptance bar is ≥95%)
        import urllib.request as _ur

        with _ur.urlopen(
            f"http://127.0.0.1:{server.port}/debug/hotpath.json",
            timeout=10,
        ) as resp:
            out["latency_budget"] = json.loads(resp.read().decode("utf-8"))
    finally:
        post.close()
        server.stop()

    try:
        server, service, post = _serve_single(variant, microbatch_us=1500)
        try:
            # warm until the adaptive probe settles (or caps out) so the
            # timed stage measures the POST-decision steady state
            post({"user": "u1", "num": 10})
            _drive_until_decided(server.port, service, n_users)
            out["concurrent_microbatch"] = _with_metrics_delta(
                server.port,
                lambda: _concurrent_stage(server.port, n_users),
            )
            out["concurrent_microbatch"]["time_to_ready_s"] = (
                server.time_to_ready_s
            )
            mb = service._batcher.to_dict()
            out["concurrent_microbatch"]["mode"] = mb["mode"]
            out["concurrent_microbatch"]["mode_by_bucket"] = mb.get(
                "modeByBucket", {}
            )
            out["concurrent_microbatch"]["probe"] = mb["probe"]
            out["concurrent_microbatch"]["avg_batch"] = round(
                mb["batchedQueries"] / max(1, mb["batches"]), 2
            )
            out["concurrent_microbatch"]["max_batch"] = mb["maxBatch"]
            # shape-bucket accounting: per-bucket dispatch counts, the
            # retrace counter (steady state should be flat — every count
            # beyond the warmup sweep is a lost compile on the hot path)
            # and the cache's own view (generation, warmed ladder)
            eng = service.variant.engine_id
            out["concurrent_microbatch"]["bucket_dispatches"] = {
                str(b): int(
                    service._bucket_dispatch_total.labels(eng, str(b)).value
                )
                for b in service._buckets.buckets
            }
            out["concurrent_microbatch"]["bucket_retraces"] = int(
                service._bucket_retrace_total.labels(eng).value
            )
            out["concurrent_microbatch"]["buckets"] = (
                service._buckets.to_dict()
            )
        finally:
            post.close()
            server.stop()
    except Exception as exc:
        _stage_failed("microbatch serving stage", exc)

    try:
        out["overload"] = _bench_overload(
            variant, n_users, out["concurrent"]["qps"]
        )
    except Exception as exc:
        _stage_failed("overload serving stage", exc)
    return out


def _bench_overload(variant, n_users: int, base_qps: float) -> dict:
    """Overload stage (ISSUE 3): re-serve the same engine with admission
    control capped at roughly HALF the measured concurrent capacity and
    drive the full 16-thread load against it — about 2× saturation. The
    interesting numbers are the control plane's, not the data plane's:
    what fraction was shed (429/503 + Retry-After), the p99 of the
    requests that WERE admitted (shedding exists to protect exactly
    this), and what fraction the stale cache answered instead
    (``X-Pio-Degraded: stale-cache``)."""
    import urllib.request

    from pio_tpu.server.query_server import create_query_server

    # budget: half the measured capacity with a token-thin burst (a deep
    # burst would absorb the whole stage); stale cache smaller than the
    # hot key space so the artifact shows all three outcomes — admitted,
    # degraded (cache hit), shed (cache miss)
    rps = max(base_qps / 2.0, 20.0)
    spec = f"rps={rps:.0f},burst=8,cache=32"
    server, _service = create_query_server(
        variant, host="127.0.0.1", port=0, qos=spec
    )
    server.start()
    _wait_readyz(server.port)
    try:
        warm = _KeepAliveClient(server.port)
        try:
            # warm pass: compile/route warmup + seeds the stale cache so
            # degradation is possible from the first shed
            for q in range(min(n_users, 16)):
                warm({"user": f"u{q}", "num": 10})
        finally:
            warm.close()
        got = _overload_stage(server.port, n_users)
        got["qos_spec"] = spec
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/qos.json", timeout=5.0
        ) as r:
            snap = json.loads(r.read().decode("utf-8"))
        got["server_shed"] = snap.get("shed")
        got["server_degraded"] = snap.get("degraded")
        got["server_admitted"] = snap.get("admitted")
        return got
    finally:
        server.stop()


def _bench_resident_serving(n_queries: int) -> dict:
    """Device-resident classification serving (ISSUE 8): the same
    trained engine served through the resident scorer on BOTH feature
    wires — int8 and float32 — over an identical steady window. The
    artifact records per-request host→device bytes on each wire and
    their ratio (the acceptance bar is ≥3×, i.e. the int8 wire ships at
    most a third of the float32 bytes), the steady-state donation hit
    rate (bar: ≥0.95), retraces over the window (bar: zero — the warmup
    sweep owns every compile), and wire parity (fraction of label
    disagreements between the wires; bar: ≤0.001). In-process, no HTTP:
    this stage isolates the wire + dispatch path from socket churn."""
    import datetime as dtm

    import pio_tpu.templates  # noqa: F401  (registers engine factories)
    from pio_tpu.controller import ComputeContext
    from pio_tpu.data import Event
    from pio_tpu.server.query_server import QueryServerService
    from pio_tpu.storage import Storage
    from pio_tpu.storage.records import App
    from pio_tpu.templates.classification import Query
    from pio_tpu.workflow.core_workflow import run_train
    from pio_tpu.workflow.engine_json import build_engine, variant_from_dict

    home = os.environ["PIO_TPU_HOME"]
    saved = {
        k: os.environ.get(k)
        for k in (
            "PIO_TPU_DEVICE_RESIDENT", "PIO_TPU_SERVE_WIRE",
            "PIO_TPU_BATCH_BUCKETS", "PIO_TPU_BUCKET_WARMUP",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE",
            "PIO_STORAGE_SOURCES_RESIDENT_TYPE",
            "PIO_STORAGE_SOURCES_RESIDENT_PATH",
        )
    }
    os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "RESIDENT"
    os.environ["PIO_STORAGE_SOURCES_RESIDENT_TYPE"] = "sqlite"
    os.environ["PIO_STORAGE_SOURCES_RESIDENT_PATH"] = os.path.join(
        home, "resident_bench"
    )
    # force residency on regardless of backend: the stage measures the
    # wire, and the CPU smoke run must exercise the same code path the
    # accelerator run does
    os.environ["PIO_TPU_DEVICE_RESIDENT"] = "1"
    os.environ["PIO_TPU_BATCH_BUCKETS"] = "1,2,4,8"
    os.environ["PIO_TPU_BUCKET_WARMUP"] = "1"
    Storage.reset()
    try:
        app_id = Storage.get_meta_data_apps().insert(
            App(0, "bench-resident")
        )
        # three linearly separable plans over three attrs — the smoke
        # engine's toy, big enough to train and assert parity on
        le = Storage.get_levents()
        t0 = dtm.datetime(2026, 3, 1, tzinfo=dtm.timezone.utc)
        rng = np.random.default_rng(7)
        n = 0
        for plan, hot in (("basic", 0), ("premium", 1), ("pro", 2)):
            for _ in range(8):
                attrs = rng.integers(0, 3, size=3)
                attrs[hot] += 6
                props = {f"attr{j}": int(attrs[j]) for j in range(3)}
                props["plan"] = plan
                le.insert(
                    Event("$set", "user", f"u{n}", properties=props,
                          event_time=t0 + dtm.timedelta(minutes=n)),
                    app_id,
                )
                n += 1
        variant = variant_from_dict({
            "id": "bench-resident",
            "engineFactory": "templates.classification",
            "datasource": {"params": {"app_name": "bench-resident"}},
            "algorithms": [{"name": "logreg", "params": {}}],
        })
        engine, ep = build_engine(variant)
        ctx = ComputeContext.create(seed=0)
        run_train(engine, ep, variant, ctx=ctx)

        proto = np.array([9.0, 1.0, 1.0], np.float32)
        queries = [
            Query(attrs=tuple(float(v) for v in np.roll(proto, q % 3)))
            for q in range(n_queries)
        ]

        def one_wire(wire: str) -> tuple:
            os.environ["PIO_TPU_SERVE_WIRE"] = wire
            svc = QueryServerService(variant, ctx=ctx)
            if not svc._resident:
                raise RuntimeError("no resident scorer placed")
            sc = svc._resident[0]
            # snapshot AFTER the warmup sweep so the window's deltas are
            # pure steady state (the sweep's dispatches are deploy cost)
            h0, hit0, miss0 = (
                sc.h2d_bytes, sc.donation_hits, sc.donation_misses
            )
            r0 = svc._buckets.retraces
            labels = [svc._predict_one(q).label for q in queries]
            hits = sc.donation_hits - hit0
            misses = sc.donation_misses - miss0
            # device digest (ISSUE 17): the window is steady state, so
            # the watch's compile total must equal the warmup sweep's —
            # a live dispatch that compiled would show up here
            dp = svc.devwatch.payload()
            stats = {
                "wire": sc.wire,
                "h2d_bytes_per_request": round(
                    (sc.h2d_bytes - h0) / max(1, len(queries)), 1
                ),
                "donation_hit_rate": round(
                    hits / max(1, hits + misses), 4
                ),
                "retraces": svc._buckets.retraces - r0,
                "param_bytes": sc.placed_bytes,
                "device": {
                    "mode": dp.get("mode"),
                    "peak_bytes": max(
                        (d.get("peakBytes") or 0
                         for d in dp.get("devices") or []),
                        default=0,
                    ),
                    "compiles": (dp.get("compiles") or {}).get("total", 0),
                    "compile_seconds": round(sum(
                        float(r.get("seconds") or 0.0) for r in
                        ((dp.get("compiles") or {}).get("sites") or {})
                        .values()
                    ), 4),
                },
            }
            return labels, stats

        labels_i8, i8 = one_wire("int8")
        labels_f32, f32 = one_wire("float32")
        disagree = sum(
            1 for a, b in zip(labels_i8, labels_f32) if a != b
        )
        return {
            "queries": n_queries,
            "int8": i8,
            "float32": f32,
            "device": i8.get("device"),
            "h2d_ratio_f32_over_i8": round(
                f32["h2d_bytes_per_request"]
                / max(1e-9, i8["h2d_bytes_per_request"]), 2
            ),
            "donation_hit_rate": i8["donation_hit_rate"],
            "parity_delta": round(disagree / max(1, n_queries), 6),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        Storage.reset()


def _bench_evfront(n_queries: int) -> dict:
    """Event-loop HTTP front vs the threaded baseline (ISSUE 13): the
    same trained classification engine served over HTTP behind both
    fronts (``PIO_TPU_HTTP_FRONT``), each driven by a serial raw-socket
    keep-alive client so every request's wall time is a clean e2e
    sample. The threaded front serves the JSON wire; the evloop front
    serves the packed int8 wire — the deployment the tentpole ships.
    Records per-front qps / p50 / admit+parse+serialize share of e2e,
    the evloop attributedFraction, and the speedup. Acceptance bar:
    evloop-packed >= 1.5x threaded-json qps with lower p50 and a
    strictly smaller overhead share on the same host."""
    import datetime as dtm
    import socket as socketlib

    import pio_tpu.templates  # noqa: F401  (registers engine factories)
    from pio_tpu.controller import ComputeContext
    from pio_tpu.data import Event
    from pio_tpu.server import create_query_server
    from pio_tpu.server.http import PACKED_QUERY_CONTENT_TYPE
    from pio_tpu.storage import Storage
    from pio_tpu.storage.records import App
    from pio_tpu.workflow.core_workflow import run_train
    from pio_tpu.workflow.engine_json import build_engine, variant_from_dict

    saved = {
        k: os.environ.get(k)
        for k in (
            "PIO_TPU_DEVICE_RESIDENT", "PIO_TPU_SERVE_WIRE",
            "PIO_TPU_BATCH_BUCKETS", "PIO_TPU_BUCKET_WARMUP",
            "PIO_TPU_HTTP_FRONT",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE",
            "PIO_STORAGE_SOURCES_MEM_TYPE",
        )
    }
    # in-memory storage throughout: this stage measures the HTTP front
    # and the wire, not the storage backend — a sqlite-backed store
    # adds a per-request cost that compresses the front-to-front ratio
    os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "MEM"
    os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "MEM"
    os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "MEM"
    os.environ["PIO_STORAGE_SOURCES_MEM_TYPE"] = "memory"
    # packed wire requires a device-resident int8 scorer on both fronts
    os.environ["PIO_TPU_DEVICE_RESIDENT"] = "1"
    os.environ["PIO_TPU_SERVE_WIRE"] = "int8"
    os.environ["PIO_TPU_BATCH_BUCKETS"] = "1,2,4"
    os.environ["PIO_TPU_BUCKET_WARMUP"] = "1"
    Storage.reset()
    try:
        app_id = Storage.get_meta_data_apps().insert(App(0, "bench-evfront"))
        le = Storage.get_levents()
        t0 = dtm.datetime(2026, 3, 1, tzinfo=dtm.timezone.utc)
        rng = np.random.default_rng(7)
        n = 0
        for plan, hot in (("basic", 0), ("premium", 1), ("pro", 2)):
            for _ in range(8):
                attrs = rng.integers(0, 3, size=3)
                attrs[hot] += 6
                props = {f"attr{j}": int(attrs[j]) for j in range(3)}
                props["plan"] = plan
                le.insert(
                    Event("$set", "user", f"u{n}", properties=props,
                          event_time=t0 + dtm.timedelta(minutes=n)),
                    app_id,
                )
                n += 1
        variant = variant_from_dict({
            "id": "bench-evfront",
            "engineFactory": "templates.classification",
            "datasource": {"params": {"app_name": "bench-evfront"}},
            "algorithms": [{"name": "logreg", "params": {}}],
        })
        engine, ep = build_engine(variant)
        # no mesh: a size-1 mesh would pin a per-request explicit
        # device_put (sharded h2d path) on the scorer, burying the
        # front-to-front difference this stage exists to measure
        ctx = ComputeContext.local(seed=0)
        run_train(engine, ep, variant, ctx=ctx)

        body = {"attrs": [9.0, 1.0, 1.0]}
        json_payload = json.dumps(body).encode("utf-8")

        def mk_req(payload, ctype):
            return (b"POST /queries.json HTTP/1.1\r\nHost: x\r\n"
                    b"Content-Type: " + ctype.encode("latin-1") + b"\r\n"
                    b"Content-Length: " + str(len(payload)).encode() +
                    b"\r\n\r\n" + payload)

        def read_one(sock, buf):
            # pop one Content-Length-framed response off the socket
            while True:
                he = buf.find(b"\r\n\r\n")
                if he >= 0:
                    cl = 0
                    for hline in bytes(buf[:he]).lower().split(b"\r\n"):
                        if hline.startswith(b"content-length:"):
                            cl = int(hline.split(b":", 1)[1])
                    if len(buf) >= he + 4 + cl:
                        out = bytes(buf[he + 4:he + 4 + cl])
                        del buf[:he + 4 + cl]
                        return out
                chunk = sock.recv(65536)
                if not chunk:
                    raise RuntimeError("keep-alive connection closed")
                buf += chunk

        def window(port, req, total):
            # ONE keep-alive connection, serial requests: every sample
            # is clean unloaded e2e latency — a concurrent client would
            # fold queueing delay into p50
            s = socketlib.create_connection(("127.0.0.1", port))
            s.setsockopt(socketlib.IPPROTO_TCP, socketlib.TCP_NODELAY, 1)
            buf = bytearray()
            lats = []
            try:
                w0 = time.perf_counter()
                for _ in range(total):
                    q0 = time.perf_counter()
                    s.sendall(req)
                    read_one(s, buf)
                    lats.append(time.perf_counter() - q0)
                took = time.perf_counter() - w0
            finally:
                s.close()
            lats.sort()
            return {
                "qps": round(total / took, 1),
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 3),
            }

        def pooled(port, req, n_conns, total):
            # the ISSUE-13 deployment shape: many keep-alive client
            # connections, one outstanding request each, multiplexed in
            # ONE client thread (a thread-per-connection client would
            # spend more GIL time than either front under test). Each
            # sample is one connection's send→response wall time, so
            # p50 includes the server-side queueing the load creates.
            import selectors as sel_mod

            sel = sel_mod.DefaultSelector()
            socks = []
            for _ in range(n_conns):
                s = socketlib.create_connection(("127.0.0.1", port))
                s.setblocking(False)
                s.setsockopt(
                    socketlib.IPPROTO_TCP, socketlib.TCP_NODELAY, 1
                )
                socks.append(s)
                sel.register(s, sel_mod.EVENT_READ, [bytearray(), 0.0])
            sent = done = 0
            lats = []
            try:
                w0 = time.perf_counter()
                for s in socks:
                    sel.get_key(s).data[1] = time.perf_counter()
                    s.sendall(req)
                    sent += 1
                while done < total:
                    for key, _ in sel.select(10):
                        s, d = key.fileobj, key.data
                        buf = d[0]
                        chunk = s.recv(65536)
                        if not chunk:
                            raise RuntimeError(
                                "keep-alive connection closed"
                            )
                        buf += chunk
                        he = buf.find(b"\r\n\r\n")
                        while he >= 0:
                            cl = 0
                            for hline in bytes(buf[:he]).lower() \
                                    .split(b"\r\n"):
                                if hline.startswith(b"content-length:"):
                                    cl = int(hline.split(b":", 1)[1])
                            if len(buf) < he + 4 + cl:
                                break
                            del buf[:he + 4 + cl]
                            done += 1
                            lats.append(time.perf_counter() - d[1])
                            if sent < total:
                                d[1] = time.perf_counter()
                                s.sendall(req)
                                sent += 1
                            he = buf.find(b"\r\n\r\n")
                took = time.perf_counter() - w0
            finally:
                for s in socks:
                    sel.unregister(s)
                    s.close()
                sel.close()
            lats.sort()
            return {
                "qps": round(total / took, 1),
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 3),
            }

        def get_json(port, path):
            s = socketlib.create_connection(("127.0.0.1", port))
            s.sendall(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
            try:
                return json.loads(read_one(s, bytearray()))
            finally:
                s.close()

        servers = {}
        fronts = {}
        try:
            for front, wire in (("threaded", "json"), ("evloop", "packed")):
                os.environ["PIO_TPU_HTTP_FRONT"] = front
                server, svc = create_query_server(
                    variant, host="127.0.0.1", port=0, ctx=ctx
                )
                server.start()
                if wire == "json":
                    req = mk_req(json_payload, "application/json")
                else:
                    req = mk_req(svc.pack_query_body(body),
                                 PACKED_QUERY_CONTENT_TYPE)
                servers[front] = (server, req, wire)
                window(server.port, req, max(32, n_queries // 8))  # settle
            # Phase 1 — interleaved serial windows (best-of-2): clean
            # unloaded e2e latency, and the cumulative traffic the
            # /debug/hotpath.json stage shares are computed over stays
            # pure serial (pooled load would fold queueing into e2e and
            # mechanically shrink every stage's share)
            for _ in range(2):
                for front, (server, req, wire) in servers.items():
                    w = window(server.port, req, n_queries)
                    cur = fronts.setdefault(
                        front,
                        {"wire": wire, "serial_qps": w["qps"],
                         "serial_p50_ms": w["p50_ms"]},
                    )
                    cur["serial_qps"] = max(cur["serial_qps"], w["qps"])
                    cur["serial_p50_ms"] = min(
                        cur["serial_p50_ms"], w["p50_ms"]
                    )
            for front, (server, req, wire) in servers.items():
                hp = get_json(server.port, "/debug/hotpath.json")
                e2e = hp["e2e"]["avgMs"]
                overhead = sum(
                    st["avgMs"] for st in hp.get("stages", ())
                    if st["stage"] in ("admit", "parse", "serialize")
                )
                fronts[front]["overhead_share"] = round(
                    overhead / max(1e-9, e2e), 4
                )
                if front == "evloop":
                    fronts[front]["attributed_fraction"] = hp.get(
                        "attributedFraction"
                    )
            # Phase 2 — interleaved pooled windows (best-of-3): the
            # headline. Both servers stay up and windows alternate front
            # by front, so host scheduling drift on a shared single-core
            # box lands on BOTH sides of the ratio instead of biasing
            # whichever front ran second.
            for _ in range(3):
                for front, (server, req, wire) in servers.items():
                    p = pooled(server.port, req, 16, 2 * n_queries)
                    cur = fronts[front]
                    if p["qps"] > cur.get("qps", 0.0):
                        cur["qps"] = p["qps"]
                        cur["pooled_p50_ms"] = p["p50_ms"]
        finally:
            for server, _, _ in servers.values():
                server.stop()

        ev, th = fronts["evloop"], fronts["threaded"]
        # headline: pooled-load qps, unloaded e2e p50 (the pooled p50
        # is queueing-dominated at saturation and tracks conns/qps, not
        # the front's per-request cost)
        return {
            "qps": ev["qps"],
            "p50_ms": ev["serial_p50_ms"],
            "speedup_x": round(ev["qps"] / max(1e-9, th["qps"]), 2),
            "evloop": ev,
            "threaded": th,
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        Storage.reset()


def _overload_stage(port: int, n_users: int, n_threads=16,
                    per_thread=40) -> dict:
    """16 threads at full speed against a rate-limited server; unlike
    ``_concurrent_stage`` the client tolerates 429/503 — those ARE the
    measurement."""
    import concurrent.futures

    # hot key space intentionally larger than the server's stale cache:
    # refused requests split between degraded (cached) and shed (not)
    key_space = min(n_users, 64)

    def worker(t):
        client = _RawIngestClient(port, "/queries.json")
        lats = []
        counts = {"admitted": 0, "degraded": 0, "shed": 0}
        try:
            for q in range(per_thread):
                body = json.dumps({
                    "user":
                        f"u{((t * per_thread + q) * 104729) % key_space}",
                    "num": 10,
                }).encode()
                t0 = time.perf_counter()
                try:
                    status = client.post(body)
                except (ConnectionError, OSError, RuntimeError):
                    client.close()
                    client = _RawIngestClient(port, "/queries.json")
                    continue
                dt = time.perf_counter() - t0
                if status in (429, 503):
                    counts["shed"] += 1
                elif b"x-pio-degraded" in client.last_head.lower():
                    counts["degraded"] += 1
                else:
                    counts["admitted"] += 1
                    lats.append(dt)
        finally:
            client.close()
        return lats, counts

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(n_threads) as ex:
        results = list(ex.map(worker, range(n_threads)))
    wall = time.perf_counter() - t0
    lat = [l for ls, _ in results for l in ls]
    totals = {"admitted": 0, "degraded": 0, "shed": 0}
    for _, c in results:
        for k in totals:
            totals[k] += c[k]
    offered = sum(totals.values())
    ms = np.array(lat) * 1000.0 if lat else np.array([0.0])
    return {
        "offered": offered,
        "offered_qps": round(offered / wall, 1),
        "shed_rate": round(totals["shed"] / max(offered, 1), 3),
        "degraded_fraction": round(
            totals["degraded"] / max(offered, 1), 3
        ),
        "admitted": totals["admitted"],
        "admitted_p50_ms": round(float(np.percentile(ms, 50)), 3),
        "admitted_p99_ms": round(float(np.percentile(ms, 99)), 3),
    }


class _KeepAliveClient:
    """Persistent-connection query load-gen client (one per thread).
    Real SDKs/load balancers hold connections open — a fresh TCP
    handshake per request would measure the client's socket churn — and
    since round 5 the transport is the same raw-socket machinery as the
    ingest client (``_RawIngestClient``): on the single shared core,
    ``http.client``'s header build/parse cost ~100 µs/request, a third
    of the measured "serving QPS" budget going to the load generator
    itself. The JSON response is still parsed per call (a real SDK
    does)."""

    def __init__(self, port: int, path: str = "/queries.json"):
        self._port, self._path = port, path
        self._c = _RawIngestClient(port, path)

    def __call__(self, body: dict):
        payload = json.dumps(body).encode()
        for attempt in (0, 1):  # one reconnect on a dropped keep-alive
            try:
                status = self._c.post(payload)
                break
            except (ConnectionError, OSError, RuntimeError):
                if attempt:
                    raise
                self._c.close()
                self._c = _RawIngestClient(self._port, self._path)
        got = self._c.last_body
        if status >= 400:
            raise RuntimeError(
                f"{self._path}: HTTP {status} {got[:200]!r}"
            )
        return json.loads(got)

    def close(self):
        self._c.close()


def _wait_readyz(port: int, timeout: float = 30.0) -> float:
    """Poll ``GET /readyz`` until 200 (the orchestrator's view of
    startup); returns seconds waited."""
    import urllib.error
    import urllib.request

    t0 = time.perf_counter()
    deadline = t0 + timeout
    while time.perf_counter() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/readyz", timeout=2.0
            ) as r:
                if r.status == 200:
                    break
        except (urllib.error.HTTPError, OSError):
            pass
        time.sleep(0.02)
    return time.perf_counter() - t0


def _serve_single(variant, microbatch_us: int):
    from pio_tpu.server.query_server import create_query_server

    prev = os.environ.pop("PIO_TPU_SERVE_MICROBATCH_US", None)
    if microbatch_us:
        os.environ["PIO_TPU_SERVE_MICROBATCH_US"] = str(microbatch_us)
    t_boot = time.perf_counter()
    try:
        server, service = create_query_server(
            variant, host="127.0.0.1", port=0
        )
    finally:
        os.environ.pop("PIO_TPU_SERVE_MICROBATCH_US", None)
        if prev is not None:
            os.environ["PIO_TPU_SERVE_MICROBATCH_US"] = prev
    server.start()
    # time-to-ready: server construction (engine + model load) through
    # the first /readyz 200 — what a rolling deploy actually waits on
    _wait_readyz(server.port)
    server.time_to_ready_s = round(time.perf_counter() - t_boot, 4)
    return server, service, _KeepAliveClient(server.port)


def _scrape_metrics(port: int):
    """One ``GET /metrics`` scrape → ParsedMetrics (obs promparse)."""
    import urllib.request

    from pio_tpu.obs.promparse import parse_prometheus_text

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5.0
    ) as r:
        return parse_prometheus_text(r.read().decode("utf-8"))


def _metrics_delta(before, after) -> dict:
    """Server-side view of a bench stage: request/error counter deltas
    plus per-stage mean latency between two /metrics snapshots. Embedded
    in the artifact so a QPS regression can be localized (queue vs
    execute vs serialize) without re-running under a profiler."""
    fam_sum = lambda pm, name: sum(pm.family(name).values())
    out = {
        "queries": int(
            fam_sum(after, "pio_tpu_queries_total")
            - fam_sum(before, "pio_tpu_queries_total")
        ),
        "errors": int(
            fam_sum(after, "pio_tpu_query_errors_total")
            - fam_sum(before, "pio_tpu_query_errors_total")
        ),
    }
    stages: dict = {}
    for ls, cnt_after in after.family(
        "pio_tpu_query_stage_seconds_count"
    ).items():
        d = dict(ls)
        stage = d.pop("stage", "?")
        d["stage"] = stage
        dn = cnt_after - (
            before.value("pio_tpu_query_stage_seconds_count", **d) or 0.0
        )
        ds = (after.value("pio_tpu_query_stage_seconds_sum", **d) or 0.0) - (
            before.value("pio_tpu_query_stage_seconds_sum", **d) or 0.0
        )
        if dn > 0:  # aggregate across engine_id label values
            prev_n, prev_s = stages.get(stage, (0.0, 0.0))
            stages[stage] = (prev_n + dn, prev_s + ds)
    out["stage_avg_ms"] = {
        s: round(ds / dn * 1e3, 3) for s, (dn, ds) in sorted(stages.items())
    }
    return out


def _with_metrics_delta(port: int, stage_fn):
    """Run ``stage_fn()`` bracketed by /metrics snapshots; attach the
    delta as ``server_metrics`` (best-effort — a scrape failure never
    fails the bench stage)."""
    try:
        m0 = _scrape_metrics(port)
    except Exception:
        m0 = None
    got = stage_fn()
    if m0 is not None:
        try:
            got["server_metrics"] = _metrics_delta(m0, _scrape_metrics(port))
        except Exception as exc:
            _stage_failed("metrics delta scrape", exc)
    try:
        got["device"] = _device_block(port)
    except Exception as exc:
        _stage_failed("device scrape", exc)
    return got


def _device_block(port: int) -> dict:
    """Compact /device.json digest for a stage record (ISSUE 17): each
    stage runs against a fresh server, so the watch's totals ARE the
    stage's — peak bytes per device plus the compile-site attribution."""
    import urllib.request

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/device.json", timeout=5.0
    ) as r:
        data = json.loads(r.read().decode("utf-8"))
    compiles = data.get("compiles") or {}
    return {
        "mode": data.get("mode"),
        "peak_bytes": {
            str(d.get("device")): d.get("peakBytes")
            for d in data.get("devices") or []
        },
        "compiles": compiles.get("total", 0),
        "compile_seconds": round(sum(
            float(row.get("seconds") or 0.0)
            for row in (compiles.get("sites") or {}).values()
        ), 4),
        "headroom_bytes": data.get("headroomBytes"),
    }


def _concurrent_stage(port: int, n_users: int, n_threads=16,
                      per_thread=40, repeats=2) -> dict:
    """16 keep-alive client threads hammering /queries.json; best of
    ``repeats`` rounds (client and server share cores here, so one round
    can eat a scheduler hiccup)."""
    import concurrent.futures

    def worker(t):
        client = _KeepAliveClient(port)
        lats = []
        try:
            for q in range(per_thread):
                body = {
                    "user": f"u{((t * per_thread + q) * 104729) % n_users}",
                    "num": 10,
                }
                t0 = time.perf_counter()
                client(body)
                lats.append(time.perf_counter() - t0)
        finally:
            client.close()
        return lats

    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(n_threads) as ex:
            lat = [
                l for ls in ex.map(worker, range(n_threads)) for l in ls
            ]
        wall = time.perf_counter() - t0
        ms = np.array(lat) * 1000.0
        got = {
            "qps": round(len(lat) / wall, 1),
            "p50_ms": round(float(np.percentile(ms, 50)), 3),
            "p95_ms": round(float(np.percentile(ms, 95)), 3),
        }
        if best is None or got["qps"] > best["qps"]:
            best = got
    return best


def _drive_until_decided(port: int, service, n_users: int,
                         cap: int = 600) -> None:
    """Concurrent warm traffic until the adaptive micro-batcher settles."""
    import concurrent.futures

    def worker(t):
        client = _KeepAliveClient(port)
        try:
            for q in range(cap // 8):
                if service._batcher.mode in ("on", "off"):
                    return
                client({"user": f"u{(t * 131 + q) % n_users}", "num": 10})
        finally:
            client.close()

    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        list(ex.map(worker, range(8)))


_POOL_ENGINE_SRC = '''\
"""Spawn-importable serving engine for the bench worker-pool stage: wraps
pre-trained ALS factors stored beside this module (bench_factors.npz)."""
import os

import numpy as np

from pio_tpu.controller import (
    Algorithm, DataSource, Engine, FirstServing, IdentityPreparator,
)
from pio_tpu.data.bimap import BiMap
from pio_tpu.models.als import ALSFactors
from pio_tpu.templates.recommendation import (
    ALSModel, Query, predict_user_topn,
)

_HERE = os.path.dirname(os.path.abspath(__file__))


class PoolDataSource(DataSource):
    def read_training(self, ctx):
        return None


class PoolServeAlgorithm(Algorithm):
    query_class = Query

    def train(self, ctx, pd):
        z = np.load(os.path.join(_HERE, "bench_factors.npz"))
        uf, itf = z["user_factors"], z["item_factors"]
        return ALSModel(
            ALSFactors(user_factors=uf, item_factors=itf),
            BiMap({f"u{i}": i for i in range(uf.shape[0])}),
            BiMap({f"i{i}": i for i in range(itf.shape[0])}),
        )

    def predict(self, model, query):
        return predict_user_topn(
            model, query, model.user_index, model.item_index
        )

    def prepare_for_serving(self, model):
        model.scorer(warmup=True)
        return model


def engine():
    return Engine(
        PoolDataSource, IdentityPreparator,
        {"als": PoolServeAlgorithm}, FirstServing,
    )
'''


def _bench_pool_serving(factors, n_users: int, n_items: int) -> dict:
    """SO_REUSEPORT worker-pool serving stage. The pool multiplies
    host-path QPS by the worker count ON MULTI-CORE HOSTS; this records
    whatever the current host gives it plus ``host_cores`` so the number
    reads honestly (on a 1-core box the pool pays context-switch tax)."""
    import sys as _sys

    from pio_tpu.server.worker_pool import ServingPool
    from pio_tpu.workflow.core_workflow import run_train
    from pio_tpu.workflow.engine_json import build_engine, variant_from_dict

    home = os.environ["PIO_TPU_HOME"]
    np.savez(
        os.path.join(home, "bench_factors.npz"),
        user_factors=factors.user_factors,
        item_factors=factors.item_factors,
    )
    with open(os.path.join(home, "pio_bench_pool_engine.py"), "w") as f:
        f.write(_POOL_ENGINE_SRC)
    # spawned workers import the factory by dotted path — they need the
    # module on THEIR sys.path (PYTHONPATH propagates; sys.path doesn't)
    if home not in _sys.path:
        _sys.path.insert(0, home)
    os.environ["PYTHONPATH"] = (
        home + os.pathsep + os.environ.get("PYTHONPATH", "")
    )
    variant = variant_from_dict({
        "id": "bench-recommendation-pool",
        "version": "1",
        "engineFactory": "pio_bench_pool_engine:engine",
        "algorithms": [{"name": "als", "params": {}}],
    })
    engine, ep = build_engine(variant)
    run_train(engine, ep, variant)

    cores = len(os.sched_getaffinity(0))
    n_workers = max(2, min(4, cores))
    # no device_worker on the HEADLINE pool number: it measures
    # independent per-worker serving, the fast path on a homogeneous
    # pool — funneling through one lane drainer serializes dispatch.
    # The laned variant is measured separately below as ``laned_qps``
    # so the artifact shows both sides of that trade.
    pool = ServingPool(
        variant, host="127.0.0.1", port=0, n_workers=n_workers
    )
    t_boot = time.perf_counter()
    pool.start()
    try:
        # wait_ready polls /readyz, so this is spawn → first worker READY
        pool.wait_ready(timeout=180)
        time_to_ready_s = round(time.perf_counter() - t_boot, 4)
        warm = _KeepAliveClient(pool.port)
        for _ in range(2 * n_workers):  # hit every worker's first-compile
            warm({"user": "u1", "num": 10})
            warm.close()
            warm = _KeepAliveClient(pool.port)
        warm.close()
        # pool /metrics is pool-wide (shared-memory aggregation), so one
        # scrape on whatever worker answers covers every sibling
        got = _with_metrics_delta(
            pool.port, lambda: _concurrent_stage(pool.port, n_users)
        )
        got["workers"] = n_workers
        got["host_cores"] = cores
        got["time_to_ready_s"] = time_to_ready_s
        # routed pass (ISSUE 18): the SAME live pool fronted by the
        # serving router, so routed_qps vs the direct number above
        # isolates the fabric's relay cost on this host; the overhead
        # metric is the concurrent p50 delta through the extra hop.
        try:
            from pio_tpu.server.routerd import create_router_server

            rs = create_router_server(
                [("pool", f"http://127.0.0.1:{pool.port}")],
                host="127.0.0.1", port=0, interval_s=1.0,
            ).start()
            rs.service.start()
            try:
                _wait_readyz(rs.port)
                rg = _concurrent_stage(rs.port, n_users)
                got["routed_qps"] = rg["qps"]
                got["routed_p50_ms"] = rg.get("p50_ms")
                got["routed_p95_ms"] = rg.get("p95_ms")
                if rg.get("p50_ms") is not None and \
                        got.get("p50_ms") is not None:
                    got["router_overhead_ms"] = round(
                        rg["p50_ms"] - got["p50_ms"], 3
                    )
                # shadow-mirroring pass (ISSUE 19): the same routed hop
                # with a live rollout parked in shadow, mirroring 100%
                # of queries back at the pool. The p50 delta vs the
                # plain routed pass is the mirror's relay-path cost —
                # the contract is fire-and-forget off the hot path, so
                # the delta prices the member's doubled load, not a
                # synchronous mirror hop.
                try:
                    import urllib.request as _ur

                    with _ur.urlopen(
                        f"http://127.0.0.1:{pool.port}/deploy.json",
                        timeout=5,
                    ) as r:
                        iid = json.loads(
                            r.read().decode("utf-8")
                        )["engineInstanceId"]
                    body = json.dumps({
                        "engineInstanceId": iid,
                        "targets": f"127.0.0.1:{pool.port}",
                        "by": "bench", "auto": False,
                        "shadowRate": 1.0, "shadowMinSamples": 1,
                        "shadowHoldSeconds": 3600.0,
                        "judgeIntervalSeconds": 1.0,
                    }).encode("utf-8")
                    req = _ur.Request(
                        f"http://127.0.0.1:{rs.port}/rollout",
                        data=body,
                        headers={"Content-Type": "application/json"},
                    )
                    with _ur.urlopen(req, timeout=30):
                        pass
                    deadline = time.time() + 30
                    while time.time() < deadline:
                        with _ur.urlopen(
                            f"http://127.0.0.1:{rs.port}/rollout.json",
                            timeout=5,
                        ) as r:
                            stage = json.loads(
                                r.read().decode("utf-8")
                            )["stage"]
                        if stage == "shadow":
                            break
                        time.sleep(0.1)
                    sg = _concurrent_stage(rs.port, n_users)
                    got["shadow_qps"] = sg["qps"]
                    got["shadow_p50_ms"] = sg.get("p50_ms")
                    if sg.get("p50_ms") is not None and \
                            rg.get("p50_ms") is not None:
                        got["shadow_overhead_ms"] = round(
                            sg["p50_ms"] - rg["p50_ms"], 3
                        )
                    abort = _ur.Request(
                        f"http://127.0.0.1:{rs.port}/rollout/abort",
                        data=b"{}",
                        headers={"Content-Type": "application/json"},
                    )
                    with _ur.urlopen(abort, timeout=30):
                        pass
                except Exception as exc:
                    _stage_failed("shadow mirroring stage", exc)
            finally:
                rs.service.stop()
                rs.stop()
        except Exception as exc:
            _stage_failed("routed serving stage", exc)
    finally:
        pool.stop()

    # laned pass: same engine, same worker count, but every worker
    # forwards through the shared-memory batch lane to the designated
    # device worker (one process owns the accelerator; siblings are I/O
    # front-ends). Recorded alongside the headline so pool_qps vs
    # pool_laned_qps quantifies the funnel cost on THIS host.
    if _holds_accelerator():
        got["laned"] = dict(_NEEDS_CHIP_CHILD)
        return got
    try:
        laned = ServingPool(
            variant, host="127.0.0.1", port=0, n_workers=n_workers,
            device_worker=True,
        )
        t_boot = time.perf_counter()
        laned.start()
        try:
            laned.wait_ready(timeout=180)
            got["laned_time_to_ready_s"] = round(
                time.perf_counter() - t_boot, 4
            )
            warm = _KeepAliveClient(laned.port)
            for _ in range(2 * n_workers):
                warm({"user": "u1", "num": 10})
                warm.close()
                warm = _KeepAliveClient(laned.port)
            warm.close()
            lg = _concurrent_stage(laned.port, n_users)
            got["laned_qps"] = lg["qps"]
            got["laned_p50_ms"] = lg.get("p50_ms")
            got["laned_p95_ms"] = lg.get("p95_ms")
        finally:
            laned.stop()
    except Exception as exc:
        _stage_failed("laned pool stage", exc)
    return got


def _bench_sharded_serving(factors, n_users: int, n_items: int,
                           baseline_qps=None) -> dict:
    """Mesh-worker pool stage: worker 0 owns the whole device mesh and
    serves with partition-rule-sharded factor tables (ISSUE 10). Runs on
    the devices this process was given (the spawned worker inherits the
    environment): skipped on an accelerator, which this process holds,
    and on fewer than two devices — a simulated CPU mesh is the caller's
    choice (``XLA_FLAGS=--xla_force_host_platform_device_count=8``), and
    then the number mostly proves the sharded dispatch path and its
    retrace behavior. ``scaling_x`` is sharded QPS over the
    single-device laned pool."""
    import sys as _sys
    import urllib.request

    import jax

    if _holds_accelerator():
        return dict(_NEEDS_CHIP_CHILD)
    n_devices = len(jax.devices())
    if n_devices < 2:
        return {"skipped": "needs more than one device"}

    from pio_tpu.server.worker_pool import ServingPool
    from pio_tpu.workflow.core_workflow import run_train
    from pio_tpu.workflow.engine_json import build_engine, variant_from_dict

    home = os.environ["PIO_TPU_HOME"]
    np.savez(
        os.path.join(home, "bench_factors.npz"),
        user_factors=factors.user_factors,
        item_factors=factors.item_factors,
    )
    with open(os.path.join(home, "pio_bench_pool_engine.py"), "w") as f:
        f.write(_POOL_ENGINE_SRC)
    if home not in _sys.path:
        _sys.path.insert(0, home)
    os.environ["PYTHONPATH"] = (
        home + os.pathsep + os.environ.get("PYTHONPATH", "")
    )
    variant = variant_from_dict({
        "id": "bench-recommendation-sharded",
        "version": "1",
        "engineFactory": "pio_bench_pool_engine:engine",
        "algorithms": [{"name": "als", "params": {}}],
    })
    engine, ep = build_engine(variant)
    run_train(engine, ep, variant)

    cores = len(os.sched_getaffinity(0))
    n_workers = max(2, min(4, cores))
    got: dict = {"workers": n_workers, "mesh_devices": n_devices}
    pool = ServingPool(
        variant, host="127.0.0.1", port=0, n_workers=n_workers,
        mesh_worker=True,
    )
    t_boot = time.perf_counter()
    pool.start()
    try:
        pool.wait_ready(timeout=180)
        got["time_to_ready_s"] = round(time.perf_counter() - t_boot, 4)
        warm = _KeepAliveClient(pool.port)
        for _ in range(2 * n_workers):
            warm({"user": "u1", "num": 10})
            warm.close()
            warm = _KeepAliveClient(pool.port)
        warm.close()
        sg = _concurrent_stage(pool.port, n_users)
        got["qps"] = sg["qps"]
        got["p50_ms"] = sg.get("p50_ms")
        got["p95_ms"] = sg.get("p95_ms")
        # the kernel picks which worker answers /stats.json; retry
        # until the mesh owner (the only one with sharding enabled)
        # answers, so the artifact records the actual placement
        for _ in range(16):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{pool.port}/stats.json", timeout=5.0
            ) as r:
                st = json.loads(r.read().decode("utf-8"))
            sh = st.get("sharding") or {}
            if sh.get("enabled"):
                got["sharding"] = sh
                break
    finally:
        pool.stop()
    if baseline_qps is None:
        # no laned pool_qps to compare against (pool stage failed):
        # measure the single-device funnel here
        try:
            base = ServingPool(
                variant, host="127.0.0.1", port=0, n_workers=n_workers,
                device_worker=True,
            )
            base.start()
            try:
                base.wait_ready(timeout=180)
                warm = _KeepAliveClient(base.port)
                for _ in range(2 * n_workers):
                    warm({"user": "u1", "num": 10})
                    warm.close()
                    warm = _KeepAliveClient(base.port)
                warm.close()
                baseline_qps = _concurrent_stage(base.port, n_users)["qps"]
            finally:
                base.stop()
        except Exception as exc:
            _stage_failed("sharded baseline pool", exc)
    if baseline_qps:
        got["baseline_qps"] = baseline_qps
        got["scaling_x"] = round(got["qps"] / baseline_qps, 3)
    return got


# ------------------------------------------------------------- secondary
def _bench_classification(ctx, scale: float) -> dict:
    """BASELINE config #2: LogReg (treeAggregate ≡ psum all-reduce).
    examples/sec = rows touched per optimizer iteration × iterations.

    Best-vs-best dtype policy: the accelerator side opts into the int8
    feature wire (quarters the dominant host→device shipment; per-column
    scales fold into the weights on device, so the learned model still
    serves raw floats — the library default stays float32), the CPU
    anchor runs float32 (quantized/bf16 wires only slow a local-RAM CPU
    run, inflating the ratio). Each platform at its best config, with
    ``train_acc`` recorded on BOTH so the ratio is accuracy-honest.

    Variance discipline (round-5): MEDIAN of 5 timed runs on each side —
    the recorded ratio previously swung ~1.7× run-to-run on the
    contended single-core host under best-of-2."""
    import jax

    from pio_tpu.models.logreg import LogRegConfig, train_logreg

    n, d, c = int(100_000 * scale), 256, 10
    iters = 100  # a realistic full-batch training length; also amortizes
    # the one-time [N, D] feature upload like the headline's 10 iterations
    rng = np.random.default_rng(1)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d, c))
    y = np.argmax(X @ w_true, axis=1).astype(np.int32)
    plat = (
        list(ctx.mesh.devices.flat)[0].platform
        if ctx is not None and ctx.mesh is not None
        else jax.default_backend()
    )
    cfg = LogRegConfig(
        iterations=iters, learning_rate=0.05,
        input_dtype="float32" if plat == "cpu" else "int8",
    )
    # the stage is h2d-wire-bound on a slow host link (the feature
    # upload): the ratio tracks the link, so every recorded value
    # carries its own same-moment probe
    link = _link_meta(plat != "cpu")
    times, model = _timed_runs(
        lambda: train_logreg(ctx, X, y, c, cfg), repeats=5
    )
    dt = times[len(times) // 2]
    return {
        "value": n * iters / dt,
        "train_acc": round(float((model.predict(X) == y).mean()), 4),
        "wire": cfg.input_dtype,
        "anchor_note": "median-of-5 each side, same program+depth",
        **link,
    }


def _bench_similarproduct(ctx, scale: float) -> dict:
    """BASELINE config #3: implicit ALS (MLlib trainImplicit analog).

    Round-5 discipline: median-of-5 on each side plus a same-moment link
    probe, so a recorded ratio shift is attributable — link swing vs
    real regression (the r3→r4 record showed 5.3×→4.11× with no
    code change on this path)."""
    from pio_tpu.models.als import ALSConfig, train_als

    n_edges = int(5_000_000 * scale)
    n_users, n_items = int(50_000 * scale) + 64, int(20_000 * scale) + 64
    iters = 10  # reference template default depth (see headline note)
    rng = np.random.default_rng(2)
    u = rng.integers(0, n_users, n_edges).astype(np.int32)
    i = (rng.random(n_edges) ** 2 * n_items).astype(np.int32)
    r = np.ones(n_edges, np.float32)
    cfg = ALSConfig(rank=16, iterations=iters, reg=0.1, implicit=True,
                    alpha=40.0)
    link = _link_meta(_on_accelerator(ctx))
    times, _ = _timed_runs(
        lambda: train_als(ctx, u, i, r, n_users, n_items, cfg), repeats=5
    )
    dt = times[len(times) // 2]
    return {
        "value": n_edges * iters / dt,
        "anchor_note": "median-of-5 each side, same program+depth",
        **link,
    }


def _on_accelerator(ctx) -> bool:
    """True when the context's devices are not host-CPU (the link probe
    is meaningless — and wasteful — on the anchor side)."""
    import jax

    if ctx is not None and ctx.mesh is not None:
        return list(ctx.mesh.devices.flat)[0].platform != "cpu"
    return jax.default_backend() != "cpu"


def _bench_textclass(scale: float) -> dict:
    """BASELINE config #4: the embedding-bag hot op — Pallas kernel vs
    the plain-XLA gather+einsum lowering. Beyond raw tokens/sec, this
    stage records the kernel's ACTUAL wins as artifacts:

    - accuracy: max relative error vs a float64 host reference — the
      XLA default contracts in bf16 on the MXU (~2 decimal digits); the
      kernel accumulates f32 on the VPU. ``xla_f32_tokens_per_sec`` is
      the apples-to-apples comparison at equal (f32) accuracy.
    - memory: XLA materializes the gathered [B, L, D] intermediate in
      HBM; the kernel streams rows through an O(depth·D) VMEM ring. The
      large-shape stage runs a bag batch whose XLA intermediate alone
      exceeds v5e HBM — the kernel must survive it, XLA cannot.
    """
    import jax
    import jax.numpy as jnp

    from pio_tpu.ops.embedding import (
        _embedding_bag_pallas, _embedding_bag_xla, _use_pallas,
    )

    V, D = 50_000, 256
    B, L = int(4096 * scale) or 8, 64
    rng = np.random.default_rng(3)
    table_h = rng.normal(size=(V, D)).astype(np.float32)
    ids_h = rng.integers(0, V, (B, L)).astype(np.int32)
    w_h = rng.random((B, L)).astype(np.float32)
    table = jax.device_put(table_h)
    ids = jax.device_put(ids_h)
    w = jax.device_put(w_h)
    tokens = B * L

    K = 8  # chained applications per timed dispatch — amortizes the
    # dispatch round trip; the scalar pulled to host forces real
    # execution

    def timed(fn):
        def many(t, i, w):
            def body(k, acc):
                # roll by the loop index so no iteration can be hoisted
                out = fn(t, jnp.roll(i, k, axis=0), w)
                return acc + jnp.sum(out)

            return jax.lax.fori_loop(0, K, body, jnp.float32(0))

        jf = jax.jit(many)
        dt, _ = _best_of(
            lambda: float(jf(table, ids, w)), repeats=3
        )
        # accuracy sample from the JITTED op — what the templates run
        # (eager and jitted einsum pick different default precisions)
        return K * tokens / dt, np.asarray(jax.jit(fn)(table, ids, w))

    def xla_unpinned(table, ids, w):
        # the raw default lowering (no pinned precision) — reference
        # point for what the shipped op's HIGHEST pin costs
        rows = table[ids]
        return jnp.einsum(
            "bld,bl->bd", rows.astype(jnp.float32),
            w.astype(jnp.float32),
        )

    xla_rate, xla_out = timed(_embedding_bag_xla)  # shipped path (f32)
    out = {"xla_tokens_per_sec": round(xla_rate, 1)}
    # f64 host reference for the accuracy artifact (sampled rows keep
    # the host cost bounded at full scale)
    sample = np.arange(0, B, max(1, B // 256))
    ref = np.einsum(
        "bld,bl->bd",
        table_h.astype(np.float64)[ids_h[sample]],
        w_h[sample].astype(np.float64),
    )
    denom = max(1e-9, float(np.abs(ref).max()))

    def max_err(got):
        return float(
            np.abs(np.asarray(got)[sample].astype(np.float64) - ref).max()
        ) / denom

    acc = {"xla_max_err": round(max_err(xla_out), 8)}
    unp_rate, unp_out = timed(xla_unpinned)
    out["xla_unpinned_default_tokens_per_sec"] = round(unp_rate, 1)
    acc["xla_unpinned_default_max_err"] = round(max_err(unp_out), 8)
    if _use_pallas(table):
        p_rate, p_out = timed(_embedding_bag_pallas)
        out["pallas_tokens_per_sec"] = round(p_rate, 1)
        out["pallas_speedup_vs_xla"] = round(p_rate / xla_rate, 3)
        acc["pallas_max_err"] = round(max_err(p_out), 8)
    out["accuracy"] = acc
    out["memory_mb"] = {
        # what each path needs beyond inputs + outputs at this shape
        "xla_intermediate": round(B * L * D * 4 / 1e6, 1),
        "pallas_scratch": round(4 * D * 4 / 1e6, 4),
    }

    if _use_pallas(table) and scale >= 0.5:
        # large-shape survival: the gathered [B, L, D] f32 intermediate
        # is ~24 GB > v5e HBM; the kernel's O(B·D) output + VMEM ring
        # fits easily
        Bl, Ll = 16_384, 1_436
        ids_l = jax.device_put(
            rng.integers(0, V, (Bl, Ll)).astype(np.int32)
        )
        w_l = jax.device_put(rng.random((Bl, Ll)).astype(np.float32))
        big = {"B": Bl, "L": Ll,
               "xla_intermediate_gb": round(Bl * Ll * D * 4 / 1e9, 1)}
        try:
            jf = jax.jit(
                lambda t, i, w: jnp.sum(_embedding_bag_pallas(t, i, w))
            )
            dt, _ = _best_of(
                lambda: float(jf(table, ids_l, w_l)), repeats=1
            )
            big["pallas_tokens_per_sec"] = round(Bl * Ll / dt, 1)
        except Exception as exc:
            big["pallas_error"] = str(exc)[:200]
        big["xla"] = "skipped: intermediate alone exceeds v5e HBM"
        out["large_shape"] = big
    return out


#: two-tower bench shape, shared with the achieved-GFLOP/s computation in
#: main() — keep them in one place so a tuned config can't silently
#: desync the published utilization number
_TT_BATCH, _TT_EMBED, _TT_HIDDEN, _TT_OUT = 4096, 64, 128, 64


def _bench_twotower(ctx, scale: float) -> dict:
    """BASELINE config #5: two-tower retrieval training, examples/sec
    (one example = one positive pair through a contrastive step).

    Round-5 finding: training is ONE compiled scan over device-resident
    ids — the e2e cost was ~78% the OUTPUT readback of the full vector
    tables over the host link, not any input feed. The stage opts
    into the bf16 table wire (half those bytes; tables are retrieval
    embeddings) and records the phase split so the achieved-GFLOP/s
    figure carries its real bound."""
    from pio_tpu.models.two_tower import TwoTowerConfig, train_two_tower
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh

    n_pairs = int(500_000 * scale)
    n_users, n_items = int(100_000 * scale) + 64, int(50_000 * scale) + 64
    steps, batch = 200, _TT_BATCH  # fixed transfer costs dominate short runs
    # (measured ~3 ms/step vs ~1.8 s fixed); 200 steps is a realistic
    # retrieval-training depth
    rng = np.random.default_rng(4)
    u = rng.integers(0, n_users, n_pairs).astype(np.int32)
    i = rng.integers(0, n_items, n_pairs).astype(np.int32)
    on_acc = _on_accelerator(ctx)
    cfg = TwoTowerConfig(
        embed_dim=_TT_EMBED, hidden=_TT_HIDDEN, out_dim=_TT_OUT,
        steps=steps, batch_size=batch,
        # bf16 emulation only slows the CPU anchor — each side at its
        # best config, like the classification wire policy
        table_wire="bfloat16" if on_acc else "float32",
    )
    mesh = build_mesh(  # the tower shardings need a model axis too
        MeshSpec(data=-1, model=1), devices=list(ctx.mesh.devices.flat)
    )
    # table-READBACK-bound (see phases): probe the d2h direction, which
    # need not match the upload direction
    link = _link_meta(on_acc, d2h=True)
    times, _ = _timed_runs(
        lambda: train_two_tower(mesh, u, i, n_users, n_items, cfg),
        repeats=5 if on_acc else 3,
    )
    dt = times[len(times) // 2]
    out = {
        "value": steps * batch / dt,
        "table_wire": cfg.table_wire,
        "anchor_note": "median each side, same program+depth",
        **link,
    }
    if on_acc:
        st = {}
        train_two_tower(mesh, u, i, n_users, n_items, cfg, stats=st)
        out["phases"] = {
            k: round(v, 3) if isinstance(v, float) else v
            for k, v in st.items()
        }
    return out


def _bench_train_streamed(ctx, scale: float) -> dict:
    """ISSUE 14: the streamed training feed (parallel/stream.py) —
    examples/sec/chip for a streamed two-tower run on the full mesh,
    the profiled h2d/device phase split, the achieved h2d/compute
    overlap ratio, and the mesh-vs-single-chip scaling factor.

    The overlap ratio comes from a controlled executor-level probe (a
    profiled serialized pass vs an overlapped double-buffered pass over
    the SAME chunk workload) rather than from the e2e trainer, whose
    wall time also carries init/readback and would drown the feed
    phases in noise. record_overlap_ratio publishes the gauge."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.models.two_tower import TwoTowerConfig, train_two_tower
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh
    from pio_tpu.parallel.stream import record_overlap_ratio, stream_feed

    n_pairs = max(4096, int(200_000 * scale))
    n_users, n_items = int(50_000 * scale) + 64, int(20_000 * scale) + 64
    # batch capped so the epoch always has several spans to stream,
    # even at smoke scale (one batch = nothing to overlap)
    steps = 60
    batch = max(256, min(_TT_BATCH, n_pairs // 8))
    rng = np.random.default_rng(14)
    u = rng.integers(0, n_users, n_pairs).astype(np.int32)
    i = rng.integers(0, n_items, n_pairs).astype(np.int32)
    cfg = TwoTowerConfig(
        embed_dim=_TT_EMBED, hidden=_TT_HIDDEN, out_dim=_TT_OUT,
        steps=steps, batch_size=batch, stream="on",
    )
    devices = list(ctx.mesh.devices.flat)
    mesh = build_mesh(MeshSpec(data=-1, model=1), devices=devices)

    times, _ = _timed_runs(
        lambda: train_two_tower(mesh, u, i, n_users, n_items, cfg),
        repeats=3,
    )
    rate = steps * batch / times[len(times) // 2]
    st: dict = {}
    # device accounting for the profiled pass (ISSUE 17): stream-carry
    # ledger + train_step compile attribution land in this watch
    from pio_tpu.obs import devicewatch

    dw = devicewatch.DeviceWatch()
    with devicewatch.watching(dw, sample=False):
        train_two_tower(mesh, u, i, n_users, n_items, cfg, stats=st)
        dw.sample()
    dw_payload = dw.payload()

    # single-chip anchor: same streamed program without collectives
    t_single, _ = _timed_runs(
        lambda: train_two_tower(None, u, i, n_users, n_items, cfg),
        repeats=3,
    )
    rate_single = steps * batch / t_single[len(t_single) // 2]

    # executor-level overlap probe: heavy async chunk programs vs
    # multi-MB puts — the serialized pass measures the phases, the
    # double-buffered pass measures how much of the put time hides
    side = 512 if scale < 1 else 1024
    n_chunks, burn_iters = 6, 4
    host_chunks = [
        rng.normal(size=(side, side)).astype(np.float32) * 0.01
        for _ in range(n_chunks)
    ]

    @jax.jit
    def _burn(carry, dev):
        x = carry
        for _ in range(burn_iters):
            x = jnp.tanh(x @ dev)
        return x

    def _probe(stats=None, lookahead=0):
        from pio_tpu.obs import monotonic_s

        t0 = monotonic_s()
        out = stream_feed(
            list(range(n_chunks)),
            encode=lambda c: host_chunks[c],
            dispatch=lambda carry, dev, _i: _burn(carry, dev),
            init_carry=lambda: jnp.eye(side, dtype=jnp.float32),
            lookahead=lookahead,
            stats=stats,
        )
        jax.block_until_ready(out)
        return monotonic_s() - t0

    pst: dict = {}
    _probe(stats=pst)  # warm compile + serialized phases
    pst = {}
    _probe(stats=pst)
    wall = min(_probe(lookahead=2) for _ in range(3))
    overlap = record_overlap_ratio(pst["h2d_s"], pst["device_s"], wall)

    return {
        "value": rate / max(1, len(devices)),
        "examples_per_sec": round(rate, 1),
        "sharded_scaling_x": round(rate / rate_single, 2),
        "n_devices": len(devices),
        "overlap_ratio": round(overlap, 3),
        "probe_h2d_s": round(pst["h2d_s"], 4),
        "probe_device_s": round(pst["device_s"], 4),
        "probe_wall_s": round(wall, 4),
        "device": {
            "mode": dw_payload.get("mode"),
            "peak_bytes": max(
                (d.get("peakBytes") or 0
                 for d in dw_payload.get("devices") or []),
                default=0,
            ),
            "compiles": (dw_payload.get("compiles") or {}).get("total", 0),
        },
        "phases": {
            k: round(v, 3) if isinstance(v, float) else v
            for k, v in st.items()
        },
    }


#: v5e bf16 peak, GFLOP/s — the roofline anchor for utilization notes
_V5E_BF16_PEAK_GFLOPS = 197_000.0


def _v5e_peak_note(gflops: float, what: str):
    """``"x% of v5e bf16 peak — <what>"`` when the run's device IS a
    v5e; None on any other device (the peak is not its peak)."""
    import jax

    if "v5 lite" not in jax.devices()[0].device_kind.lower():
        return None
    return f"{gflops / _V5E_BF16_PEAK_GFLOPS:.2%} of v5e bf16 peak — {what}"


def _bench_seqrec(ctx, scale: float) -> dict:
    """Sequence-recommender (transformer) train step — the second
    MXU-capable workload (beyond the reference's template set; no
    Spark analog, so no vs_baseline). Reports tokens/sec and achieved
    matmul GFLOP/s from the analytic count (attention projections +
    scores/values + FFN + the vocab-parallel CE logits matmul, ×3 for
    backward; embedding gathers excluded → conservative)."""
    from pio_tpu.models.seqrec import SeqRecConfig, train_seqrec
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh

    n, t = max(8, int(256 * scale)), 128
    d, heads, layers, ffn = 256, 8, 4, 1024
    vocab, steps = 20_000, 30
    rng = np.random.default_rng(6)
    lens = rng.integers(t // 2, t, n)
    seqs = np.zeros((n, t), np.int32)
    for r in range(n):
        seqs[r, : lens[r]] = rng.integers(1, vocab + 1, lens[r])
    cfg = SeqRecConfig(
        d_model=d, n_heads=heads, n_layers=layers, ffn=ffn,
        max_len=t, steps=steps,
    )
    mesh = build_mesh(
        MeshSpec(data=-1, pipe=1, seq=1, model=1),
        devices=list(ctx.mesh.devices.flat),
    )
    dt, _ = _best_of(
        lambda: train_seqrec(mesh, seqs, vocab, cfg), repeats=2
    )
    tokens = n * t * steps
    fwd_per_token = (
        layers * (8 * d * d + 4 * t * d + 4 * d * ffn) + 2 * d * vocab
    )
    gflops = 3 * fwd_per_token * tokens / dt / 1e9
    return {
        "tokens_per_sec": round(tokens / dt, 1),
        "achieved_gflops": round(gflops, 1),
        "roofline_note": _v5e_peak_note(
            gflops, "e2e wall-clock incl. host batch staging; f32 params"
        ),
    }


def _bench_rank_sweep(ctx, scale: float) -> dict:
    """ALS rank scaling {16, 64, 128}: the K²-per-edge normal-equation
    term pushes the MXU where rank 16 is gather/transfer-bound. Reports
    end-to-end + device-phase rates and achieved GFLOP/s (normal-equation
    build term only, 4·K·(K+1) FLOPs per edge per iteration — solves and
    packing excluded, so the figure is conservative)."""
    from pio_tpu.models.als import ALSConfig, train_als

    iters = 4
    out = {}
    # entity counts shrink with rank: the per-entity K×K normal-equation
    # tensor is rank²·4 bytes/entity and the batched-CG solver carries
    # ~3 copies — 80k entities at rank 128 needs >20 GB HBM (measured
    # OOM on 16 GB v5e); 16k keeps the whole sweep resident
    sizes = {16: 80_000, 64: 40_000, 128: 16_000}
    for rank, U0 in sizes.items():
        E = int(8_000_000 * scale)
        U, I = int(U0 * scale) + 64, int(U0 * scale) // 2 + 64
        rng = np.random.default_rng(7)
        u = rng.integers(0, U, E).astype(np.int32)
        i = (rng.random(E) ** 2 * I).astype(np.int32)
        r = (rng.integers(1, 11, E) * 0.5).astype(np.float32)
        cfg = ALSConfig(rank=rank, iterations=iters, reg=0.1)
        try:
            # repeats=1: the sweep is a scaling curve, not the headline —
            # one warm timed run per rank bounds the sweep's wall-clock
            dt, _ = _best_of(
                lambda: train_als(ctx, u, i, r, U, I, cfg), repeats=1
            )
            st = {}
            train_als(ctx, u, i, r, U, I, cfg, stats=st)
        except Exception as exc:  # one rank failing must not kill the curve
            _stage_failed(f"rank sweep rank={rank}", exc)
            continue
        flops = 4 * rank * (rank + 1) * E * iters
        out[f"rank{rank}"] = {
            "examples_per_sec": round(E * iters / dt, 1),
            "device_examples_per_sec": round(
                E * iters / st["device_s"], 1
            ),
            "achieved_gflops": round(flops / st["device_s"] / 1e9, 1),
        }
    return out


class _RawIngestClient:
    """Minimal keep-alive load-gen client: preformatted header template,
    single-pass status/Content-Length response scan. ``http.client``
    costs ~100 µs/request building and parsing MIME headers — on the
    single shared core that was a third of the measured "ingest rate",
    i.e. the load generator throttling the server under test."""

    def __init__(self, port: int, path_qs: str):
        import socket

        self._sock = socket.create_connection(("127.0.0.1", port),
                                              timeout=30)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._tmpl = (
            f"POST {path_qs} HTTP/1.1\r\nHost: x\r\n"
            "Content-Type: application/json\r\n"
            "Content-Length: %d\r\n\r\n"
        )
        self._buf = b""
        self.last_body = b""  # response body of the latest post()
        self.last_head = b""  # response headers of the latest post()

    def post(self, body: bytes) -> int:
        self._sock.sendall((self._tmpl % len(body)).encode() + body)
        while True:
            i = self._buf.find(b"\r\n\r\n")
            if i >= 0:
                head = self._buf[:i]
                clen = int(
                    head.lower().split(b"content-length:")[1]
                    .split(b"\r\n")[0]
                )
                while len(self._buf) < i + 4 + clen:
                    got = self._sock.recv(65536)
                    if not got:  # EOF mid-body must fail, not spin
                        raise RuntimeError(
                            "server closed mid-response"
                        )
                    self._buf += got
                status = int(head.split(b" ", 2)[1])
                self.last_head = head
                self.last_body = self._buf[i + 4:i + 4 + clen]
                self._buf = self._buf[i + 4 + clen:]
                return status
            got = self._sock.recv(65536)
            if not got:
                raise RuntimeError("server closed the connection")
            self._buf += got

    def close(self):
        self._sock.close()


def _bench_event_ingest(scale: float) -> dict:
    """Events/sec through a LIVE Event Server (HTTP POST, auth included):
    single ``/events.json`` posts and ≤50-event ``/batch/events.json``
    batches, against the sqlite event store (quickstart default) and the
    native C++ eventlog backend (the HBase-slot store). Also records the
    IN-PROCESS handler rate (no HTTP) so the artifact shows how the
    measured number decomposes: handler floor (storage commit + parse +
    validate) vs the HTTP/socket layer vs the load client sharing the
    core — see docs/operations.md §"Ingest cost profile"."""
    from pio_tpu.server.event_server import (
        EventServerService,
        create_event_server,
    )
    from pio_tpu.server.http import Request
    from pio_tpu.storage import Storage
    from pio_tpu.storage.records import AccessKey, App

    n_single = max(50, int(3000 * min(scale, 1.0)))
    n_batches = max(4, int(30 * min(scale, 1.0)))
    home = os.environ["PIO_TPU_HOME"]

    def one_backend(backend: str) -> dict:
        saved = {
            k: os.environ.get(k)
            for k in (
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE",
                "PIO_STORAGE_SOURCES_INGEST_TYPE",
                "PIO_STORAGE_SOURCES_INGEST_PATH",
            )
        }
        os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "INGEST"
        os.environ["PIO_STORAGE_SOURCES_INGEST_TYPE"] = backend
        os.environ["PIO_STORAGE_SOURCES_INGEST_PATH"] = os.path.join(
            home, f"ingest_{backend}"
        )
        Storage.reset()
        try:
            app_id = Storage.get_meta_data_apps().insert(
                App(0, f"bench-ingest-{backend}")
            )
            key = Storage.get_meta_data_access_keys().insert(
                AccessKey("", app_id)
            )
            server = create_event_server(
                host="127.0.0.1", port=_free_port()
            )
            server.start()
            # keep-alive connections — the reference SDKs hold one open;
            # a fresh TCP handshake per event would measure the client's
            # socket churn, not the server's ingest path
            single_cli = _RawIngestClient(
                server.port, f"/events.json?accessKey={key}"
            )
            batch_cli = _RawIngestClient(
                server.port, f"/batch/events.json?accessKey={key}"
            )
            try:
                def post(cli, body):
                    status = cli.post(json.dumps(body).encode())
                    if status >= 400:  # a 401/400 must fail the bench,
                        # not get timed as a successful ingest
                        raise RuntimeError(f"ingest: HTTP {status}")
                    return status

                def ev(n):
                    return {
                        "event": "rate",
                        "entityType": "user",
                        "entityId": f"u{n}",
                        "targetEntityType": "item",
                        "targetEntityId": f"i{n % 97}",
                        "properties": {"rating": float(n % 10) / 2.0},
                    }

                # in-process handler floor FIRST (no HTTP, no client;
                # fresh store, before WAL growth/checkpoints from the
                # HTTP phases can stall it): the measured HTTP numbers
                # then read as floor + HTTP layer + load client on the
                # shared core
                service = EventServerService()
                n_inproc = max(200, n_single // 2)

                def inproc_req(n):
                    return Request(
                        method="POST", path="/events.json",
                        params={"accessKey": key}, body=ev(n),
                    )

                status, _b = service.create_event(inproc_req(499_999))
                assert status == 201, status  # warm route + store
                t0 = time.perf_counter()
                for n in range(n_inproc):
                    status, _b = service.create_event(
                        inproc_req(500_000 + n)
                    )
                    assert status == 201, status
                dt_inproc = time.perf_counter() - t0

                post(single_cli, ev(0))  # warm the route + store
                # median-of-3 wall trials + per-request p50: hypervisor
                # STEAL on this 1-core host parks the whole VM for
                # 100-300 ms at random (seen as 0.1% of requests eating
                # ~30% of wall time), so a lone trial swings ~2×. The
                # p50 is the steal-free capability number; the wall
                # median is what a tenant actually gets.
                single_rates = []
                req_lat = []
                for trial in range(3):
                    base = trial * n_single
                    t0 = time.perf_counter()
                    for n in range(n_single):
                        tr = time.perf_counter()
                        post(single_cli, ev(base + n))
                        req_lat.append(time.perf_counter() - tr)
                    single_rates.append(
                        n_single / (time.perf_counter() - t0)
                    )
                single_rates.sort()
                req_lat.sort()
                p50_us = req_lat[len(req_lat) // 2] * 1e6
                t0 = time.perf_counter()
                for b in range(n_batches):
                    post(batch_cli,
                         [ev(b * 50 + j) for j in range(50)])
                dt_batch = time.perf_counter() - t0

                # concurrent single-POSTs (8 keep-alive clients): where
                # the storage layer's group commit earns its keep —
                # contemporaneous inserts coalesce into one WAL commit /
                # log append
                import concurrent.futures

                def conc_worker(t):
                    client = _RawIngestClient(
                        server.port, f"/events.json?accessKey={key}"
                    )
                    try:
                        for n in range(n_single // 4):
                            post(client, ev(100_000 + t * 10_000 + n))
                    finally:
                        client.close()

                t0 = time.perf_counter()
                with concurrent.futures.ThreadPoolExecutor(8) as ex:
                    list(ex.map(conc_worker, range(8)))
                dt_conc = time.perf_counter() - t0
                return {
                    "single_events_per_sec": round(single_rates[1], 1),
                    "single_trials": [round(r, 1) for r in single_rates],
                    "single_p50_us": round(p50_us, 1),
                    "single_p50_events_per_sec": round(1e6 / p50_us, 1),
                    "inproc_events_per_sec": round(
                        n_inproc / dt_inproc, 1
                    ),
                    "concurrent_single_events_per_sec": round(
                        8 * (n_single // 4) / dt_conc, 1
                    ),
                    "batch_events_per_sec": round(
                        n_batches * 50 / dt_batch, 1
                    ),
                    "client": "raw-keepalive",
                }
            finally:
                single_cli.close()
                batch_cli.close()
                server.stop()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            Storage.reset()

    out = {}
    for backend in ("sqlite", "eventlog"):
        try:
            out[backend] = one_backend(backend)
        except Exception as exc:
            _stage_failed(f"ingest {backend}", exc)
    return out


def _bench_partitioned_ingest(scale: float) -> dict:
    """``ingest.partitioned`` (ISSUE 9): concurrent HTTP ingest into the
    hash-partitioned event log at N=1/2/4 partitions through a live
    Event Server. The router spreads contemporaneous inserts over N
    independent group-commit queues, so the N=1 column is the single-log
    baseline and ``ingest_part_x`` (N=4 over N=1) is the concurrency win
    partitioning buys on THIS host. A final replicated pass (N=2, one
    in-process follower, the default ``batch`` durability → async
    replication off the ack path) records the rate with a follower
    attached plus ``repl_lag_p95_ms`` — the p95 of the
    ``pio_tpu_repl_ack_seconds`` send-to-ack histogram — and how long
    the follower took to drain to zero lag after the load stopped."""
    from pio_tpu.server.event_server import create_event_server
    from pio_tpu.storage import Storage
    from pio_tpu.storage.records import AccessKey, App

    n_each = max(40, int(1200 * min(scale, 1.0)))  # per client, 8 clients
    home = os.environ["PIO_TPU_HOME"]
    _ENV_KEYS = (
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE",
        "PIO_STORAGE_SOURCES_PART_TYPE",
        "PIO_STORAGE_SOURCES_PART_PATH",
        "PIO_TPU_PARTLOG_PARTITIONS",
        "PIO_TPU_PARTLOG_REPLICAS",
    )

    def one_pass(n: int, follower=None) -> dict:
        import concurrent.futures

        saved = {k: os.environ.get(k) for k in _ENV_KEYS}
        tag = f"part{n}" + ("r" if follower is not None else "")
        os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "PART"
        os.environ["PIO_STORAGE_SOURCES_PART_TYPE"] = "partlog"
        os.environ["PIO_STORAGE_SOURCES_PART_PATH"] = os.path.join(
            home, f"ingest_{tag}"
        )
        os.environ["PIO_TPU_PARTLOG_PARTITIONS"] = str(n)
        os.environ.pop("PIO_TPU_PARTLOG_REPLICAS", None)
        if follower is not None:
            os.environ["PIO_TPU_PARTLOG_REPLICAS"] = (
                f"127.0.0.1:{follower.port}"
            )
        Storage.reset()
        try:
            app_id = Storage.get_meta_data_apps().insert(
                App(0, f"bench-{tag}")
            )
            key = Storage.get_meta_data_access_keys().insert(
                AccessKey("", app_id)
            )
            server = create_event_server(host="127.0.0.1", port=_free_port())
            server.start()
            try:
                def ev(m):
                    return {
                        "event": "rate",
                        "entityType": "user",
                        "entityId": f"u{m}",
                        "targetEntityType": "item",
                        "targetEntityId": f"i{m % 97}",
                        "properties": {"rating": float(m % 10) / 2.0},
                    }

                def conc_worker(t):
                    client = _RawIngestClient(
                        server.port, f"/events.json?accessKey={key}"
                    )
                    try:
                        for m in range(n_each):
                            status = client.post(
                                json.dumps(ev(t * 100_000 + m)).encode()
                            )
                            if status >= 400:
                                raise RuntimeError(f"ingest: HTTP {status}")
                    finally:
                        client.close()

                warm = _RawIngestClient(
                    server.port, f"/events.json?accessKey={key}"
                )
                try:
                    assert warm.post(json.dumps(ev(999_999)).encode()) < 400
                finally:
                    warm.close()
                t0 = time.perf_counter()
                with concurrent.futures.ThreadPoolExecutor(8) as ex:
                    list(ex.map(conc_worker, range(8)))
                dt = time.perf_counter() - t0
                got = {
                    "concurrent_events_per_sec": round(8 * n_each / dt, 1),
                }
                if follower is not None:
                    # async replication: let the follower drain before
                    # reading the lag/ack artifacts (drain time is itself
                    # the interesting number — the unreplicated window a
                    # crash at batch durability could cost)
                    lev = Storage.get_levents()
                    t0 = time.perf_counter()
                    deadline = t0 + 20.0
                    while time.perf_counter() < deadline:
                        rows = lev._replicator.lag_snapshot()
                        if rows and all(
                            row["acked"].get(str(k), 0) >= lev.committed(k)
                            for row in rows
                            for k in range(n)
                        ):
                            break
                        time.sleep(0.02)
                    got["repl_drain_s"] = round(time.perf_counter() - t0, 3)
                    from pio_tpu.storage.partlog.replication import (
                        _ACK_SECONDS,
                    )

                    # per-partition/per-follower since ISSUE 11; the
                    # family-wide quantile merges cells bucket-wise
                    p95 = _ACK_SECONDS.quantile(0.95)
                    if p95 is not None:
                        got["repl_lag_p95_ms"] = round(p95 * 1e3, 3)
                return got
            finally:
                server.stop()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            Storage.reset()

    # partitioning multiplies COMMIT concurrency; on a 1-core host the
    # passes contend for the same CPU, so record the core count the
    # ratio was measured under (same honesty rule as the pool stage)
    out: dict = {
        "concurrent_events_per_sec": {},
        "host_cores": len(os.sched_getaffinity(0)),
    }
    for n in (1, 2, 4):
        try:
            got = one_pass(n)
            out["concurrent_events_per_sec"][str(n)] = (
                got["concurrent_events_per_sec"]
            )
        except Exception as exc:
            _stage_failed(f"partitioned ingest N={n}", exc)
    r1 = out["concurrent_events_per_sec"].get("1")
    r4 = out["concurrent_events_per_sec"].get("4")
    if r1 and r4:
        out["ingest_part_x"] = round(r4 / r1, 2)
    try:
        from pio_tpu.storage.partlog.replication import FollowerServer

        froot = os.path.join(home, "ingest_follower")
        follower = FollowerServer(froot)
        try:
            rep = one_pass(2, follower=follower)
        finally:
            follower.stop()
        rep["partitions"] = 2
        rep["durability"] = "batch (async replication)"
        out["replicated"] = rep
    except Exception as exc:
        _stage_failed("replicated ingest pass", exc)
    return out


#: hard budget for the final stdout line — the driver records only the
#: LAST 2000 characters of output, so the printed summary (plus newline)
#: must always fit; the full result goes to BENCH_FULL.json instead
SUMMARY_CHAR_BUDGET = 1900


def build_summary(full: dict, full_path: str = "BENCH_FULL.json") -> dict:
    """Compact, tail-window-safe summary of a full bench result.

    The round-4 artifact of record was lost because the single JSON line
    outgrew the driver's 2000-char tail window and the FRONT of the line
    (the headline) was truncated away. The contract now: the full detail
    blob is written to ``BENCH_FULL.json`` and stdout carries ONLY this
    summary — headline value/vs_baseline, link probe, device-phase rate,
    pack_s, serving p50s + concurrent/pool QPS, and per-config
    vs_baseline ratios — small enough that the whole line always
    survives the tail window.
    """

    def get(*path, default=None):
        node = full
        for key in path:
            if not isinstance(node, dict) or key not in node:
                return default
            node = node[key]
        return node

    s = {
        "metric": full.get("metric"),
        "value": full.get("value"),
        "unit": full.get("unit"),
        "vs_baseline": full.get("vs_baseline"),
        "value_best_of_5": full.get("value_best_of_5"),
        "link_mb_s": full.get("link_mb_s"),
        "device_examples_per_sec": full.get("device_examples_per_sec"),
        "pack_s": get("phases", "pack_s"),
        "p50_predict_ms": full.get("p50_predict_ms"),
        "p50_inproc_ms": full.get("p50_inproc_ms"),
        "serving_qps": get("serving", "concurrent", "qps"),
        "serving_mb_qps": get("serving", "concurrent_microbatch", "qps"),
        "serving_mb_mode": get("serving", "concurrent_microbatch", "mode"),
        "pool_qps": get("serving", "pool", "qps"),
        "pool_laned_qps": get("serving", "pool", "laned_qps"),
        "routed_qps": get("serving", "pool", "routed_qps"),
        "router_overhead_ms": get("serving", "pool", "router_overhead_ms"),
        "shadow_overhead_ms": get("serving", "pool", "shadow_overhead_ms"),
        "pool_workers": get("serving", "pool", "workers"),
        "host_cores": get("serving", "pool", "host_cores"),
        "sharded_qps": get("serving", "sharded", "qps"),
        "sharded_scaling_x": get("serving", "sharded", "scaling_x"),
        "evfront_qps": get("serving", "evfront", "qps"),
        "evfront_p50_ms": get("serving", "evfront", "p50_ms"),
        "serving_attributed": get(
            "serving", "latency_budget", "attributedFraction"
        ),
    }
    # per-bucket micro-batch decisions replace the single mode string
    # when present (compacted to {bucket: mode} — the p50s live in the
    # full blob)
    mode_map = get("serving", "concurrent_microbatch", "mode_by_bucket")
    if isinstance(mode_map, dict) and mode_map:
        s["serving_mb_mode"] = {
            b: (v.get("mode") if isinstance(v, dict) else v)
            for b, v in sorted(mode_map.items(), key=lambda kv: int(kv[0]))
        }
    res = get("serving", "resident")
    if isinstance(res, dict):
        s["serving_h2d_x"] = res.get("h2d_ratio_f32_over_i8")
        s["serving_donation_hit"] = res.get("donation_hit_rate")
        s["serving_wire_parity_delta"] = res.get("parity_delta")
    sec = full.get("secondary") or {}
    configs: dict = {}
    for short, key in (
        ("classification", "classification_examples_per_sec"),
        ("similarproduct", "similarproduct_examples_per_sec"),
        ("twotower", "twotower_examples_per_sec"),
    ):
        entry = sec.get(key)
        if isinstance(entry, dict):
            c = {"v": entry.get("value"), "x": entry.get("vs_baseline")}
            for src, dst in (("achieved_gflops", "gflops"),
                             ("anchor_note", "anchor"),
                             ("link_mb_s", "link"),
                             ("link_d2h_mb_s", "link_d2h"),
                             ("train_acc", "acc"),
                             ("anchor_train_acc", "anchor_acc"),
                             ("wire", "wire")):
                if src in entry:
                    c[dst] = entry[src]
            configs[short] = c
    if isinstance(sec.get("seqrec"), dict):
        sq = sec["seqrec"]
        configs["seqrec"] = {
            "tokens_s": sq.get("tokens_per_sec"),
            "gflops": sq.get("achieved_gflops"),
        }
    ts = sec.get("train_streamed")
    if isinstance(ts, dict):
        configs["train_streamed"] = {
            "v": ts.get("value"),
            "overlap": ts.get("overlap_ratio"),
            "shard_x": ts.get("sharded_scaling_x"),
            "h2d_s": (ts.get("phases") or {}).get("h2d_s"),
            "device_s": (ts.get("phases") or {}).get("device_s"),
        }
        # trajectory fields ride the summary top level so the history
        # delta table can watch them (see HISTORY_FIELDS)
        s["train_streamed_eps"] = ts.get("value")
        s["train_stream_overlap"] = ts.get("overlap_ratio")
        s["train_sharded_x"] = ts.get("sharded_scaling_x")
        s["train_peak_bytes"] = (ts.get("device") or {}).get("peak_bytes")
    # device accounting (ISSUE 17): serving-stage compile total — the
    # steady-state flatness trajectory the history table watches
    dev = get("serving", "resident", "device") or get(
        "serving", "concurrent", "device"
    )
    if isinstance(dev, dict):
        s["serving_compiles"] = dev.get("compiles")
    if isinstance(sec.get("textclassification"), dict):
        tc = sec["textclassification"]
        configs["textclass"] = {
            "tokens_s": max(
                tc.get("pallas_tokens_per_sec") or 0.0,
                tc.get("xla_tokens_per_sec") or 0.0,
            ) or None,
            "x": tc.get("vs_baseline"),
        }
    ing = sec.get("eventserver_events_per_sec")
    if isinstance(ing, dict):
        flat = {}
        for backend, row in ing.items():
            if isinstance(row, dict):
                flat[f"{backend}_single"] = row.get("single_events_per_sec")
                if "single_p50_events_per_sec" in row:
                    flat[f"{backend}_p50"] = row["single_p50_events_per_sec"]
                flat[f"{backend}_batch"] = row.get("batch_events_per_sec")
        if flat:
            configs["ingest"] = flat
    ip = sec.get("ingest_partitioned")
    if isinstance(ip, dict):
        rates = ip.get("concurrent_events_per_sec") or {}
        c = {f"n{n}": rates.get(n) for n in ("1", "2", "4")
             if rates.get(n) is not None}
        if "ingest_part_x" in ip:
            c["x"] = ip["ingest_part_x"]
        rep = ip.get("replicated")
        if isinstance(rep, dict):
            if "repl_lag_p95_ms" in rep:
                c["lag_p95_ms"] = rep["repl_lag_p95_ms"]
            if "concurrent_events_per_sec" in rep:
                c["repl"] = rep["concurrent_events_per_sec"]
        if c:
            configs["ingest_part"] = c
    if configs:
        s["configs"] = configs
    s["full"] = os.path.basename(full_path)
    # belt and braces: if the summary somehow outgrows the budget, shed
    # down to the driver-required core rather than risk truncation again
    if len(json.dumps(s)) > SUMMARY_CHAR_BUDGET:
        s = {k: s.get(k) for k in
             ("metric", "value", "unit", "vs_baseline", "full")}
    return s


#: workload env knobs and their full-scale defaults — a knob set to a
#: NON-default value marks a SMOKE run, whose artifact must not clobber
#: the committed artifact of record (explicitly exporting a default is
#: still a full run)
_FULL_SCALE_DEFAULTS = {
    "PIO_TPU_BENCH_EDGES": "25000000",
    "PIO_TPU_BENCH_ITERS": "10",
    "PIO_TPU_BENCH_RANK": "16",
    "PIO_TPU_BENCH_CPU_EDGES": "2000000",
    "PIO_TPU_BENCH_QUERIES": "200",
    "PIO_TPU_BENCH_SECONDARY": "1",
    "PIO_TPU_BENCH_SCALE": "1",
    "PIO_TPU_BENCH_RANKSWEEP": "1",
    "PIO_TPU_BENCH_DEADLINE_S": "3000",
}


def _is_smoke_run() -> bool:
    for k, default in _FULL_SCALE_DEFAULTS.items():
        v = os.environ.get(k)
        if v is None:
            continue
        try:
            if float(v) != float(default):
                return True
        except ValueError:
            return True  # unparseable knob: refuse to claim full scale
    return False


def emit(full: dict, path: str | None = None,
         base_dir: str | None = None) -> str:
    """Write ``full`` to its JSON file and return the summary line (the
    ONLY thing main prints to stdout, as its last act). Full-scale runs
    write BENCH_FULL.json (the committed artifact of record); runs with
    any workload-shrinking env knob write the gitignored
    bench_full_smoke.json instead."""
    if path is None:
        if base_dir is None:
            base_dir = os.path.dirname(os.path.abspath(__file__))
        name = ("bench_full_smoke.json" if _is_smoke_run()
                else "BENCH_FULL.json")
        path = os.path.join(base_dir, name)
    # atomic replace: a mid-serialization failure (e.g. a stage leaking
    # a non-JSON type) must not destroy the previous artifact of record
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(full, f, indent=1)
            f.write("\n")
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):  # failed mid-write: no .tmp litter
            os.unlink(tmp)
    print(f"# full result written to {path}", file=sys.stderr)
    return json.dumps(build_summary(full, full_path=path))


# ---------------------------------------------------------------------------
# bench history ledger (ISSUE 11): ``bench.py --history`` appends each
# run's trajectory fields to BENCH_HISTORY.jsonl and prints a
# delta-vs-previous-run table (to stderr — stdout stays the one summary
# line) with a configurable regression threshold. The BENCH_r0x
# artifacts are point-in-time snapshots; this is the trend line.
# ---------------------------------------------------------------------------

HISTORY_BASENAME = "BENCH_HISTORY.jsonl"
DEFAULT_REGRESSION_THRESHOLD = 0.05

#: trajectory fields and their good direction; a move against the
#: direction by more than the threshold is flagged REGRESSION
HISTORY_FIELDS = (
    ("value", "up"),                 # headline examples/sec/chip
    ("serving_qps", "up"),
    ("pool_qps", "up"),
    ("routed_qps", "up"),            # through the serving-fabric router
    ("router_overhead_ms", "down"),  # router hop p50 cost vs direct
    ("shadow_overhead_ms", "down"),  # shadow-mirroring p50 cost vs routed
    ("evfront_qps", "up"),
    ("evfront_p50_ms", "down"),
    ("p50_predict_ms", "down"),
    ("p95_predict_ms", "down"),
    ("serving_attributed", "up"),    # latency-attribution coverage
    ("serving_h2d_x", "up"),         # f32/i8 h2d byte ratio (wire win)
    ("shed_rate", "down"),           # overload stage shed fraction
    ("train_streamed_eps", "up"),    # streamed-feed examples/sec/chip
    ("train_stream_overlap", "up"),  # h2d hidden behind compute
    ("train_sharded_x", "up"),       # mesh vs single-chip train rate
    ("serving_compiles", "down"),    # attributed serving compiles (flat)
    ("train_peak_bytes", "down"),    # streamed-train HBM high-water
)


def _git_sha() -> str | None:
    import subprocess

    try:
        got = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5.0,
        )
        sha = got.stdout.strip()
        return sha or None
    except (OSError, subprocess.SubprocessError):
        return None


def history_record(full: dict, summary: dict,
                   git_sha: str | None = None,
                   timestamp: str | None = None) -> dict:
    """One BENCH_HISTORY.jsonl row: the trajectory fields only."""
    if timestamp is None:
        import datetime as _dt

        timestamp = _dt.datetime.now(_dt.timezone.utc).isoformat(
            timespec="seconds"
        )
    serving = full.get("serving") or {}
    conc = serving.get("concurrent") or {}
    overload = serving.get("overload") or {}
    rec = {
        "timestamp": timestamp,
        "git_sha": git_sha if git_sha is not None else _git_sha(),
        "smoke": _is_smoke_run(),
        "metric": summary.get("metric"),
        "value": summary.get("value"),
        "vs_baseline": summary.get("vs_baseline"),
        "serving_qps": summary.get("serving_qps"),
        "pool_qps": summary.get("pool_qps"),
        "routed_qps": summary.get("routed_qps"),
        "router_overhead_ms": summary.get("router_overhead_ms"),
        "shadow_overhead_ms": summary.get("shadow_overhead_ms"),
        "evfront_qps": summary.get("evfront_qps"),
        "evfront_p50_ms": summary.get("evfront_p50_ms"),
        "p50_predict_ms": summary.get("p50_predict_ms"),
        "p95_predict_ms": conc.get("p95_ms"),
        "serving_attributed": summary.get("serving_attributed"),
        "serving_h2d_x": summary.get("serving_h2d_x"),
        "shed_rate": overload.get("shed_rate"),
        "train_streamed_eps": summary.get("train_streamed_eps"),
        "train_stream_overlap": summary.get("train_stream_overlap"),
        "train_sharded_x": summary.get("train_sharded_x"),
        "serving_compiles": summary.get("serving_compiles"),
        "train_peak_bytes": summary.get("train_peak_bytes"),
        "shed_counts": {
            "offered": overload.get("offered"),
            "admitted": overload.get("admitted"),
            "server_shed": overload.get("server_shed"),
        },
    }
    return rec


def append_history(record: dict, path: str) -> None:
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")


def read_history(path: str) -> list:
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    print(f"# skipping malformed history line in {path}",
                          file=sys.stderr)
    except OSError:
        pass
    return out


def history_delta_table(prev: dict, cur: dict,
                        threshold: float) -> tuple:
    """``(table_lines, regressed_fields)`` comparing two history rows.
    A field counts as a regression when it moves AGAINST its good
    direction by more than ``threshold`` (fractional, e.g. 0.05).
    The direction-aware comparison itself is shared with the training
    run ledger (``pio runs --diff``) via trainwatch."""
    from pio_tpu.obs.trainwatch import delta_rows

    rows, regressed = delta_rows(prev, cur, HISTORY_FIELDS, threshold)
    lines = [
        f"bench history delta vs {prev.get('git_sha') or '?'} "
        f"({prev.get('timestamp') or '?'}), threshold "
        f"{threshold * 100:.1f}%:",
        f"  {'field':<20} {'prev':>12} {'now':>12} {'delta':>9}",
    ]
    for field, a, b, delta, tag in rows:
        lines.append(f"  {field:<20} {a:>12} {b:>12} {delta:>9}{tag}")
    if not rows:
        lines.append("  (no comparable numeric fields)")
    return lines, regressed


def parse_history_argv(argv: list) -> dict:
    """``--history [--history-file PATH] [--regression-threshold FRAC]``
    (also enabled by ``PIO_TPU_BENCH_HISTORY=1`` for env-only drivers).
    Unknown argv entries are ignored — bench is env-driven otherwise."""
    opts = {
        "history": os.environ.get("PIO_TPU_BENCH_HISTORY", "0") == "1",
        "history_file": os.environ.get("PIO_TPU_BENCH_HISTORY_FILE"),
        "threshold": DEFAULT_REGRESSION_THRESHOLD,
    }
    it = iter(argv)
    for a in it:
        if a == "--history":
            opts["history"] = True
        elif a == "--history-file":
            opts["history_file"] = next(it, None)
        elif a.startswith("--history-file="):
            opts["history_file"] = a.split("=", 1)[1]
        elif a == "--regression-threshold":
            raw = next(it, None)
            try:
                opts["threshold"] = float(raw)
            except (TypeError, ValueError):
                print(f"# bad --regression-threshold {raw!r}; keeping "
                      f"{opts['threshold']}", file=sys.stderr)
        elif a.startswith("--regression-threshold="):
            raw = a.split("=", 1)[1]
            try:
                opts["threshold"] = float(raw)
            except ValueError:
                print(f"# bad --regression-threshold {raw!r}; keeping "
                      f"{opts['threshold']}", file=sys.stderr)
    return opts


def maybe_record_history(full: dict, summary: dict, argv: list) -> None:
    """Append this run to the ledger and print the delta table (stderr).
    Best-effort by design: a ledger problem must never cost the summary
    line. The previous run compared against is the last ledger row with
    the SAME smoke flag — comparing a smoke run against a full-scale one
    would flag phantom regressions."""
    opts = parse_history_argv(argv)
    if not opts["history"]:
        return
    try:
        path = opts["history_file"] or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), HISTORY_BASENAME
        )
        rec = history_record(full, summary)
        prior = [
            r for r in read_history(path)
            if r.get("smoke") == rec.get("smoke")
        ]
        append_history(rec, path)
        print(f"# history appended to {path} "
              f"({'smoke' if rec['smoke'] else 'full'} run)",
              file=sys.stderr)
        if prior:
            lines, regressed = history_delta_table(
                prior[-1], rec, opts["threshold"]
            )
            for line in lines:
                print(f"# {line}", file=sys.stderr)
            if regressed:
                print(f"# REGRESSION in: {', '.join(regressed)}",
                      file=sys.stderr)
        else:
            print("# no prior comparable run in ledger; baseline row "
                  "recorded", file=sys.stderr)
    except Exception as exc:
        _stage_failed("bench history", exc)


def run_check_history(argv: list) -> int:
    """``bench.py --check-history``: no benchmark run — read the ledger,
    diff the last two rows with the matching smoke flag, exit 1 on a
    regression past the threshold. Smoke wires this after its bench
    stage so a silent slowdown fails the pipeline loudly (ISSUE 16).
    Must run before :func:`main`'s PIO_TPU_HOME override — it only
    reads the ledger, it must not create a throwaway home."""
    opts = parse_history_argv(argv)
    path = opts["history_file"] or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), HISTORY_BASENAME
    )
    rows = read_history(path)
    if not rows:
        print(f"# no bench history at {path}; nothing to check",
              file=sys.stderr)
        return 0
    same = [r for r in rows if r.get("smoke") == rows[-1].get("smoke")]
    if len(same) < 2:
        print("# only one comparable run in ledger; baseline recorded, "
              "nothing to diff", file=sys.stderr)
        return 0
    lines, regressed = history_delta_table(
        same[-2], same[-1], opts["threshold"]
    )
    for line in lines:
        print(f"# {line}", file=sys.stderr)
    if regressed:
        print(f"# REGRESSION in: {', '.join(regressed)}", file=sys.stderr)
        return 1
    print("# no regression past threshold", file=sys.stderr)
    return 0


def main() -> None:
    # isolate the serving benchmark's storage in a throwaway home (must be
    # set before the first Storage touch; always overridden — bench junk
    # must never land in a real deployment home)
    os.environ["PIO_TPU_HOME"] = tempfile.mkdtemp(prefix="pio_tpu_bench_")
    t_main = time.perf_counter()
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()  # before the first backend use
    import jax

    from pio_tpu.models.als import ALSConfig
    from pio_tpu.parallel.context import ComputeContext, default_mesh

    n_edges = int(os.environ.get("PIO_TPU_BENCH_EDGES", ML25M_EDGES))
    scale = n_edges / ML25M_EDGES
    n_users = max(64, int(ML25M_USERS * min(scale, 1.0)))
    n_items = max(64, int(ML25M_ITEMS * min(scale, 1.0)))
    # reference ALS template default numIterations=10 — the honest
    # workload depth; also amortizes fixed host/wire costs on BOTH the
    # accelerator and the anchor side, which stabilizes vs_baseline
    iters = int(os.environ.get("PIO_TPU_BENCH_ITERS", 10))
    rank = int(os.environ.get("PIO_TPU_BENCH_RANK", 16))
    n_queries = int(os.environ.get("PIO_TPU_BENCH_QUERIES", 200))
    cfg = ALSConfig(rank=rank, iterations=iters, reg=0.1)

    u, i, r = _synth_ratings(n_edges, n_users, n_items)

    devices = jax.devices()
    n_chips = len(devices)
    ctx = ComputeContext(mesh=default_mesh(("data",), devices=devices))
    link_mb_s = _probe_link_mb_s()
    times, factors = _time_train(ctx, u, i, r, n_users, n_items, cfg)
    dt_median = times[len(times) // 2]
    rate_per_chip = n_edges * iters / dt_median / n_chips
    rate_best = n_edges * iters / times[0] / n_chips

    # phase decomposition: one PROFILED run (already warm) with blocking
    # between host-pack / host→device / device-compute — answers "how much
    # of the headline is TPU and how much is the link"
    phases = {}
    try:
        from pio_tpu.models.als import train_als as _train_als

        st = {}
        _train_als(ctx, u, i, r, n_users, n_items, cfg, stats=st)
        # normal-equation build term only (4·K·(K+1) FLOPs/edge/iter);
        # solves + packing excluded → conservative
        flops = 4 * cfg.rank * (cfg.rank + 1) * n_edges * iters
        phases = {
            "pack_s": round(st["pack_s"], 3),
            "h2d_s": round(st["h2d_s"], 3),
            "device_s": round(st["device_s"], 3),
            "wire_bytes": int(st["wire_bytes"]),
            "wire_mb_per_s": round(
                st["wire_bytes"] / st["h2d_s"] / 1e6, 1
            ),
            "encoding": st["encoding"],
            "n_stream": st["n_stream"],
            "overlapped_total_s": round(dt_median, 3),
            "device_examples_per_sec": round(
                n_edges * iters / st["device_s"], 1
            ),
            "achieved_gflops": round(flops / st["device_s"] / 1e9, 1),
        }
    except Exception as exc:
        _stage_failed("phase profile", exc)

    p50_inproc = _predict_p50_inproc_ms(factors, n_users, n_queries)
    try:
        serving = _bench_server_p50(factors, n_users, n_items, n_queries)
    except Exception as exc:  # the headline number must survive a serving
        # stack failure; report the hole rather than crash
        _stage_failed("server p50", exc)
        serving = {}
    try:
        serving["pool"] = _bench_pool_serving(factors, n_users, n_items)
    except Exception as exc:
        _stage_failed("pool serving stage", exc)
    try:
        serving["sharded"] = _bench_sharded_serving(
            factors, n_users, n_items,
            baseline_qps=serving.get("pool", {}).get("laned_qps"),
        )
    except Exception as exc:
        _stage_failed("sharded serving stage", exc)
    try:
        serving["resident"] = _bench_resident_serving(
            min(n_queries, 200)
        )
    except Exception as exc:
        _stage_failed("resident serving stage", exc)
    try:
        serving["evfront"] = _bench_evfront(min(n_queries, 400))
    except Exception as exc:
        _stage_failed("evfront serving stage", exc)
    p50_server = serving.get("p50_ms")

    # CPU anchor: same XLA program, single host CPU device, subsampled edges.
    cpu_edges = int(os.environ.get("PIO_TPU_BENCH_CPU_EDGES",
                                   min(n_edges, 2_000_000)))
    cpu_rate = None
    try:
        cpu_dev = jax.devices("cpu")[0]
        sub = slice(0, cpu_edges)
        cpu_cfg = ALSConfig(rank=rank, iterations=iters, reg=0.1)
        with jax.default_device(cpu_dev):
            cpu_ctx = ComputeContext(mesh=None)
            # same median-of-N and the same iteration count as the
            # accelerator side: an asymmetric comparison (median vs best,
            # or amortized vs unamortized fixed costs) would inflate
            # vs_baseline
            cpu_times, _ = _time_train(cpu_ctx, u[sub], i[sub], r[sub],
                                       n_users, n_items, cpu_cfg,
                                       repeats=3)
        cpu_rate = cpu_edges * iters / cpu_times[len(cpu_times) // 2]
    except Exception as exc:  # pragma: no cover - CPU backend always present
        _stage_failed("cpu anchor", exc)

    secondary = {}
    if os.environ.get("PIO_TPU_BENCH_SECONDARY", "1") != "0":
        sscale = float(os.environ.get("PIO_TPU_BENCH_SCALE", "1"))
        cpu_dev = jax.devices("cpu")[0]
        # the one JSON line must always print: past the deadline the
        # remaining secondary stages are skipped (with a stderr note)
        # rather than risking the whole run being cut off
        deadline_s = float(
            os.environ.get("PIO_TPU_BENCH_DEADLINE_S", "3000")
        )

        def over_deadline(stage: str) -> bool:
            if time.perf_counter() - t_main > deadline_s:
                print(f"# deadline reached; skipping {stage}",
                      file=sys.stderr)
                return True
            return False

        def run_on_cpu(fn, frac):
            """Own-CPU anchor: SAME program on the XLA-CPU device, with a
            subsampled workload (rates normalize per example, so the
            ratio is per-example speedup — the headline's anchor
            discipline applied to every config)."""
            with jax.default_device(cpu_dev):
                cpu_ctx = ComputeContext(
                    mesh=default_mesh(("data",), devices=[cpu_dev])
                )
                return fn(cpu_ctx, sscale * frac)

        for name, fn, cpu_frac in (
            ("classification_examples_per_sec", _bench_classification,
             0.25),
            ("similarproduct_examples_per_sec", _bench_similarproduct,
             0.1),
            ("twotower_examples_per_sec", _bench_twotower, 1.0),
        ):
            if over_deadline(name):
                continue  # note every skipped stage, not just the first
            try:
                def split(res):
                    # stages may return {"value": rate, ...metadata}
                    # (anchor methodology, link probe, accuracy) or a
                    # bare rate
                    if isinstance(res, dict):
                        extra = dict(res)
                        return float(extra.pop("value")), extra
                    return float(res), {}

                v, extra = split(fn(ctx, sscale))
                entry = {"value": round(v, 1), **extra}
                try:
                    cv, cextra = split(run_on_cpu(fn, cpu_frac))
                    entry["cpu_anchor"] = round(cv, 1)
                    entry["vs_baseline"] = round(v / cv, 2)
                    if "train_acc" in cextra:
                        # accuracy honesty: the quantized/bf16 device
                        # wire must not buy throughput with quality
                        entry["anchor_train_acc"] = cextra["train_acc"]
                except Exception as exc:
                    _stage_failed(f"cpu anchor {name}", exc)
                secondary[name] = entry
            except Exception as exc:
                _stage_failed(f"secondary {name}", exc)

        if "twotower_examples_per_sec" in secondary:
            # achieved matmul GFLOP/s from the analytic per-example count
            # (two towers + the [B, B] in-batch-negative logits, ×3 for
            # backward; embedding gathers excluded → conservative). Uses
            # the e2e rate, so fixed host staging costs are included.
            B, E, H, O = _TT_BATCH, _TT_EMBED, _TT_HIDDEN, _TT_OUT
            fpe = 3 * (2 * (2 * E * H + 2 * H * O) + 2 * B * O)
            tt = secondary["twotower_examples_per_sec"]
            g = tt["value"] * fpe / 1e9
            tt["achieved_gflops"] = round(g, 1)
            tt["roofline_note"] = _v5e_peak_note(
                g, "e2e wall-clock; bound = output table readback over "
                "the host link (see phases), training is one "
                "compiled scan"
            )

        if not over_deadline("train.streamed"):
            try:
                secondary["train_streamed"] = _bench_train_streamed(
                    ctx, sscale
                )
            except Exception as exc:
                _stage_failed("secondary train.streamed", exc)

        if not over_deadline("seqrec"):
            try:
                secondary["seqrec"] = _bench_seqrec(ctx, sscale)
            except Exception as exc:
                _stage_failed("secondary seqrec", exc)

        if not over_deadline("textclassification"):
            try:
                tc = _bench_textclass(sscale)
                try:
                    with jax.default_device(cpu_dev):
                        tc_cpu = _bench_textclass(sscale * 0.25)
                    # the shipped op dispatches to XLA at this shape, so
                    # the device number of record is the faster path
                    best = max(
                        tc.get("pallas_tokens_per_sec", 0.0),
                        tc["xla_tokens_per_sec"],
                    )
                    tc["cpu_anchor"] = tc_cpu["xla_tokens_per_sec"]
                    tc["vs_baseline"] = round(
                        best / tc_cpu["xla_tokens_per_sec"], 2
                    )
                except Exception as exc:
                    _stage_failed("cpu anchor textclassification", exc)
                secondary["textclassification"] = tc
            except Exception as exc:
                _stage_failed("secondary textclassification", exc)

        if os.environ.get("PIO_TPU_BENCH_RANKSWEEP", "1") != "0" \
                and not over_deadline("als_rank_sweep"):
            try:
                secondary["als_rank_sweep"] = _bench_rank_sweep(
                    ctx, sscale
                )
            except Exception as exc:
                _stage_failed("rank sweep", exc)

        if not over_deadline("eventserver_events_per_sec"):
            try:
                secondary["eventserver_events_per_sec"] = (
                    _bench_event_ingest(sscale)
                )
            except Exception as exc:
                _stage_failed("event ingest", exc)

        if not over_deadline("ingest.partitioned"):
            try:
                secondary["ingest_partitioned"] = (
                    _bench_partitioned_ingest(sscale)
                )
            except Exception as exc:
                _stage_failed("partitioned ingest", exc)

    vs_baseline = rate_per_chip / cpu_rate if cpu_rate else 1.0
    out = {
        "metric": "ALS@MovieLens-25M examples/sec/chip",
        # headline: MEDIAN of 5 end-to-end runs, with the same-session
        # link probe and the link-independent device-phase rate
        # promoted alongside
        "value": round(rate_per_chip, 1),
        "value_best_of_5": round(rate_best, 1),
        "link_mb_s": round(link_mb_s, 1),
        "device_examples_per_sec": phases.get("device_examples_per_sec"),
        "unit": "examples/sec/chip",
        "vs_baseline": round(vs_baseline, 2),
        # BASELINE.md's second tracked metric: serving p50 through a LIVE
        # query server (HTTP); p50_inproc_ms is the round-1 continuity number
        "p50_predict_ms": (
            round(p50_server, 3) if p50_server is not None else None
        ),
        "p50_inproc_ms": round(p50_inproc, 3),
        # phase decomposition of the headline (pack / link / device) +
        # the device-only rate the host link hides
        "phases": phases,
        # serving under concurrent load (16 clients): qps/p50/p95, with
        # and without the micro-batching aggregator
        "serving": serving,
        "secondary": secondary,
    }
    line = emit(out)
    maybe_record_history(out, json.loads(line), sys.argv[1:])
    print(line)
    if _FAILED_STAGES:
        sys.exit("bench stages failed: " + ", ".join(_FAILED_STAGES))


if __name__ == "__main__":
    if "--check-history" in sys.argv[1:]:
        sys.exit(run_check_history(sys.argv[1:]))
    main()
