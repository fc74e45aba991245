"""SO_REUSEPORT serving pool (pio_tpu/server/worker_pool.py).

Correctness tier for the multi-process query-serving mode: connections
balance across workers, answers match the single-process server, /reload
rolls every worker via the shared generation counter, and /undeploy
brings the whole pool down. Perf (the pool's reason to exist) needs a
multi-core host — this environment pins to ONE core, so QPS claims live
in BASELINE.md, not here.
"""

import datetime as dt
import http.client
import json
import time

import pytest

import pio_tpu.templates  # noqa: F401  (registers the engine factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.obs import monotonic_s
from pio_tpu.data import Event
from pio_tpu.storage import App, Storage
from pio_tpu.workflow import build_engine, run_train, variant_from_dict

pytestmark = pytest.mark.slow  # spawns real worker processes

VARIANT = {
    "id": "pool-e2e",
    "engineFactory": "templates.recommendation",
    "datasource": {"params": {"app_name": "pool-test"}},
    "algorithms": [
        {
            "name": "als",
            "params": {
                "rank": 4, "num_iterations": 5, "lambda_": 0.05, "seed": 1,
            },
        }
    ],
}


def _seed_and_train(n_users=10, n_items=6):
    app_id = Storage.get_meta_data_apps().insert(App(0, "pool-test"))
    le = Storage.get_levents()
    t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
    for u in range(n_users):
        for i in range(n_items):
            in_block = (u < 5) == (i < 3)
            le.insert(
                Event(
                    "rate", "user", f"u{u}", "item", f"i{i}",
                    properties={"rating": 5.0 if in_block else 1.0},
                    event_time=t0 + dt.timedelta(minutes=u * 60 + i),
                ),
                app_id,
            )
    variant = variant_from_dict(VARIANT)
    engine, ep = build_engine(variant)
    # local (single-device) training: this suite exercises pool SERVING;
    # the mesh training path has its own coverage in test_als.py
    run_train(engine, ep, variant, ctx=ComputeContext.local())
    return variant


def _post(port, path, body, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", path, body=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def _post_h(port, path, body, timeout=30, headers=None):
    """Like _post but also returns the response headers (lowercased)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", path, body=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", **(headers or {})},
        )
        r = conn.getresponse()
        return (r.status, json.loads(r.read()),
                {k.lower(): v for k, v in r.getheaders()})
    finally:
        conn.close()


@pytest.fixture()
def pool(tmp_home):
    from pio_tpu.server.worker_pool import ServingPool

    Storage.reset()
    variant = _seed_and_train()
    pool = ServingPool(variant, host="127.0.0.1", port=0, n_workers=2)
    pool.start()
    pool.wait_ready(timeout=120)
    yield pool
    pool.stop()
    Storage.reset()


class TestServingPool:
    def test_concurrent_correctness_and_balancing(self, pool):
        # single-process reference answer (same storage, same instance)
        status, ref = _post(pool.port, "/queries.json",
                            {"user": "u1", "num": 3})
        assert status == 200 and len(ref["itemScores"]) == 3
        # u1 is in the first block → top items must come from i0..i2
        top_ref = {s["item"] for s in ref["itemScores"]}
        assert top_ref <= {"i0", "i1", "i2"}

        # every worker (fresh connections rotate across listeners) must
        # return the identical ranking — they loaded the same instance
        workers_seen = set()
        for _ in range(30):
            status, got = _post(pool.port, "/queries.json",
                                {"user": "u1", "num": 3})
            assert status == 200
            assert [s["item"] for s in got["itemScores"]] == \
                [s["item"] for s in ref["itemScores"]]
            _, stats = _get(pool.port, "/stats.json")
            assert stats["poolSize"] == 2
            workers_seen.add(stats["worker"])
        # kernel balancing is stochastic but 60+ fresh connections
        # virtually never all land on one listener
        assert len(workers_seen) == 2, workers_seen

    def test_pool_wide_metrics_on_any_worker(self, pool):
        """Acceptance criterion: with the shared-memory segment bound,
        GET /metrics on whichever worker answers reports POOL-WIDE
        totals — N requests in, a scraped counter of exactly N out,
        regardless of how the kernel split the connections."""
        from pio_tpu.obs.promparse import parse_prometheus_text

        def scrape():
            conn = http.client.HTTPConnection("127.0.0.1", pool.port,
                                              timeout=30)
            try:
                conn.request("GET", "/metrics")
                r = conn.getresponse()
                assert r.status == 200
                return parse_prometheus_text(r.read().decode())
            finally:
                conn.close()

        base = scrape().value("pio_tpu_queries_total", engine_id="pool-e2e")
        N = 20
        workers_seen = set()
        for _ in range(N):
            status, _ = _post(pool.port, "/queries.json",
                              {"user": "u1", "num": 2})
            assert status == 200
            _, stats = _get(pool.port, "/stats.json")
            workers_seen.add(stats["worker"])
        # several scrapes (fresh connections → possibly different
        # workers) must all agree on the pool-wide total
        for _ in range(6):
            pm = scrape()
            assert pm.value(
                "pio_tpu_queries_total", engine_id="pool-e2e"
            ) == base + N
        assert len(workers_seen) == 2, workers_seen
        # stage histograms aggregate the same way: every request passed
        # through execute exactly once, whichever worker served it
        assert pm.value(
            "pio_tpu_query_stage_seconds_count",
            engine_id="pool-e2e", stage="execute",
        ) >= base + N
        # /stats.json carries the pool block alongside per-worker stats
        _, stats = _get(pool.port, "/stats.json")
        assert stats["pool"]["requestCount"] >= base + N

    def test_reload_rolls_every_worker(self, pool):
        # retrain → new COMPLETED instance; one /reload must roll ALL
        # workers (generation counter), not just the one that got the POST
        variant = variant_from_dict(VARIANT)
        engine, ep = build_engine(variant)
        new_id = run_train(
            engine, ep, variant, ctx=ComputeContext.local()
        )
        status, out = _post(pool.port, "/reload", {})
        assert status == 200 and out["engineInstanceId"] == new_id
        # every worker must now serve the new instance (lazy reload on
        # next query) — hit both via fresh connections
        seen = set()
        for _ in range(30):
            status, got = _post(pool.port, "/queries.json",
                                {"user": "u2", "num": 2})
            assert status == 200
            _, st = _get(pool.port, "/")
            seen.add(st["engineInstanceId"])
        assert seen == {new_id}, seen

    def test_supervisor_respawns_crashed_worker(self, pool):
        """A worker killed out-of-band comes back under supervision and
        serves again; /undeploy then stops supervision and every worker."""
        import threading

        sup = threading.Thread(target=pool.wait, daemon=True)
        sup.start()
        victim = pool._procs[0]
        victim.terminate()
        victim.join(10)
        deadline = monotonic_s() + 30
        while monotonic_s() < deadline:
            if pool._procs[0] is not victim and pool._procs[0].is_alive():
                break
            time.sleep(0.2)
        assert pool._procs[0] is not victim, "worker never respawned"
        assert pool._respawns[0]["crash"] == 1
        # the pool still answers (either worker may take the connection)
        status, got = _post(pool.port, "/queries.json",
                            {"user": "u1", "num": 2})
        assert status == 200 and len(got["itemScores"]) == 2
        _post(pool.port, "/undeploy", {})
        sup.join(30)
        assert not sup.is_alive()
        assert all(not p.is_alive() for p in pool._procs)

    def test_supervisor_kills_wedged_worker_via_health_probe(self, pool):
        """ISSUE 2 acceptance: a worker that is alive-but-wedged (frozen
        with SIGSTOP — its process exists, its /healthz never answers)
        is killed after the consecutive-failure threshold and respawned
        by the ordinary crash path."""
        import os
        import signal
        import threading

        # every worker publishes its loopback health sidecar port
        deadline = monotonic_s() + 30
        while monotonic_s() < deadline:
            if all(p > 0 for p in pool._health_ports):
                break
            time.sleep(0.2)
        ports = list(pool._health_ports)
        assert all(p > 0 for p in ports), ports
        for p in ports:
            status, report = _get(p, "/healthz")
            assert status == 200 and report["status"] == "ok"

        sup = threading.Thread(
            target=pool.wait,
            kwargs={"poll_s": 0.2, "health_poll_s": 0.5},
            daemon=True,
        )
        sup.start()
        victim = pool._procs[1]
        os.kill(victim.pid, signal.SIGSTOP)  # wedged, not dead
        deadline = monotonic_s() + 60
        while monotonic_s() < deadline:
            if pool._procs[1] is not victim and pool._procs[1].is_alive():
                break
            time.sleep(0.2)
        assert pool._procs[1] is not victim, "wedged worker never replaced"
        assert pool._respawns[1]["unhealthy"] == 1
        # the health-sweep kill spent the unhealthy budget, not the
        # crash budget (the split is the point of the per-reason split)
        assert pool._respawns[1]["crash"] == 0
        # the replacement serves (either worker may take the connection)
        status, got = _post(pool.port, "/queries.json",
                            {"user": "u1", "num": 2})
        assert status == 200 and len(got["itemScores"]) == 2
        _post(pool.port, "/undeploy", {})
        sup.join(30)
        assert not sup.is_alive()

    def test_undeploy_stops_whole_pool(self, pool):
        status, out = _post(pool.port, "/undeploy", {})
        assert status == 200
        # the shared event reaches the supervisor and every worker
        deadline = monotonic_s() + 30
        while monotonic_s() < deadline:
            if all(not p.is_alive() for p in pool._procs):
                break
            time.sleep(0.2)
        assert all(not p.is_alive() for p in pool._procs)


@pytest.fixture()
def qos_pool(tmp_home):
    from pio_tpu.server.worker_pool import ServingPool

    Storage.reset()
    variant = _seed_and_train()
    # rps is tiny so refill during the burst stays under one token: the
    # observable budget is the burst, shared by BOTH workers
    pool = ServingPool(variant, host="127.0.0.1", port=0, n_workers=2,
                       qos="rps=0.2,burst=6")
    pool.start()
    pool.wait_ready(timeout=120)
    yield pool
    pool.stop()
    Storage.reset()


class TestPoolQoS:
    def test_rps_budget_enforced_pool_wide(self, qos_pool):
        """ISSUE 3 acceptance: with --workers 2, an rps= budget is
        enforced pool-wide, not per worker. 40 requests against a
        shared burst of 6 must admit ~6 TOTAL (each worker's token
        bucket observes the other's admissions through the shm segment)
        — per-worker budgets would admit ~12."""
        import concurrent.futures

        from pio_tpu.obs.promparse import parse_prometheus_text

        def one(t):
            # fresh connection per request → kernel spreads them over
            # both workers' SO_REUSEPORT listeners
            return _post_h(qos_pool.port, "/queries.json",
                           {"user": f"u{t % 10}", "num": 2})

        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            results = list(ex.map(one, range(40)))
        admitted = [r for r in results if r[0] == 200]
        shed = [r for r in results if r[0] == 429]
        assert {r[0] for r in results} <= {200, 429}
        assert len(admitted) + len(shed) == 40
        # the SHARED budget: burst 6, plus at most a couple of tokens
        # from the cross-worker race window and trickle refill. Split
        # per-worker budgets would admit 12+.
        assert 6 <= len(admitted) <= 9, len(admitted)
        for _, body, headers in shed:
            assert int(headers["retry-after"]) >= 1
            assert "overloaded" in body["message"]
        # pool-wide accounting, scraped from whichever worker answers:
        # shed_total covers every 429, admitted the pool-wide 200s
        conn = http.client.HTTPConnection("127.0.0.1", qos_pool.port,
                                          timeout=30)
        try:
            conn.request("GET", "/metrics")
            pm = parse_prometheus_text(conn.getresponse().read().decode())
        finally:
            conn.close()
        assert pm.value(
            "pio_tpu_qos_shed_total",
            scope="queryserver", reason="rate_limit",
        ) == len(shed)
        status, snap = _get(qos_pool.port, "/qos.json")
        assert status == 200 and snap["enabled"] is True
        assert snap["admitted"] == len(admitted)
        assert snap["policy"]["rps"] == pytest.approx(0.2)
        # the pool survived the burst
        status, got = _get(qos_pool.port, "/healthz")
        assert status == 200


@pytest.fixture()
def traced_pool(tmp_home, monkeypatch):
    from pio_tpu.server.worker_pool import ServingPool

    # 100 ns slow threshold: every request breaches, so both workers'
    # slow rings fill deterministically (workers inherit the env)
    monkeypatch.setenv("PIO_TPU_SLOW_TRACE_MS", "0.0001")
    Storage.reset()
    variant = _seed_and_train()
    pool = ServingPool(variant, host="127.0.0.1", port=0, n_workers=2)
    pool.start()
    pool.wait_ready(timeout=120)
    yield pool
    pool.stop()
    Storage.reset()


class TestPoolTraceAttribution:
    def test_pool_unique_ids_merged_rings_and_slow_capture(self, traced_pool):
        """ISSUE 6 acceptance: in pool mode, minted trace ids are
        worker-namespaced (query-wN-...), /traces.json?id= resolves a
        trace whichever worker holds it (sidecar fan-out), and a slow
        request's waterfall is retrievable by id from ?slow=1 on ANY
        worker's merged view."""
        pool = traced_pool
        # sidecar ports must be published before fan-out can merge
        deadline = monotonic_s() + 30
        while monotonic_s() < deadline:
            if all(p > 0 for p in pool._health_ports):
                break
            time.sleep(0.2)
        assert all(p > 0 for p in pool._health_ports)

        ids = set()
        for i in range(12):
            status, body, headers = _post_h(
                pool.port, "/queries.json", {"user": f"u{i % 8}", "num": 2}
            )
            assert status == 200
            tid = headers.get("x-pio-trace")
            assert tid and tid.startswith("query-w"), tid
            ids.add(tid)
        assert len(ids) == 12  # pool-unique: no cross-worker collisions

        # by-id lookup crosses workers: whichever worker answers the GET
        # must resolve ids minted by EITHER worker
        for tid in sorted(ids)[:6]:
            status, got = _get(pool.port, f"/traces.json?id={tid}")
            assert status == 200, tid
            t = got["traces"][0]
            assert t["id"] == tid
            stages = {s["stage"] for s in t["spans"]}
            assert {"accept", "parse", "execute"} <= stages, stages

        # inbound header adoption still works under the pool
        status, body, headers = _post_h(
            pool.port, "/queries.json", {"user": "u1", "num": 2},
            headers={"X-Pio-Trace": "pool-client-1/dispatch"},
        )
        assert status == 200
        assert headers.get("x-pio-trace") == "pool-client-1"
        ids.add("pool-client-1")

        # every request breached the 100 ns threshold: the MERGED slow
        # view on any worker eventually covers ids from both workers
        deadline = monotonic_s() + 15
        seen = set()
        while monotonic_s() < deadline and not ids <= seen:
            status, got = _get(pool.port, "/traces.json?slow=1&n=128")
            assert status == 200
            seen = {t["id"] for t in got["traces"]}
            time.sleep(0.2)
        assert ids <= seen, ids - seen
        slow = {t["id"]: t for t in got["traces"]}
        assert all(slow[tid].get("slow") for tid in ids)
        # worker index rides the trace for the merged view
        assert all("worker" in slow[tid] for tid in ids)


def test_device_owner_dying_at_startup_stops_pool(tmp_home, monkeypatch):
    """A device_worker pool whose worker 0 dies before it is ready is
    never ready — its siblings only score on the host mirror — and
    stops, instead of answering from the CPU."""
    from pio_tpu.faults.registry import ENV_VAR
    from pio_tpu.server.worker_pool import ServingPool

    Storage.reset()
    variant = _seed_and_train()
    pool = ServingPool(
        variant, host="127.0.0.1", port=0, n_workers=2, device_worker=True,
    )
    spawn = pool._spawn

    def spawn_arming_worker0(idx):
        # workers arm from the environment they are spawned with
        if idx == 0:
            monkeypatch.setenv(ENV_VAR, "worker.start=crash")
        else:
            monkeypatch.delenv(ENV_VAR, raising=False)
        return spawn(idx)

    pool._spawn = spawn_arming_worker0
    pool.start()
    try:
        with pytest.raises(RuntimeError, match="worker 0 exited"):
            pool.wait_ready(timeout=120)
        assert all(not p.is_alive() for p in pool._procs)
    finally:
        pool.stop()
        Storage.reset()
