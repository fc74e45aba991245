"""Ops plane (ISSUE 2): structured logs + trace correlation, health
probes, SLO burn-rate engine, and the pool supervisor's health-driven
respawn logic — the unit tier (server-route coverage lives in
test_servers.py, real-process pool coverage in test_worker_pool.py)."""

import json
import logging
import threading
import time

import pytest

from pio_tpu.obs import slog
from pio_tpu.obs.health import Heartbeat, HealthMonitor, thread_alive
from pio_tpu.obs.metrics import MetricsRegistry, REGISTRY
from pio_tpu.obs.slo import (
    DEFAULT_BURN_WINDOWS,
    SLOEngine,
    SLObjective,
    engine_for_specs,
    parse_duration_s,
    parse_slo,
)
from pio_tpu.obs.tracing import Tracer


@pytest.fixture(autouse=True)
def fresh_slog():
    slog._reset_for_tests()
    yield
    slog._reset_for_tests()


# ---------------------------------------------------------------- slog
class TestJsonLogHandler:
    def test_one_line_json_with_fields(self):
        h = slog.JsonLogHandler(worker=3)
        rec = logging.LogRecord(
            "pio_tpu.test", logging.WARNING, __file__, 1,
            "boom %d", (7,), None,
        )
        line = h.format_line(rec)
        assert "\n" not in line
        entry = json.loads(line)
        assert entry["level"] == "WARNING"
        assert entry["logger"] == "pio_tpu.test"
        assert entry["msg"] == "boom 7"
        assert entry["worker"] == 3
        assert entry["trace_id"] is None and entry["span"] is None
        assert entry["ts"].endswith("+00:00")  # UTC ISO-8601
        assert "levelno" not in entry  # internal field stays internal

    def test_exception_text_attached(self):
        h = slog.JsonLogHandler()
        try:
            raise ValueError("bad")
        except ValueError:
            import sys

            rec = logging.LogRecord(
                "pio_tpu.test", logging.ERROR, __file__, 1,
                "failed", (), sys.exc_info(),
            )
        entry = json.loads(h.format_line(rec))
        assert "ValueError: bad" in entry["exc"]

    def test_bad_format_does_not_raise(self):
        h = slog.JsonLogHandler()
        rec = logging.LogRecord(
            "pio_tpu.test", logging.INFO, __file__, 1,
            "%d", ("not-an-int",), None,
        )
        assert json.loads(h.format_line(rec))["msg"] == "%d"

    def test_emit_feeds_ring_and_counter(self):
        h = slog.JsonLogHandler()
        before = REGISTRY.counter(
            "pio_tpu_log_messages_total", "", ("level", "logger")
        ).value("INFO", "pio_tpu.feedtest")
        logger = logging.getLogger("pio_tpu.feedtest")
        logger.addHandler(h)
        logger.setLevel(logging.INFO)
        try:
            logger.info("hello ring")
        finally:
            logger.removeHandler(h)
        assert h.ring.tail()[-1]["msg"] == "hello ring"
        after = REGISTRY.counter(
            "pio_tpu_log_messages_total", "", ("level", "logger")
        ).value("INFO", "pio_tpu.feedtest")
        assert after == before + 1


class TestLogRing:
    def _fill(self, ring, n, **kw):
        for i in range(n):
            ring.append({"msg": f"m{i}", "levelno": logging.INFO,
                         "level": "INFO", **kw})

    def test_bounded_with_dropped_count(self):
        ring = slog.LogRing(cap=4)
        self._fill(ring, 10)
        got = ring.snapshot()
        assert [e["msg"] for e in got] == ["m6", "m7", "m8", "m9"]
        assert ring.dropped == 6

    def test_tail_newest_n_chronological(self):
        ring = slog.LogRing(cap=8)
        self._fill(ring, 5)
        assert [e["msg"] for e in ring.tail(n=2)] == ["m3", "m4"]

    def test_level_is_minimum_severity(self):
        ring = slog.LogRing(cap=8)
        ring.append({"msg": "d", "levelno": logging.DEBUG})
        ring.append({"msg": "w", "levelno": logging.WARNING})
        ring.append({"msg": "e", "levelno": logging.ERROR})
        assert [e["msg"] for e in ring.tail(level="warning")] == ["w", "e"]
        with pytest.raises(ValueError, match="unknown level"):
            ring.tail(level="loud")

    def test_trace_and_logger_filters(self):
        ring = slog.LogRing(cap=8)
        ring.append({"msg": "a", "levelno": 20, "trace_id": "query-1",
                     "logger": "pio_tpu.queryserver"})
        ring.append({"msg": "b", "levelno": 20, "trace_id": "query-2",
                     "logger": "pio_tpu.storage"})
        assert [e["msg"] for e in ring.tail(trace_id="query-2")] == ["b"]
        assert [e["msg"] for e in ring.tail(logger="pio_tpu.query")] == ["a"]

    def test_install_idempotent_upgrades_in_place(self):
        h1 = slog.install()
        h2 = slog.install(worker=5)
        assert h1 is h2 and h1.worker == 5
        pio = logging.getLogger("pio_tpu")
        assert sum(1 for x in pio.handlers
                   if isinstance(x, slog.JsonLogHandler)) == 1


class TestTraceCorrelation:
    def test_logs_inside_span_carry_trace_id(self):
        slog.install()
        tracer = Tracer("corr")
        log = logging.getLogger("pio_tpu.corrtest")
        with tracer.trace("corr") as tr:
            log.info("at trace top")
            with tr.span("work"):
                log.info("inside span")
            trace_id = tr._trace.trace_id
        log.info("after trace")
        entries = slog.ring().tail(trace_id=trace_id)
        assert [e["msg"] for e in entries] == [
            "at trace top", "inside span",
        ]
        assert entries[0]["span"] is None
        assert entries[1]["span"] == "work"
        # context restored on exit
        assert slog.current_trace_id() is None
        # and the post-trace record has no trace id
        assert slog.ring().tail()[-1]["trace_id"] is None

    def test_contextvar_restored_on_error(self):
        slog.install()
        tracer = Tracer("corr2")
        with pytest.raises(RuntimeError):
            with tracer.trace("corr2"):
                raise RuntimeError("x")
        assert slog.current_trace_id() is None


# -------------------------------------------------------------- health
class TestHealth:
    def test_heartbeat_ages_out(self):
        hb = Heartbeat(max_age_s=0.05)
        ok, _ = hb.check()
        assert ok
        time.sleep(0.08)
        ok, detail = hb.check()
        assert not ok and "last beat" in detail
        hb.beat()
        assert hb.check()[0]

    def test_thread_alive_check(self):
        evt = threading.Event()
        t = threading.Thread(target=evt.wait, daemon=True)
        t.start()
        check = thread_alive(lambda: t)
        assert check()[0]
        evt.set()
        t.join()
        ok, detail = check()
        assert not ok and "dead" in detail
        # None thread = feature disabled, not a failure
        assert thread_alive(lambda: None)()[0]

    def test_monitor_reports_and_normalizes(self):
        mon = HealthMonitor()
        mon.add_liveness("truthy", lambda: True)
        mon.add_liveness("tuple", lambda: (True, "fine"))
        mon.add_readiness("raises", lambda: 1 / 0)
        ok, report = mon.liveness()
        assert ok and report["status"] == "ok"
        assert report["checks"]["tuple"] == {"ok": True, "detail": "fine"}
        ok, report = mon.readiness()
        assert not ok and report["status"] == "not ready"
        assert "ZeroDivisionError" in report["checks"]["raises"]["detail"]

    def test_one_failure_flips_probe(self):
        mon = HealthMonitor()
        mon.add_liveness("good", lambda: True)
        mon.add_liveness("bad", lambda: (False, "wedged"))
        ok, report = mon.liveness()
        assert not ok
        assert report["checks"]["good"]["ok"]
        assert not report["checks"]["bad"]["ok"]


class TestGroupCommitProbe:
    """Group commit is leader/follower (no thread to watch): the event
    server's /healthz liveness instead probes that the commit lock is
    acquirable — a leader wedged inside a hung backend flush holds it."""

    def test_acquirable_lock_is_healthy(self):
        from pio_tpu.storage.groupcommit import GroupCommitter

        gc = GroupCommitter(lambda payloads: list(payloads), store="t")
        ok, detail = gc.probe(timeout=0.1)
        assert ok and "acquirable" in detail
        # probing must not LEAVE the lock held
        ok, _ = gc.probe(timeout=0.1)
        assert ok

    def test_wedged_flush_flips_probe(self):
        from pio_tpu.storage.groupcommit import GroupCommitter

        wedge = threading.Event()
        in_flush = threading.Event()

        def hung_flush(payloads):
            in_flush.set()
            wedge.wait(timeout=10)
            return list(payloads)

        gc = GroupCommitter(hung_flush, store="t")
        t = threading.Thread(target=gc.submit, args=("x",), daemon=True)
        t.start()
        assert in_flush.wait(timeout=5)
        ok, detail = gc.probe(timeout=0.2)
        assert not ok and "0.2" in detail
        wedge.set()
        t.join(timeout=5)
        assert gc.probe(timeout=0.5)[0]


# ----------------------------------------------------------------- slo
class TestSLOParsing:
    def test_latency_spec(self):
        slo = parse_slo("p99=50ms:99.9")
        assert slo.name == "latency_p99" and slo.kind == "latency"
        assert slo.objective == pytest.approx(0.999)
        assert slo.threshold_s == pytest.approx(0.05)
        assert slo.window_s == 3600.0

    def test_availability_spec_with_window(self):
        slo = parse_slo("availability=99.95/6h")
        assert slo.kind == "availability"
        assert slo.objective == pytest.approx(0.9995)
        assert slo.window_s == 6 * 3600.0

    @pytest.mark.parametrize("bad", [
        "p99=50ms", "p99:99.9", "availability=101", "availability=0",
        "nonsense", "p99=50parsecs:99.9", "p99=50ms:99.9/2fortnights",
    ])
    def test_rejects_malformed(self, bad):
        with pytest.raises(ValueError):
            parse_slo(bad)

    def test_duration_units(self):
        assert parse_duration_s("250us") == pytest.approx(2.5e-4)
        assert parse_duration_s("50ms") == pytest.approx(0.05)
        assert parse_duration_s("2m") == 120.0
        assert parse_duration_s("1d") == 86400.0
        with pytest.raises(ValueError):
            parse_duration_s("fast")

    def test_objective_validates(self):
        with pytest.raises(ValueError):
            SLObjective("x", "availability", objective=1.5)
        with pytest.raises(ValueError):
            SLObjective("x", "latency", objective=0.99)  # no threshold


class TestCountLe:
    def test_threshold_snaps_down_to_bucket_edge(self):
        reg = MetricsRegistry()
        cell = reg.histogram(
            "t_req_seconds", "", (), buckets=(0.01, 0.05, 0.1)
        ).labels()
        for v in (0.005, 0.02, 0.06, 0.2):
            cell.observe(v)
        # 0.05 is an edge: counts the <=0.01 and <=0.05 buckets
        assert cell.count_le(0.05, pool=False) == (2, 4)
        # 0.07 is NOT an edge: snaps DOWN to 0.05 (conservative)
        assert cell.count_le(0.07, pool=False) == (2, 4)
        assert cell.count_le(0.005, pool=False) == (0, 4)
        # a threshold past the last edge can't see into +Inf
        assert cell.count_le(0.1, pool=False) == (3, 4)


class TestSLOEngine:
    def _engine_with_source(self, registry=None):
        eng = SLOEngine(registry=registry)
        state = {"good": 0.0, "total": 0.0}
        eng.add(
            SLObjective("availability", "availability", objective=0.999),
            lambda: (state["good"], state["total"]),
        )
        return eng, state

    def test_burn_rate_and_budget_from_windows(self):
        eng, state = self._engine_with_source()
        t = 1000.0
        eng.sample(now=t)
        # 1000 requests, 10 errors over the next hour → error rate 1%,
        # burn = 0.01 / 0.001 = 10 on every window that saw the delta
        state["good"], state["total"] = 990.0, 1000.0
        out = eng.evaluate(now=t + 3600.0)["slos"][0]
        assert out["total"] == 1000.0 and out["errors"] == 10.0
        assert out["burnRates"]["3600s"] == pytest.approx(10.0, abs=0.01)
        # budget for the hour: 0.001 * 1000 = 1 allowed error, 10 spent
        assert out["errorBudgetRemaining"] == pytest.approx(-9.0, abs=0.01)

    def test_alerts_need_both_windows(self):
        eng, state = self._engine_with_source()
        t = 1000.0
        eng.sample(now=t)
        # big burst INSIDE the fast window only: 5m sees it, the 1h
        # window also sees it (same delta) → page fires
        state["good"], state["total"] = 900.0, 1000.0
        out = eng.evaluate(now=t + 300.0)["slos"][0]
        page = [a for a in out["alerts"] if a["severity"] == "page"][0]
        assert page["firing"]
        # quiet hour afterwards: fast window decays to zero burn → the
        # SAME cumulative numbers no longer page
        eng.sample(now=t + 300.0)
        out = eng.evaluate(now=t + 300.0 + 3600.0)["slos"][0]
        page = [a for a in out["alerts"] if a["severity"] == "page"][0]
        assert not page["firing"]

    def test_no_traffic_is_healthy(self):
        eng, _ = self._engine_with_source()
        out = eng.evaluate(now=10.0)["slos"][0]
        assert out["errorBudgetRemaining"] == 1.0
        assert all(not a["firing"] for a in out["alerts"])

    def test_gauges_exported(self):
        reg = MetricsRegistry()
        eng, state = self._engine_with_source(registry=reg)
        state["good"], state["total"] = 990.0, 1000.0
        eng.sample(now=0.0)
        eng.evaluate(now=3600.0)
        text = "\n".join(reg.render())
        assert "pio_tpu_slo_error_budget_remaining{" in text
        assert 'pio_tpu_slo_burn_rate{slo="availability",window="300s"}' \
            in text

    def test_engine_for_specs_wires_latency_to_histogram(self):
        reg = MetricsRegistry()
        cell = reg.histogram(
            "t2_req_seconds", "", (), buckets=(0.01, 0.05, 0.1)
        ).labels()
        eng = engine_for_specs(
            ["p99=50ms:99.9", "availability=99.9"], reg,
            availability_source=lambda: (10.0, 10.0),
            latency_cell_getter=lambda: cell,
        )
        assert len(eng) == 2
        for v in (0.02, 0.02, 0.2):  # 2 fast, 1 slow
            cell.observe(v)
        eng.sample(now=0.0)
        by_name = {
            s["name"]: s for s in eng.evaluate(now=60.0)["slos"]
        }
        lat = by_name["latency_p99"]
        assert lat["total"] == 3.0 and lat["errors"] == 1.0
        assert lat["thresholdMs"] == 50.0
        assert by_name["availability"]["errors"] == 0.0

    def test_default_burn_windows_shape(self):
        # the documented fast/slow page+ticket pairs (SRE workbook)
        assert DEFAULT_BURN_WINDOWS[0] == (300.0, 3600.0, 14.4, "page")
        assert DEFAULT_BURN_WINDOWS[1] == (1800.0, 21600.0, 6.0, "ticket")


# -------------------------------------------- supervisor health logic
class _FakeProc:
    """Process stand-in for the supervisor sweep (no real spawn)."""

    def __init__(self):
        self.alive = True
        self.killed = 0

    def is_alive(self):
        return self.alive

    def kill(self):
        self.killed += 1
        self.alive = False

    def join(self, timeout=None):
        pass

    def terminate(self):
        self.alive = False


class TestSupervisorHealthSweep:
    @pytest.fixture()
    def harness(self):
        """A ServingPool shell (no spawned workers) + one in-process HTTP
        server whose /healthz status the test flips at will."""
        from pio_tpu.server.http import JsonHTTPServer, Router
        from pio_tpu.server.worker_pool import ServingPool

        state = {"status": 503}
        r = Router()
        r.add("GET", "/healthz", lambda req: (state["status"], {}))
        server = JsonHTTPServer(r, "127.0.0.1", 0, name="fake-worker")
        server.start()

        pool = ServingPool.__new__(ServingPool)  # skip __init__: no spawn
        pool.n_workers = 1
        pool._procs = [_FakeProc()]
        pool._respawns = [{"crash": 0, "unhealthy": 0}]
        pool._health_ports = [server.port]
        pool._health_fails = [0]
        pool._kill_reason = [None]
        pool._health_gauge = REGISTRY.gauge(
            "pio_tpu_worker_health_state", "", ("worker",)
        )
        yield pool, state
        server.stop()

    def test_kill_after_k_consecutive_failures(self, harness):
        from pio_tpu.server.worker_pool import _HEALTH_FAILS_TO_KILL

        pool, state = harness
        proc = pool._procs[0]
        for i in range(_HEALTH_FAILS_TO_KILL - 1):
            pool._health_sweep()
            assert proc.killed == 0, f"killed after only {i + 1} failures"
        pool._health_sweep()
        assert proc.killed == 1
        pool._health_sweep()  # next sweep sees the corpse
        assert pool._health_gauge.value("0") == -1

    def test_success_resets_failure_streak(self, harness):
        pool, state = harness
        proc = pool._procs[0]
        pool._health_sweep()
        pool._health_sweep()  # two strikes
        state["status"] = 200
        pool._health_sweep()  # healthy → streak resets
        assert pool._health_fails[0] == 0
        assert pool._health_gauge.value("0") == 1
        state["status"] = 503
        pool._health_sweep()
        pool._health_sweep()
        assert proc.killed == 0  # needs a fresh full streak

    def test_unpublished_port_is_not_a_failure(self, harness):
        pool, _ = harness
        pool._health_ports = [0]  # sidecar not up yet
        pool._health_sweep()
        assert pool._health_fails[0] == 0
        assert pool._procs[0].killed == 0


class TestDeviceOwnerReadiness:
    """A device-owning pool is ready only when WORKER 0 is: its siblings
    are pinned to the host mirror, so one of them answering /readyz on
    the shared port must not make the pool ready."""

    @pytest.fixture()
    def harness(self):
        """A two-worker ServingPool shell whose shared port is served by
        a READY "sibling" and whose worker-0 sidecar the test controls."""
        import threading

        from pio_tpu.server.http import JsonHTTPServer, Router
        from pio_tpu.server.worker_pool import ServingPool

        state = {"worker0": 503}
        servers = []
        for status in (lambda: 200, lambda: state["worker0"]):
            r = Router()
            r.add("GET", "/readyz", lambda req, st=status: (st(), {}))
            servers.append(
                JsonHTTPServer(r, "127.0.0.1", 0, name="fake").start()
            )
        sibling, sidecar = servers
        pool = ServingPool.__new__(ServingPool)  # skip __init__: no spawn
        pool.n_workers = 2
        pool._host, pool.port = "127.0.0.1", sibling.port
        pool._owns_device = True
        pool._shutdown = threading.Event()
        pool._procs = [_FakeProc(), _FakeProc()]
        pool._health_ports = [0, 0]
        pool._anchor = pool._metrics_seg = pool._lane_seg = None
        yield pool, state, sidecar.port
        for srv in servers:
            srv.stop()

    def test_sibling_ready_is_not_pool_ready(self, harness):
        pool, state, sidecar_port = harness
        with pytest.raises(TimeoutError):  # worker 0 has no sidecar yet
            pool.wait_ready(timeout=0.5)
        pool._health_ports[0] = sidecar_port
        with pytest.raises(TimeoutError):  # worker 0 answers 503
            pool.wait_ready(timeout=0.5)
        state["worker0"] = 200
        pool.wait_ready(timeout=5.0)
        pool._owns_device = False  # a plain pool: any worker will do
        state["worker0"] = 503
        pool.wait_ready(timeout=5.0)

    def test_worker0_death_at_startup_stops_pool(self, harness):
        pool, _, _ = harness
        pool._procs[0].alive = False
        pool._procs[0].exitcode = 137
        with pytest.raises(RuntimeError, match="worker 0 exited"):
            pool.wait_ready(timeout=5.0)
        assert pool._shutdown.is_set()  # stopped, not left serving


# -------------------------------------------------- deprecation shim
class TestMetricsShim:
    def test_import_warns_once_and_reexports(self):
        import importlib
        import warnings

        import pio_tpu.server.metrics as shim

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            importlib.reload(shim)
        assert any(
            issubclass(x.category, DeprecationWarning) for x in w
        )
        from pio_tpu.server.http import METRICS_CONTENT_TYPE

        assert shim.CONTENT_TYPE == METRICS_CONTENT_TYPE
        assert shim.escape_label('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
        resp = shim.render(["# TYPE x counter", "x 1"])
        assert "x 1" in resp.body
        assert resp.content_type == METRICS_CONTENT_TYPE

    def test_no_remaining_in_tree_importers(self):
        """The shim exists for out-of-tree plugins only — nothing in
        pio_tpu/ may import it anymore (satellite: reroute callers)."""
        import pathlib
        import re

        root = pathlib.Path(__file__).resolve().parent.parent / "pio_tpu"
        offenders = []
        for py in root.rglob("*.py"):
            if py.name == "metrics.py" and py.parent.name == "server":
                continue
            text = py.read_text()
            if re.search(
                r"from pio_tpu\.server\.metrics import|"
                r"from pio_tpu\.server import metrics|"
                r"import pio_tpu\.server\.metrics", text,
            ):
                offenders.append(str(py))
        assert not offenders, offenders
