"""SO_REUSEPORT serving pool — N query-server processes on one port.

The reference serves queries from one JVM whose thread pool scales across
cores (``core/.../workflow/CreateServer.scala`` — UNVERIFIED path;
SURVEY.md §2.6 serving-concurrency row). CPython's GIL serializes nearly
all per-request work in one process, so the TPU rebuild's equivalent is a
POOL of worker processes that each bind the same TCP port with
``SO_REUSEPORT``; the kernel load-balances incoming connections across the
listeners, multiplying host-path QPS by the worker count on multi-core
serving hosts.

Accelerator ownership: libtpu admits ONE process per chip. Every pool
worker therefore scores on the **host mirror** of the factor tables (the
deserialized model state — the same adaptive scorer fallback path that
``ops/topn.py`` uses for small batches), with an opt-in for worker 0 to
own the device scorer (``device_worker=True``) when the pool runs on the
TPU VM itself. Non-owner workers pin JAX to CPU before anything imports
it, so they can never grab the chip. A pool with a device owner is ready
only when worker 0 is, and stops when worker 0 dies at start-up or is
retired: the siblings cannot stand in for the chip.

``mesh_worker=True`` is the multi-chip variant of the same ownership
model: worker 0 owns the WHOLE mesh and serves with mesh-sharded factor
tables (``PIO_TPU_MESH_SERVE=1``; partition rules in
``pio_tpu/parallel/partition.py``), so one serving host can hold a model
that exceeds a single chip's memory budget. Siblings stay host-mirror
scorers and route large batches to worker 0 through the batch lane,
exactly as with ``device_worker``.

Pool semantics (shared ``multiprocessing`` primitives, spawn context):

- **/reload** on any worker bumps a shared generation counter after
  reloading itself; every sibling lazily reloads before serving its next
  query — one admin POST rolls the whole pool.
- **/undeploy** on any worker sets a shared shutdown event; the
  supervisor terminates every worker — matching single-process behavior
  where ``pio undeploy`` stops the server.
- **/stats.json** reports per-worker numbers plus ``worker``/``poolSize``
  fields (the kernel decides which worker answers a given connection);
  aggregate across workers client-side or via Prometheus scrapes.

Start one with ``pio deploy --workers N`` or programmatically::

    pool = ServingPool(variant, port=8000, n_workers=4)
    pool.start()
    pool.wait()          # supervise until /undeploy or pool.stop()
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import os
import socket
import tempfile
import time
from typing import Optional

from pio_tpu.utils import knobs
from pio_tpu.obs.metrics import monotonic_s
from pio_tpu.workflow.engine_json import EngineVariant

log = logging.getLogger("pio_tpu.workerpool")

#: respawn budget per worker index AND per kill reason — a worker that
#: keeps dying signals a real fault (bad model, port clash), not a
#: transient, so stop burning processes on it. Budgets are split by
#: reason: a wedge the health sweep shot (``unhealthy``) is usually
#: load-induced and recoverable, so it must not consume the crash
#: budget and retire a worker that never actually crash-looped
_MAX_RESPAWNS = 3
_MAX_RESPAWNS_BY_REASON = {"crash": _MAX_RESPAWNS, "unhealthy": 6}

#: exponential respawn backoff: death N waits base * 2^(N-1), capped — a
#: worker crash-looping on startup (bad model file, import error) must
#: not hot-spin the supervisor through its whole budget in milliseconds
_RESPAWN_BACKOFF_BASE_S = 0.5
_RESPAWN_BACKOFF_CAP_S = 30.0

#: a worker that served this long before dying was not crash-looping:
#: reset its respawn count (and thus its backoff) on death
_RESPAWN_RESET_AFTER_S = 60.0

#: consecutive /healthz failures before the supervisor kills a worker —
#: one failed poll is a blip (GC pause, slow scrape); K in a row on a
#: 1 s-timeout probe is a wedge
_HEALTH_FAILS_TO_KILL = 3


def _worker_main(spec: dict, idx: int, gen, shutdown_evt,
                 health_ports=None, lane_doorbell=None,
                 lane_resp_events=None) -> None:
    """Entry point of one pool worker (spawned process)."""
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()  # before the first backend use in this process
    owns_device = (
        (spec["device_worker"] or spec.get("mesh_worker")) and idx == 0
    )
    if owns_device and spec.get("mesh_worker"):
        # the mesh owner serves sharded: partition-rule placement over
        # every local device instead of a single-chip upload
        os.environ["PIO_TPU_MESH_SERVE"] = "1"
    if not owns_device:
        # host-mirror scoring only; pin JAX to CPU before ANY import can
        # initialize the TPU runtime (single-owner constraint)
        os.environ["PIO_TPU_SERVE_DEVICE"] = "host"
        os.environ["JAX_PLATFORMS"] = "cpu"

    from pio_tpu.faults import failpoint
    from pio_tpu.server.http import JsonHTTPServer
    from pio_tpu.server.query_server import create_query_server

    # chaos hook: `worker.start=crash` kills this worker before its
    # engine loads (a device owner that cannot come up)
    failpoint("worker.start")
    if spec.get("http_front"):
        # uniform front across the pool (see ServingPool._spec): the
        # listener keeps SO_REUSEPORT either way, so evloop means one
        # event loop per worker sharing the same port
        os.environ["PIO_TPU_HTTP_FRONT"] = spec["http_front"]
    variant = EngineVariant(**spec["variant"])
    # a respawn AFTER a pool-wide /reload must join its siblings on the
    # newest COMPLETED instance, not resurrect the originally pinned one
    instance_id = spec.get("instance_id") if gen.value == 0 else None
    server, service = create_query_server(
        variant,
        host=spec["host"],
        port=spec["port"],
        instance_id=instance_id,
        feedback=spec.get("feedback", False),
        feedback_app_id=spec.get("feedback_app_id"),
        admin_key=spec.get("admin_key"),
        reuse_port=True,
        slos=spec.get("slos"),
        qos=spec.get("qos"),
    )
    service.enable_pool(
        idx, spec["n_workers"], gen, shutdown_evt,
        metrics_path=spec.get("metrics_path"),
        sidecar_ports=health_ports,
    )
    if spec.get("lane_path") and lane_doorbell is not None:
        # cross-worker batch lane: worker 0 (the device owner) drains
        # every stripe into one bucketed dispatch; siblings ship their
        # query bodies over shared memory instead of scoring locally
        service.enable_batch_lane(
            spec["lane_path"], lane_doorbell, lane_resp_events,
            device=(idx == 0),
        )
    service.attach_server(server)
    server.start()
    # health sidecar: the pool shares ONE SO_REUSEPORT port, so the
    # supervisor cannot address a SPECIFIC worker through it (the kernel
    # picks the listener). Each worker therefore also serves its full
    # router on a loopback-only ephemeral port and publishes that port
    # through the shared array — the supervisor polls sidecar /healthz.
    sidecar = None
    if health_ports is not None:
        try:
            # the sidecar stays on the threaded front regardless of
            # PIO_TPU_HTTP_FRONT: it serves /healthz to the supervisor
            # and must answer even while the main front's loop is busy
            sidecar = JsonHTTPServer(
                service.router, "127.0.0.1", 0,
                name=f"pio-tpu-health-{idx}",
            )
            sidecar.start()
            health_ports[idx] = sidecar.port
        except Exception:
            log.exception("worker %d health sidecar failed to start", idx)
            sidecar = None
    log.info("pool worker %d serving on :%d", idx, server.port)
    try:
        # POLL the event — never park in Event.wait(): a worker killed
        # while registered as a sleeper on the condition (SIGTERM/OOM,
        # i.e. exactly the crashes the supervisor exists to absorb)
        # corrupts the sleeper count, after which every set()/is_set()
        # on the SHARED event blocks forever and /undeploy can no longer
        # stop the pool. is_set() holds the internal lock only for
        # microseconds, shrinking the corruption window to ~nothing.
        # Each iteration beats the heartbeat: a wedged loop ages it out
        # and the supervisor's /healthz poll turns 503.
        while not shutdown_evt.is_set():
            # chaos hook: `worker.serve=crash:once` kills this worker
            # mid-serve to exercise the supervisor's respawn/backoff path
            failpoint("worker.serve")
            service.heartbeat.beat()
            time.sleep(0.25)
    except KeyboardInterrupt:
        pass
    if sidecar is not None:
        sidecar.stop()
    server.stop()


class ServingPool:
    """Supervisor for a fixed-size SO_REUSEPORT query-server pool."""

    def __init__(
        self,
        variant: EngineVariant,
        host: str = "0.0.0.0",
        port: int = 8000,
        n_workers: int = 2,
        instance_id: Optional[str] = None,
        feedback: bool = False,
        feedback_app_id: Optional[int] = None,
        admin_key: Optional[str] = None,
        device_worker: bool = False,
        mesh_worker: bool = False,
        slos: Optional[list] = None,
        qos: Optional[str] = None,
        http_front: Optional[str] = None,
    ):
        if n_workers < 1:
            raise ValueError("n_workers must be >= 1")
        self._ctx = mp.get_context("spawn")
        self._gen = self._ctx.Value("L", 0)
        self._shutdown = self._ctx.Event()
        self._host = host
        # port 0 → reserve an ephemeral port ALL workers can share: bind a
        # SO_REUSEPORT socket here and keep it open (bound but never
        # listening, so the kernel excludes it from connection balancing)
        self._anchor: Optional[socket.socket] = None
        if port == 0:
            self._anchor = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._anchor.setsockopt(
                socket.SOL_SOCKET, socket.SO_REUSEPORT, 1
            )
            self._anchor.bind((host, 0))
            port = self._anchor.getsockname()[1]
        self.port = port
        self._spec = {
            "variant": {
                "engine_id": variant.engine_id,
                "engine_version": variant.engine_version,
                "engine_factory": variant.engine_factory,
                "variant": variant.variant,
                "path": variant.path,
            },
            "host": host,
            "port": port,
            "n_workers": n_workers,
            "instance_id": instance_id,
            "feedback": feedback,
            "feedback_app_id": feedback_app_id,
            "admin_key": admin_key,
            "device_worker": device_worker,
            "mesh_worker": mesh_worker,
            "slos": list(slos) if slos else None,
            # QoS spec string: every worker parses the same policy, and
            # because each runs identical service-init code, their QoS
            # counter cells land on the same shared-segment slots — the
            # striped token bucket depends on that alignment to enforce
            # one rps= budget POOL-WIDE (see pio_tpu/qos/limiter.py)
            "qos": qos,
            # HTTP front for every worker (threaded|evloop); None defers
            # to the worker's own PIO_TPU_HTTP_FRONT env. MUST be
            # uniform across the pool: front choice adds metric families
            # to the registry, and the shared-stripe slot layout
            # requires identical registration order in every worker
            "http_front": http_front,
        }
        self.n_workers = n_workers
        #: worker 0 owns the accelerator; its siblings can only score on
        #: the host mirror, so the pool must not serve without it
        self._owns_device = bool(device_worker or mesh_worker)
        self._procs: list = []
        #: per-reason respawn counts ({"crash": n, "unhealthy": m}) —
        #: each reason spends its own budget (_MAX_RESPAWNS_BY_REASON)
        self._respawns = [
            {r: 0 for r in _MAX_RESPAWNS_BY_REASON} for _ in range(n_workers)
        ]
        #: worker i died with an exhausted budget for its kill reason and
        #: will never be respawned again
        self._retired = [False] * n_workers
        #: monotonic deadline before which worker i must NOT be respawned
        #: (0.0 = no respawn scheduled); gives crash-looping workers an
        #: exponentially growing cool-down instead of a hot spawn loop
        self._respawn_due = [0.0] * n_workers
        self._spawned_at = [0.0] * n_workers
        #: why the supervisor last killed worker i ("unhealthy" when the
        #: health sweep shot it; None → the process died on its own)
        self._kill_reason: list = [None] * n_workers
        #: sidecar health ports, published by each worker once its
        #: loopback health server is up (0 = not yet / unavailable)
        self._health_ports = self._ctx.Array("i", [0] * n_workers)
        self._health_fails = [0] * n_workers
        from pio_tpu.obs import REGISTRY

        #: 1 = healthy, 0 = failing /healthz, -1 = process dead
        self._health_gauge = REGISTRY.gauge(
            "pio_tpu_worker_health_state",
            "Supervisor view of each pool worker "
            "(1 healthy, 0 unhealthy, -1 dead)",
            ("worker",),
        )
        self._respawn_counter = REGISTRY.counter(
            "pio_tpu_worker_respawn_total",
            "Pool workers respawned by the supervisor, by cause "
            "(crash = process died on its own, unhealthy = killed "
            "after failing /healthz probes)",
            ("reason",),
        )
        # cross-worker metrics: the supervisor owns a fixed-layout
        # shared-memory segment; every worker mmaps its own stripe, so a
        # /metrics scrape on ANY worker can sum pool-wide totals
        # (pio_tpu/obs/shm.py). Creation failure degrades to per-worker
        # metrics rather than blocking serving.
        self._metrics_seg = None
        try:
            from pio_tpu.obs.shm import PoolMetricsSegment

            fd, seg_path = tempfile.mkstemp(
                prefix="pio-tpu-pool-metrics-", suffix=".shm"
            )
            os.close(fd)
            self._metrics_seg = PoolMetricsSegment.create(
                seg_path, n_workers
            )
            self._spec["metrics_path"] = seg_path
        except Exception:
            log.exception(
                "pool metrics segment creation failed; workers expose "
                "per-worker metrics only"
            )
        # cross-worker batch lane (ISSUE 7): only meaningful when ONE
        # worker owns the accelerator (device_worker) and there are
        # siblings to aggregate — a homogeneous CPU pool serves faster
        # per-process than funneled through one drainer. PIO_TPU_BATCH_LANE=0
        # force-disables.
        self._lane_seg = None
        self._lane_doorbell = None
        self._lane_resp_events = None
        if (
            (device_worker or mesh_worker) and n_workers > 1
            and knobs.knob_str("PIO_TPU_BATCH_LANE") != "0"
        ):
            try:
                from pio_tpu.server.batchlane import BatchLaneSegment

                fd, lane_path = tempfile.mkstemp(
                    prefix="pio-tpu-batch-lane-", suffix=".shm"
                )
                os.close(fd)
                self._lane_seg = BatchLaneSegment.create(
                    lane_path, n_workers
                )
                self._spec["lane_path"] = lane_path
                self._lane_doorbell = self._ctx.Event()
                self._lane_resp_events = [
                    self._ctx.Event() for _ in range(n_workers)
                ]
            except Exception:
                log.exception(
                    "batch lane segment creation failed; workers serve "
                    "locally"
                )
                self._lane_seg = None

    def _spawn(self, idx: int):
        self._health_ports[idx] = 0  # stale port from a previous life
        self._health_fails[idx] = 0
        self._spawned_at[idx] = monotonic_s()
        if getattr(self, "_metrics_seg", None) is not None:
            # stripe ownership handover (ISSUE 11): first spawn takes the
            # stripe at generation 1; every respawn bumps it so scrapers
            # can tell counter adoption from traffic
            try:
                self._metrics_seg.bump_generation(idx)
            except (OSError, ValueError, IndexError):
                log.exception("stripe generation bump failed (worker %d)",
                              idx)
        p = self._ctx.Process(
            target=_worker_main,
            args=(
                self._spec, idx, self._gen, self._shutdown,
                self._health_ports, self._lane_doorbell,
                self._lane_resp_events,
            ),
            name=f"pio-tpu-serve-{idx}",
            daemon=True,
        )
        p.start()
        return p

    def start(self) -> "ServingPool":
        self._procs = [self._spawn(i) for i in range(self.n_workers)]
        return self

    def wait_ready(self, timeout: float = 60.0) -> None:
        """Block until the pool reports READY (deploy readiness): a plain
        TCP accept is not enough — a worker accepts connections before
        its engine finished loading — so this polls ``GET /readyz`` until
        a 200. A plain pool is ready when ANY worker is (the shared
        port). A device-owning pool is ready only when WORKER 0 is (its
        loopback sidecar): a sibling answering first would have the pool
        serve from the CPU while the chip's owner is still — or never —
        coming up. Worker 0 exiting before it is ready stops the pool."""
        import urllib.error
        import urllib.request

        deadline = monotonic_s() + timeout
        last_err: Optional[BaseException] = None
        probe_host = (
            "127.0.0.1" if self._host in ("", "0.0.0.0", "::")
            else self._host
        )
        while monotonic_s() < deadline:
            if self._shutdown.is_set():
                raise RuntimeError("pool shut down during startup")
            if self._owns_device:
                if not self._procs[0].is_alive():
                    self.stop()
                    raise RuntimeError(
                        "device-owning worker 0 exited during startup "
                        f"(code {self._procs[0].exitcode}); pool stopped"
                    )
                if self._health_ports[0] <= 0:  # sidecar not up yet
                    time.sleep(0.1)
                    continue
                url = f"http://127.0.0.1:{self._health_ports[0]}/readyz"
            else:
                url = f"http://{probe_host}:{self.port}/readyz"
            try:
                with urllib.request.urlopen(url, timeout=2.0) as r:
                    if r.status == 200:
                        return
            except urllib.error.HTTPError as e:
                last_err = e  # reachable but not ready (503) — keep polling
            except OSError as e:
                last_err = e
                if all(not p.is_alive() for p in self._procs):
                    raise RuntimeError(
                        "every pool worker exited during startup"
                    ) from e
            time.sleep(0.1)
        raise TimeoutError(
            f"no pool worker ready on :{self.port}: {last_err}"
        )

    def _poll_worker_health(self, idx: int) -> Optional[bool]:
        """One /healthz probe of worker ``idx``'s loopback sidecar.
        None = no sidecar port published yet (can't judge)."""
        port = self._health_ports[idx]
        if port <= 0:
            return None
        import urllib.request

        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=1.0
            ) as r:
                return r.status == 200
        except Exception:
            # 503 raises HTTPError; a wedged worker times out — both are
            # health failures for the consecutive-failure counter
            return False

    def _health_sweep(self) -> None:
        """Poll every live worker's sidecar; kill a worker after
        ``_HEALTH_FAILS_TO_KILL`` consecutive failures so the existing
        crash-respawn path (respawn budget included) replaces it. Kill,
        not terminate: a wedged process may ignore SIGTERM."""
        for i, p in enumerate(self._procs):
            if not p.is_alive():
                self._health_gauge.set(-1, worker=str(i))
                continue
            res = self._poll_worker_health(i)
            if res is None:
                continue
            if res:
                self._health_fails[i] = 0
                self._health_gauge.set(1, worker=str(i))
                continue
            self._health_fails[i] += 1
            self._health_gauge.set(0, worker=str(i))
            log.warning(
                "worker %d failed /healthz (%d/%d consecutive)",
                i, self._health_fails[i], _HEALTH_FAILS_TO_KILL,
            )
            if self._health_fails[i] >= _HEALTH_FAILS_TO_KILL:
                log.error(
                    "worker %d unhealthy %d polls in a row; killing for "
                    "respawn", i, self._health_fails[i],
                )
                self._kill_reason[i] = "unhealthy"
                p.kill()
                p.join(timeout=2.0)

    def _account_death(self, i: int, exitcode, now: float) -> None:
        """Account one observed worker death against the kill reason's
        own respawn budget and schedule the backed-off respawn (or
        retire the worker when that reason's budget is spent)."""
        if self._retired[i]:
            return
        if (
            self._spawned_at[i] > 0.0
            and now - self._spawned_at[i] >= _RESPAWN_RESET_AFTER_S
        ):
            # long-lived worker: this death is not a crash loop
            for r in self._respawns[i]:
                self._respawns[i][r] = 0
        reason = self._kill_reason[i] or "crash"
        self._kill_reason[i] = None
        budget = _MAX_RESPAWNS_BY_REASON.get(reason, _MAX_RESPAWNS)
        if self._respawns[i].get(reason, 0) >= budget:
            log.error(
                "worker %d died %d times (reason %s); not respawning",
                i, self._respawns[i][reason], reason,
            )
            self._retired[i] = True
            if i == 0 and self._owns_device:
                # the siblings are pinned to the host mirror: without
                # its device owner the pool would keep answering from
                # the CPU, so it stops instead
                log.error(
                    "device-owning worker 0 retired; stopping the pool"
                )
                self._shutdown.set()
            if getattr(self, "_metrics_seg", None) is not None:
                # freeze the stripe: negative generation marks "retired,
                # totals retained" so pool/fleet scrapes keep the sums
                # but know they will never move again
                try:
                    self._metrics_seg.retire_stripe(i)
                except (OSError, ValueError, IndexError):
                    log.exception(
                        "stripe retirement failed (worker %d)", i
                    )
            return
        self._respawns[i][reason] = self._respawns[i].get(reason, 0) + 1
        self._respawn_counter.inc(reason=reason)
        # backoff grows with THIS reason's streak: a worker the health
        # sweep shot once does not inherit the cool-down its earlier
        # crashes earned
        delay = min(
            _RESPAWN_BACKOFF_CAP_S,
            _RESPAWN_BACKOFF_BASE_S
            * 2 ** (self._respawns[i][reason] - 1),
        )
        self._respawn_due[i] = now + delay
        log.warning(
            "worker %d exited (code %s, reason %s); respawning in "
            "%.1fs (%d/%d)",
            i, exitcode, reason, delay, self._respawns[i][reason], budget,
        )

    def wait(self, poll_s: float = 0.5,
             health_poll_s: float = 2.0) -> None:
        """Supervise until /undeploy (or stop()): respawn crashed workers
        within budget, kill-and-respawn workers that fail /healthz
        ``_HEALTH_FAILS_TO_KILL`` polls in a row, then reap everything
        once the event fires."""
        next_health = monotonic_s() + health_poll_s
        while not self._shutdown.is_set():
            if monotonic_s() >= next_health:
                next_health = monotonic_s() + health_poll_s
                self._health_sweep()
            now = monotonic_s()
            for i, p in enumerate(self._procs):
                if p.is_alive() or self._shutdown.is_set():
                    continue
                if self._respawn_due[i] > 0.0:
                    # phase 2: a respawn is scheduled — spawn once the
                    # backoff cool-down has elapsed
                    if now >= self._respawn_due[i]:
                        self._respawn_due[i] = 0.0
                        self._procs[i] = self._spawn(i)
                    continue
                # phase 1: first observation of this death — account for
                # it and schedule the (possibly delayed) respawn
                self._account_death(i, p.exitcode, now)
            if all(
                not p.is_alive() for p in self._procs
            ) and all(self._retired) and not any(
                d > 0.0 for d in self._respawn_due
            ):
                log.error("all workers dead and out of respawn budget")
                break
            # plain sleep, not Event.wait(): nobody ever registers as a
            # sleeper on the shared event, so a killed process can never
            # corrupt it (see the matching note in _worker_main)
            time.sleep(poll_s)
        self.stop()

    def stop(self, join_timeout: float = 5.0) -> None:
        self._shutdown.set()
        for p in self._procs:
            p.join(timeout=join_timeout)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=2.0)
        if self._anchor is not None:
            self._anchor.close()
            self._anchor = None
        if self._metrics_seg is not None:
            try:
                self._metrics_seg.close()
                self._metrics_seg.unlink()
            except OSError:
                pass
            self._metrics_seg = None
        if self._lane_seg is not None:
            try:
                self._lane_seg.close()
                self._lane_seg.unlink()
            except OSError:
                pass
            self._lane_seg = None
