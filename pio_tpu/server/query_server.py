"""Query Server — per-engine HTTP serving daemon (:8000 by default).

Rebuild of the reference's ``core/.../workflow/CreateServer.scala``
(MasterActor/ServerActor — UNVERIFIED path; see SURVEY.md). Routes:

    GET  /               status (engine, instance, uptime, request counts)
    POST /queries.json   typed query → serving.serve over all algorithms
    GET  /stats.json     request count + latency stats
    POST /reload         hot-swap to the latest COMPLETED engine instance
    POST /undeploy       stop accepting queries (reference `pio undeploy`)

Queries bind to the algorithm's declared ``query_class`` dataclass (the
JsonExtractor queryClassTag analog); responses use ``to_dict()`` when the
prediction provides it. When ``feedback`` is enabled, every response is
logged back to the event store as a ``predict`` event on entity type
``pio_pr`` carrying the prId — the reference's feedback loop.
"""

from __future__ import annotations

import collections
import dataclasses
import datetime as _dt
import json
import logging
import threading
import uuid
from typing import Any, List, Optional, Tuple

from pio_tpu.utils import knobs
from pio_tpu.analysis.runtime import make_condition, make_lock
from pio_tpu.controller.engine import Engine, EngineParams
from pio_tpu.controller.params import ParamsError, params_from_dict
from pio_tpu.data.event import Event
from pio_tpu.faults import failpoint
from pio_tpu.obs import (
    Heartbeat, HealthMonitor, MetricsRegistry, RequestWindow, TRACE_HEADER,
    Tracer, add_active_span, hotpath_payload, monotonic_s,
    parse_trace_header,
)
from pio_tpu.obs import devicewatch, slog
from pio_tpu.obs.profile import DeviceProfileHook
from pio_tpu.obs.slo import engine_for_specs
from pio_tpu.parallel.context import ComputeContext
from pio_tpu.qos import (
    DEADLINE_HEADER, DEGRADED_HEADER, DEGRADED_VALUE, PRIORITY_HEADER,
    Deadline, DeadlineExceeded, QoSGate, cache_key, resolve_policy,
    retry_after_header,
)
from pio_tpu.server.batchlane import (
    BatchLaneSegment, LaneClient, LaneDrainer, LaneFallback, PackedQuery,
    pack_query_i8, packed_frame_ok, unpack_query_i8,
)
from pio_tpu.server.bucketcache import (
    BucketExecutionCache, dispatch_bucketed,
)
from pio_tpu.server.http import (
    HTTPError, JsonHTTPServer, RawResponse, Request, Router, float_param,
    int_param, json_response, keys_equal, metrics_response,
    ssl_context_from_env,
)
from pio_tpu.storage import Storage
from pio_tpu.workflow.core_workflow import load_models_for_instance
from pio_tpu.workflow.deploy_common import (
    resolve_instance_id,
    resolve_query_class,
    to_jsonable as _to_jsonable,
)
from pio_tpu.workflow.engine_json import EngineVariant, build_engine

log = logging.getLogger("pio_tpu.queryserver")

#: query-path plugin hooks (reference EngineServerPlugin)
QUERY_BLOCKERS: List = []
QUERY_SNIFFERS: List = []

#: sentinel: the micro-batch dispatch failed; the waiting request thread
#: runs the per-query fallback itself (see _MicroBatcher.submit)
_BATCH_FAILED = object()

#: query-path trace stages, in request order: socket read + body parse
#: (measured by the HTTP layer), QoS admission, JSON binding +
#: serving.supplement, micro-batch queue wait, device/model execute,
#: response serialization (to_jsonable + hooks + feedback), response
#: write. Top-level stages TILE the request — their durations sum to the
#: end-to-end latency — which is what /debug/hotpath.json budgets against.
QUERY_STAGES = (
    "accept", "admit", "parse", "queue", "execute", "serialize", "write",
)

#: dotted substages attribute time WITHIN a top-level stage (excluded
#: from budget sums — the microseconds are already counted above).
#: Pre-declared so their histogram cells exist at pool-bind time.
QUERY_SUBSTAGES = ("admit.queue", "execute.device")


def _stripe_generation_lines(seg) -> list:
    """Exposition lines for ``pio_tpu_pool_stripe_generation`` — read
    fresh from the shared segment at every scrape (the supervisor, a
    different process, owns the generation words)."""
    lines = [
        "# HELP pio_tpu_pool_stripe_generation Pool metrics stripe "
        "ownership generation per worker slot (bumped at every respawn; "
        "negative = retired, totals frozen)",
        "# TYPE pio_tpu_pool_stripe_generation gauge",
    ]
    for w, g in enumerate(seg.generations()):
        lines.append(
            f'pio_tpu_pool_stripe_generation{{worker="{w}"}} {g}'
        )
    return lines


def _q_ms(cell, q: float):
    """Histogram-cell quantile in milliseconds (None when empty)."""
    v = cell.quantile(q)
    return round(v * 1e3, 3) if v is not None else None


class _MicroBatcher:
    """Coalesces concurrent ``/queries.json`` requests into one
    ``algo.batch_predict`` dispatch — WHEN that wins.

    The reference serves strictly per-request (one ``predictBase`` per
    HTTP call on the driver JVM). On an accelerator the per-dispatch
    round trip dominates single-query cost, so under concurrent load it
    pays to aggregate: request threads enqueue their (already parsed +
    supplemented) query and block; a worker drains the queue after a
    short collection window and pushes the whole batch through each
    algorithm's ``batch_predict`` — for factor-serving templates that is
    ONE ``[B, K] @ [K, N]`` device matmul + top-k instead of B separate
    dispatches — then serves each query individually.

    **Adaptive bypass.** Whether coalescing wins depends on the deploy:
    on a device-resident scorer with real per-dispatch RTT it does; on a
    host-mirror scorer the extra condition-variable handoffs can cost
    more than the batched matmul saves (measured losing in the round-3
    driver bench). Predicting that from first principles is guesswork,
    so the batcher measures it live: the first ``PROBE_QUERIES``
    requests run coalesced, the next ``PROBE_QUERIES`` run per-request
    in the caller's thread, and whichever regime had the lower median
    request latency under the SAME live load becomes permanent
    (Little's law: under fixed concurrency, lower mean latency ⇔ higher
    throughput). ``PIO_TPU_SERVE_MICROBATCH_ADAPTIVE=0`` pins it on.

    Enabled via ``PIO_TPU_SERVE_MICROBATCH_US`` (collection window in
    microseconds; unset/0 = off, classic per-request path). If a batch
    dispatch fails, every member falls back to the per-query path so one
    poisoned query cannot fail its batch-mates.
    """

    MAX_BATCH = 512
    #: dispatch this far BEFORE the tightest queued deadline: waking at
    #: the exact expiry instant would shed the very member the deadline
    #: bound exists to protect (cond.wait also overshoots under load).
    #: A member whose remaining budget is already under the slack
    #: dispatches immediately instead of waiting out the window.
    DEADLINE_SLACK_S = 0.05
    #: probe sample size per regime before the permanent mode decision.
    #: Only the chronologically LAST half of each window is compared —
    #: the first batches of a fresh deploy pay one-off XLA bucket
    #: compiles (seconds-scale) that would otherwise poison the batched
    #: median and lock in "off" exactly where coalescing wins.
    PROBE_QUERIES = 96

    def __init__(self, service: "QueryServerService", window_s: float,
                 adaptive: bool = True):
        self._service = service
        self._window_s = window_s
        self._cv = make_condition("query.microbatch")
        self._queue: List[list] = []
        self._stopped = False
        self.batches = 0
        self.batched_queries = 0
        self.max_batch = 0
        #: probe_batch → probe_solo → on | off
        self._mode = "probe_batch" if adaptive else "on"
        #: set when the probe decides "off" — query() then skips the
        #: batcher entirely (inline per-request path, no residual cost)
        self.bypassed = False
        #: an "off" verdict is re-examined this often: the early probe
        #: can catch compile transients / cold caches that a warmed
        #: server has long outgrown — "off" is a lease, not a latch
        #: (0 disables re-probing and restores the one-shot behavior)
        self._reprobe_s = knobs.knob_float("PIO_TPU_MB_REPROBE_S")
        self._decided_at = 0.0
        self.reprobes = 0
        self._probe_lock = make_lock("query.microbatch.probe")
        self._probe: dict = {"batch": [], "solo": []}
        #: per-bucket batched per-member latency samples (bounded ring,
        #: fresh-bucket dispatches excluded) — the post-warmup honesty
        #: map behind ``modeByBucket``: the single ``mode`` string is
        #: one global verdict, but whether coalescing wins is a
        #: PER-BUCKET question (a 64-wide dispatch amortizes RTT that a
        #: 1-wide dispatch only adds handoffs to)
        self._bucket_samples: dict = {}
        self._thread = threading.Thread(
            target=self._run, name="pio-tpu-microbatch", daemon=True
        )
        self._thread.start()

    def active(self) -> bool:
        """Should queries flow through the batcher? Cheap hot-path check
        that doubles as the re-probe trigger: once an "off" verdict has
        aged past the re-probe interval, the probe windows reset and the
        next requests measure again — a verdict poisoned by deploy-time
        transients (bucket compiles, cold caches) heals instead of
        sticking for the server's lifetime."""
        if not self.bypassed:
            return True
        if self._reprobe_s <= 0 \
                or monotonic_s() - self._decided_at < self._reprobe_s:
            return False
        with self._probe_lock:
            if not self.bypassed:  # another thread re-armed first
                return True
            self._probe = {"batch": [], "solo": []}
            self._mode = "probe_batch"
            self.bypassed = False
            self.reprobes += 1
        log.info(
            "micro-batch re-probe: re-measuring after %.0fs in bypass",
            self._reprobe_s,
        )
        return True

    def submit(self, query, span_sink=None, deadline=None):  # pio: hotpath
        """Serve one query through the current regime; blocks until done.
        If the batch dispatch failed, the fallback per-query predict runs
        HERE — in the request's own thread — so one poisoned query
        degrades its batch-mates to ordinary concurrent serving, not to a
        serial queue behind the single worker.

        ``span_sink`` (a trace handle with ``add_span``) receives the
        queue-wait and execute stage timings measured where they actually
        happen — the worker thread computes per-member queue wait at
        drain time and the shared batch dispatch duration.

        ``deadline`` (a :class:`pio_tpu.qos.Deadline`, optional) rides
        along in the pend entry: the worker sheds members whose budget
        elapsed in queue BEFORE dispatching the batch (raised here as
        ``DeadlineExceeded``) and never stretches the collection window
        past the tightest queued deadline."""
        mode = self._mode
        if mode == "off" or mode == "probe_solo":
            t0 = monotonic_s()
            out = self._service._predict_one(query)
            dt = monotonic_s() - t0
            if span_sink is not None:
                span_sink.add_span("queue", 0.0)
                span_sink.add_span("execute", dt)
            if mode == "probe_solo":
                self._note_probe("solo", dt)
            return out
        t0 = monotonic_s()
        # q, result, exc, done, enqueue_t, stage timings (worker-filled),
        # deadline, member trace id (the batch trace links its members)
        pend = [query, None, None, threading.Event(), t0, {}, deadline,
                span_sink.trace_id if span_sink is not None else None]
        with self._cv:
            if self._stopped:
                raise HTTPError(503, "undeployed")
            self._queue.append(pend)
            self._cv.notify()
        # submit IS the synchronous rendezvous: the request thread
        # parks until its batch completes
        # pio: disable=hotpath-blocking
        pend[3].wait()
        if mode == "probe_batch" and not pend[5].get("fresh_bucket"):
            # a dispatch that compiled a fresh shape bucket is a one-off
            # deploy transient, not the steady state the probe compares —
            # discard the whole batch's samples (satellite of ISSUE 7:
            # the old probe latched "off" on exactly these)
            self._note_probe("batch", monotonic_s() - t0)
        if span_sink is not None and "queue_s" in pend[5]:
            span_sink.add_span("queue", pend[5]["queue_s"])
        if span_sink is not None and "batch_id" in pend[5]:
            # back-link: the member's waterfall names the batch trace
            # whose execute span it shared
            span_sink.note(microbatch=pend[5]["batch_id"])
        if pend[2] is _BATCH_FAILED:
            t1 = monotonic_s()
            out = self._service._predict_one(pend[0])
            if span_sink is not None:
                span_sink.add_span("execute", monotonic_s() - t1)
            return out
        if span_sink is not None and "execute_s" in pend[5]:
            span_sink.add_span("execute", pend[5]["execute_s"])
        if pend[2] is not None:
            raise pend[2]
        return pend[1]

    def _note_probe(self, kind: str, dt: float) -> None:
        with self._probe_lock:
            samples = self._probe[kind]
            samples.append(dt)
            if len(samples) < self.PROBE_QUERIES:
                return
            if kind == "batch" and self._mode == "probe_batch":
                self._mode = "probe_solo"
            elif kind == "solo" and self._mode == "probe_solo":
                # steady-state comparison: drop each window's first half
                # (bucket-compile and cache warmup transients land there)
                med = lambda xs: sorted(xs[len(xs) // 2:])[len(xs) // 4]
                batch_med = med(self._probe["batch"])
                solo_med = med(self._probe["solo"])
                self._mode = "on" if batch_med <= solo_med else "off"
                log.info(
                    "micro-batch probe: batched p50 %.3f ms vs per-query "
                    "p50 %.3f ms under live load -> %s",
                    batch_med * 1e3, solo_med * 1e3, self._mode,
                )
                if self._mode == "off":
                    # true bypass: the query path re-checks this flag and
                    # goes back to inline per-request serving, byte-for-
                    # byte the no-batcher code path (zero residual cost
                    # beyond the aged-verdict check in active())
                    self.bypassed = True
                    self._decided_at = monotonic_s()

    @property
    def mode(self) -> str:
        """Current regime (lock-free read — for cheap polling)."""
        return self._mode

    def stop(self):
        with self._cv:
            self._stopped = True
            self._cv.notify()

    def to_dict(self) -> dict:
        with self._probe_lock:
            med = lambda xs: (
                round(sorted(xs)[len(xs) // 2] * 1e3, 3) if xs else None
            )
            probe = {
                "batchedP50Ms": med(self._probe["batch"]),
                "perQueryP50Ms": med(self._probe["solo"]),
            }
            solo = sorted(self._probe["solo"])
            solo_med = solo[len(solo) // 2] if solo else None
        # post-warmup per-bucket verdict: each bucket's batched
        # per-member p50 against the probe's per-query p50 — the honest
        # answer to "which batch sizes is coalescing actually winning
        # at", where the single `mode` string collapses them all
        mode_by_bucket = {}
        for b in sorted(self._bucket_samples):
            xs = sorted(self._bucket_samples[b])
            if not xs:
                continue
            p50 = xs[len(xs) // 2]
            mode_by_bucket[str(b)] = {
                "mode": (
                    "on" if solo_med is None or p50 <= solo_med else "off"
                ),
                "p50Ms": round(p50 * 1e3, 3),
                "samples": len(xs),
            }
        return {
            "mode": self._mode,
            "modeByBucket": mode_by_bucket,
            "probe": probe,
            "batches": self.batches,
            "batchedQueries": self.batched_queries,
            "maxBatch": self.max_batch,
            "windowUs": round(self._window_s * 1e6),
            "reprobeSeconds": self._reprobe_s,
            "reprobes": self.reprobes,
            "bypassed": self.bypassed,
        }

    def _run(self):  # pio: hotpath
        while True:
            with self._cv:
                while not self._queue and not self._stopped:
                    # idle park: nothing to batch until an enqueue
                    # notifies
                    # pio: disable=hotpath-blocking
                    self._cv.wait()
                if self._stopped and not self._queue:
                    return
            # collection window: let concurrent request threads pile on —
            # but don't idle when a full batch is already waiting, and
            # never wait past the tightest queued deadline (the batch
            # honors its most impatient member). Waiting happens on the
            # condition variable, NOT a blind sleep: every enqueue
            # notifies, so a member arriving mid-window with a TIGHTER
            # deadline re-shortens the wait instead of expiring in queue
            # behind a window computed before it existed.
            if self._window_s > 0:
                window_end = monotonic_s() + self._window_s
                with self._cv:
                    while not self._stopped \
                            and len(self._queue) < self.MAX_BATCH:
                        wait_s = window_end - monotonic_s()
                        tightest = min(
                            (p[6].remaining_s() for p in self._queue
                             if p[6] is not None),
                            default=None,
                        )
                        if tightest is not None:
                            wait_s = min(
                                wait_s, tightest - self.DEADLINE_SLACK_S
                            )
                        if wait_s <= 0:
                            break
                        # deadline-bounded collection window (see
                        # comment above) — not a blind stall
                        # pio: disable=hotpath-blocking
                        self._cv.wait(wait_s)
            with self._cv:
                batch = self._queue[: self.MAX_BATCH]
                del self._queue[: len(batch)]
            if not batch:
                continue
            # stage attribution: everything before the drain is queue
            # wait (per member — each enqueued at its own time), the
            # shared dispatch below is each member's execute time
            t_drain = monotonic_s()
            for p in batch:
                p[5]["queue_s"] = max(t_drain - p[4], 0.0)
            # deadline shedding: a member whose budget elapsed in queue
            # is failed HERE, before the model runs — its client already
            # gave up, and executing it would only slow its batch-mates
            live = []
            for p in batch:
                if p[6] is not None and p[6].expired():
                    p[2] = DeadlineExceeded("deadline elapsed in queue")
                    p[3].set()
                else:
                    live.append(p)
            batch = live
            if not batch:
                continue
            self.batches += 1
            self.batched_queries += len(batch)
            self.max_batch = max(self.max_batch, len(batch))
            try:
                # the batch dispatch gets ONE trace linking every member
                # request trace — "which requests shared this dispatch"
                # becomes answerable from /traces.json. Device time lands
                # on it as execute.device via the active-trace contextvar.
                queries = [p[0] for p in batch]
                # freshness from the warmed-set snapshot, not the
                # dispatch return: _predict_batch is the seam tests and
                # profilers wrap, so the batcher must go through it
                cache = self._service._buckets
                pre_warmed = cache.warmed
                fresh = any(
                    cache.bucket_for(n) not in pre_warmed
                    for n in cache.chunks(len(queries))
                )
                with self._service.tracer.trace(
                    "microbatch",
                    links=[p[7] for p in batch if p[7]],
                    batch=len(batch),
                ) as btr:
                    results = self._service._predict_batch(queries)
                exec_s = monotonic_s() - t_drain
                bucket = cache.bucket_for(len(batch))
                samples = self._bucket_samples.get(bucket)
                if samples is None:
                    samples = self._bucket_samples[bucket] = (
                        collections.deque(maxlen=64)
                    )
                for p, r in zip(batch, results):
                    p[1] = r
                    p[5]["execute_s"] = exec_s
                    p[5]["batch_id"] = btr.trace_id
                    if fresh:
                        # this dispatch paid a bucket compile — flag every
                        # member so the probe discards the transient
                        p[5]["fresh_bucket"] = True
                    else:
                        # per-member request latency (queue + execute)
                        # under this bucket, steady-state samples only
                        samples.append(p[5]["queue_s"] + exec_s)
            except Exception:
                log.exception(
                    "micro-batch dispatch failed; per-query fallback "
                    "(runs in each request's own thread)"
                )
                for p in batch:
                    p[2] = _BATCH_FAILED
            for p in batch:
                p[3].set()


class QueryServerService:
    """The ServerActor analog; MasterActor duties (reload/undeploy) included."""

    def __init__(
        self,
        variant: EngineVariant,
        instance_id: Optional[str] = None,
        ctx: Optional[ComputeContext] = None,
        feedback: bool = False,
        feedback_app_id: Optional[int] = None,
        admin_key: Optional[str] = None,
        slos: Optional[List[str]] = None,
        qos: Optional[Any] = None,
    ):
        self.variant = variant
        self.ctx = ctx or ComputeContext.create()
        self.feedback = feedback
        self.feedback_app_id = feedback_app_id
        #: guards /reload and /undeploy; without a key only loopback clients
        #: may call them (the default bind is 0.0.0.0)
        self.admin_key = admin_key
        self.start_time = _dt.datetime.now(_dt.timezone.utc)
        #: per-instance registry (not the process-global one) so embedded
        #: test servers never cross-pollinate each other's scrapes
        self.obs = MetricsRegistry()
        eng = variant.engine_id
        self._queries_total = self.obs.counter(
            "pio_tpu_queries_total", "Queries served", ("engine_id",)
        )
        self._query_errors_total = self.obs.counter(
            "pio_tpu_query_errors_total", "Queries that errored", ("engine_id",)
        )
        #: full-request latency histogram — the SLO engine's latency
        #: source (stage histograms cover WHERE time went; this one
        #: covers the request the client saw)
        self._request_hist = self.obs.histogram(
            "pio_tpu_request_seconds",
            "Full-request wall seconds of /queries.json",
            ("engine_id",),
        )
        #: end-to-end latency histogram (accept→write, stamped from the
        #: post-write hook): what the CLIENT saw, and the denominator of
        #: the /debug/hotpath.json attribution budget
        self._e2e_hist = self.obs.histogram(
            "pio_tpu_e2e_seconds",
            "End-to-end wall seconds of /queries.json (socket read "
            "through response write)",
            ("engine_id",),
        )
        # pre-create the cells so pool-mode slot layout sees them at init
        self._queries_total.labels(eng)
        self._query_errors_total.labels(eng)
        self._request_cell = self._request_hist.labels(eng)
        self._e2e_cell = self._e2e_hist.labels(eng)
        #: set by mark_evloop_front() when the evloop HTTP front serves
        #: this service: handlers run inline in the event loop, so the
        #: micro-batcher's blocking hand-off must be bypassed
        self._evloop_front = False
        self._parse_fastpath_total = self.obs.counter(
            "pio_tpu_http_parse_fastpath_total",
            "Packed binary query requests by outcome: hit = zero-copy "
            "socket→lane frame, local = served by the local packed "
            "fallback, invalid = malformed frame (400), unavailable = "
            "no single int8 resident scorer to decode it (400)",
            ("outcome",),
        )
        #: bound outcome cells — the packed hot path bumps one per
        #: request; labels() resolution there would cost more than the
        #: increment (see _Cell.inc)
        self._fastpath_cells = {
            outcome: self._parse_fastpath_total.labels(outcome)
            for outcome in ("hit", "local", "invalid", "unavailable")
        }
        self.tracer = Tracer(
            "query", registry=self.obs,
            stages=QUERY_STAGES + QUERY_SUBSTAGES,
            extra_labels={"engine_id": eng},
        )
        # tail-based slow-trace capture: threshold from (in order) the
        # PIO_TPU_SLOW_TRACE_MS override, the tightest latency SLO, or
        # the live p99 estimate once there is enough signal
        self.tracer.slow_threshold_fn = self._slow_threshold_s
        self.stats = RequestWindow()
        self.obs.add_collector(self._compat_metric_lines)
        # structured-log ring (process-wide install is record-only; the
        # CLI switches console rendering) + log-volume counter re-export
        slog.install()
        self.obs.add_collector(slog.exposition_lines)
        from pio_tpu import faults as _faults

        self.obs.add_collector(_faults.exposition_lines)
        # -- health probes (ISSUE 2) --
        self.heartbeat = Heartbeat(max_age_s=knobs.knob_float(
            "PIO_TPU_HEARTBEAT_MAX_AGE_S"
        ))
        self.health = HealthMonitor()
        self.health.add_liveness("http_loop", self._http_loop_alive)
        self.health.add_critical_thread(
            "microbatch_worker",
            lambda: getattr(self._batcher, "_thread", None),
        )
        self.health.add_readiness("engine", self._check_engine_ready)
        self.health.add_readiness("storage", self._check_storage_ready)
        # -- SLO engine (ISSUE 2): specs from the caller or PIO_TPU_SLO --
        if slos is None:
            env_slos = knobs.knob_str("PIO_TPU_SLO")
            slos = [s for s in env_slos.split(",") if s.strip()]
        self.slo = None
        if slos:
            self.slo = engine_for_specs(
                slos, self.obs,
                availability_source=self._availability_good_total,
                latency_cell_getter=lambda: self._request_cell,
            )
        # -- QoS (ISSUE 3): admission control, deadlines, degradation.
        # The gate's counters MUST be created here (before any
        # enable_pool bind) so its shed/admitted cells land in the shared
        # segment and the rps= budget is enforced POOL-WIDE.
        policy = resolve_policy(qos, variant.variant)
        self.qos = (
            QoSGate(policy, self.obs, scope="queryserver")
            if policy is not None else None
        )
        self._scorer_breaker = (
            self.qos.breaker("scorer") if self.qos is not None else None
        )
        # -- shape-bucket execution cache (ISSUE 7): every batched
        # dispatch is padded to a fixed bucket ladder so steady-state
        # serving never retraces; the warmup sweep in _load compiles the
        # ladder at deploy. Metrics MUST be created (and their label
        # cells pre-created) here, before any enable_pool bind, so the
        # retrace/dispatch counters land in the shared segment.
        self._buckets = BucketExecutionCache()
        self._bucket_dispatch_total = self.obs.counter(
            "pio_tpu_bucket_dispatch_total",
            "Batched dispatches by shape bucket (padded batch size)",
            ("engine_id", "bucket"),
        )
        self._bucket_retrace_total = self.obs.counter(
            "pio_tpu_bucket_retrace_total",
            "Batched dispatches that hit a cold shape bucket (paid an "
            "XLA trace+compile the warmup sweep should have absorbed); "
            "flat in steady state",
            ("engine_id",),
        )
        self._bucket_evictions_total = self.obs.counter(
            "pio_tpu_bucket_evictions_total",
            "Model hot-swaps that evicted the previous generation's "
            "warmed bucket entries",
            ("engine_id",),
        )
        self._bucket_occupancy = self.obs.histogram(
            "pio_tpu_bucket_occupancy_ratio",
            "Real batch size over bucket size per dispatch (1.0 = no "
            "padding waste)",
            ("engine_id",),
            buckets=(0.25, 0.5, 0.75, 0.9, 1.0),
        )
        self._bucket_entries = self.obs.gauge(
            "pio_tpu_bucket_entries",
            "Warmed shape-bucket entries for the deployed generation",
            ("engine_id",),
        )
        for b in self._buckets.buckets:
            self._bucket_dispatch_total.labels(eng, str(b))
        self._bucket_retrace_total.labels(eng)
        self._bucket_evictions_total.labels(eng)
        self._bucket_occ_cell = self._bucket_occupancy.labels(eng)
        self._bucket_entries.labels(eng)
        # -- cross-worker batch lane (ISSUE 7): wired by
        # enable_batch_lane() in pool mode; counters declared up front
        # for the same pool-bind reason as above
        self._lane_client = None
        self._lane_drainer = None
        self._lane_seg = None
        self._lane_enqueued_total = self.obs.counter(
            "pio_tpu_batchlane_enqueued_total",
            "Queries this worker served through the shared-memory batch "
            "lane (answered by the device worker's bucketed dispatch)",
            ("engine_id",),
        )
        self._lane_drained_total = self.obs.counter(
            "pio_tpu_batchlane_drained_total",
            "Lane requests the device worker drained across all stripes",
            ("engine_id",),
        )
        self._lane_batches_total = self.obs.counter(
            "pio_tpu_batchlane_batches_total",
            "Cross-worker lane drain cycles served as one bucketed "
            "dispatch",
            ("engine_id",),
        )
        self._lane_full_total = self.obs.counter(
            "pio_tpu_batchlane_full_total",
            "Lane submissions that fell back to local predict because "
            "this worker's stripe had no free slot",
            ("engine_id",),
        )
        self._lane_fallback_total = self.obs.counter(
            "pio_tpu_batchlane_fallback_total",
            "Lane submissions served by the local fallback path, by "
            "reason (full, timeout, oversize, remote_error, ...)",
            ("engine_id", "reason"),
        )
        self._lane_depth = self.obs.gauge(
            "pio_tpu_batchlane_depth",
            "Unanswered lane requests across all stripes at last drain",
            ("engine_id",),
        )
        self._lane_enqueued_total.labels(eng)
        self._lane_drained_total.labels(eng)
        self._lane_batches_total.labels(eng)
        self._lane_full_total.labels(eng)
        for reason in ("full", "timeout", "oversize", "remote_error",
                       "unserializable", "undecodable_response"):
            self._lane_fallback_total.labels(eng, reason)
        # -- device-resident serving (ISSUE 8): params placed on device
        # once per generation, donated per-bucket dispatch buffers, int8
        # query wire. Counters pre-created before any pool bind, same as
        # the bucket/lane families above.
        self._resident: List = []
        self._h2d_bytes_total = self.obs.counter(
            "pio_tpu_serving_h2d_bytes_total",
            "Host→device feature bytes shipped by resident-scorer "
            "dispatches (the int8 wire pays one byte per feature per "
            "query; float32 pays four)",
            ("engine_id",),
        )
        self._donation_total = self.obs.counter(
            "pio_tpu_donation_total",
            "Donated-buffer dispatch outcomes: hit = recycled the "
            "standing per-bucket device buffer, miss = cold shape had "
            "to allocate (once per bucket per generation)",
            ("engine_id", "outcome"),
        )
        self._resident_params_bytes = self.obs.gauge(
            "pio_tpu_resident_params_bytes",
            "Device-resident serving parameter bytes for the deployed "
            "generation (0 = host-mirror serving)",
            ("engine_id",),
        )
        self._resident_models = self.obs.gauge(
            "pio_tpu_resident_models",
            "Models in the deployed generation serving from "
            "device-resident params",
            ("engine_id",),
        )
        self._resident_fallback_total = self.obs.counter(
            "pio_tpu_resident_fallback_total",
            "Models whose device-resident scorer failed to build, so "
            "they serve from the host mirror instead",
            ("engine_id",),
        )
        self._h2d_bytes_total.labels(eng)
        self._resident_fallback_total.labels(eng)
        for outcome in ("hit", "miss"):
            self._donation_total.labels(eng, outcome)
        self._resident_params_bytes.labels(eng)
        self._resident_models.labels(eng)
        # -- mesh-sharded serving (ISSUE 10): factor tables partitioned
        # over the serving mesh via the partition-rule registry
        # (PIO_TPU_MESH_SERVE gate). Counters pre-created before any
        # pool bind, same as the families above.
        self._sharding_info: Optional[dict] = None
        self._shard_bytes_placed_total = self.obs.counter(
            "pio_tpu_shard_bytes_placed_total",
            "Model parameter bytes placed sharded across the serving "
            "mesh (summed over devices, once per deploy generation)",
            ("engine_id",),
        )
        self._shard_gather_fallback_total = self.obs.counter(
            "pio_tpu_shard_gather_fallback_total",
            "Mesh placements that fell back to single-device/replicated "
            "serving (budget exceeded, indivisible shapes, or placement "
            "error)",
            ("engine_id",),
        )
        self._shard_bytes_placed_total.labels(eng)
        self._shard_gather_fallback_total.labels(eng)
        # -- device telemetry plane (ISSUE 17): per-instance watch on
        # this registry (DeviceWatch pre-creates its compile site cells,
        # so the families exist before any pool bind like the counters
        # above). Module activation routes the residency/stream/shard
        # ledger hooks here; the sampler thread keeps memory_stats
        # reads OFF the dispatch path (PIO_TPU_DEVICEWATCH=0 keeps the
        # thread off — /device.json then samples on demand).
        self.devwatch = devicewatch.DeviceWatch(registry=self.obs)
        devicewatch.activate(self.devwatch)
        if knobs.knob_str(devicewatch.SAMPLER_ENV) != "0":
            self.devwatch.start()
        self.profile_hook = DeviceProfileHook.from_env()
        self._swap_lock = make_lock("query.model_swap")
        self._deployed = True
        #: pool mode (see server/worker_pool.py): shared reload generation
        #: + shutdown event wired in by enable_pool()
        self._pool_idx = None
        self._pool_size = None
        self._pool_gen = None
        self._pool_shutdown = None
        self._sidecar_ports = None
        self._seen_gen = 0
        #: monotone hot-swap counter, bumped on every successful _load
        #: (deploy/reload/undeploy-reload) — the rollout controller's
        #: GET /deploy.json witness that a generation actually flipped
        self._swap_generation = 0
        #: set via attach_server(); when present, /undeploy also stops the
        #: HTTP server shortly after responding (reference parity: `pio
        #: undeploy` terminates the server process, not just the flag)
        self._server = None
        self._load(instance_id)
        window_us = knobs.knob_float("PIO_TPU_SERVE_MICROBATCH_US")
        adaptive = knobs.knob_str(
            "PIO_TPU_SERVE_MICROBATCH_ADAPTIVE"
        ) != "0"
        self._batcher = (
            _MicroBatcher(self, window_us / 1e6, adaptive=adaptive)
            if window_us > 0 else None
        )

        self.router = Router()
        r = self.router
        r.add("GET", "/", self.status)
        r.add("POST", "/queries\\.json", self.query)
        r.add("GET", "/stats\\.json", self.get_stats)
        r.add("GET", "/device\\.json", self.get_device)
        r.add("GET", "/metrics", self.get_metrics)
        r.add("GET", "/traces\\.json", self.get_traces)
        r.add("GET", "/logs\\.json", self.get_logs)
        r.add("GET", "/slo\\.json", self.get_slo)
        r.add("GET", "/qos\\.json", self.get_qos)
        r.add("GET", "/faults\\.json", self.get_faults)
        r.add("GET", "/debug/hotpath\\.json", self.get_hotpath)
        r.add("GET", "/debug/profile\\.json", self.get_profile)
        r.add("POST", "/debug/profile\\.json", self.post_profile)
        r.add("GET", "/healthz", self.healthz)
        r.add("GET", "/readyz", self.readyz)
        r.add("POST", "/reload", self.reload)
        r.add("POST", "/deploy\\.json", self.deploy_verified)
        r.add("GET", "/deploy\\.json", self.deploy_report)
        r.add("POST", "/undeploy", self.undeploy)
        r.add("GET", "/plugins\\.json", self.list_plugins)

    # -- engine/model lifecycle --------------------------------------------
    def _load(self, instance_id: Optional[str]) -> None:
        engine, engine_params = build_engine(self.variant)
        instance_id = resolve_instance_id(self.variant, instance_id)
        models = load_models_for_instance(
            instance_id, engine, engine_params, self.ctx,
            variant=self.variant,
        )
        # mesh attach must precede prepare_for_serving (inside
        # algorithms_with_models): templates warm their device scorer
        # there, and a model that only fits sharded would fail the
        # per-device budget on the single-chip path
        serve_mesh = self._serving_mesh()
        if serve_mesh is not None:
            for m in models:
                try:
                    m.__dict__["_serve_mesh"] = serve_mesh
                except AttributeError:  # __slots__ model: no mesh channel
                    pass
        pairs = engine.algorithms_with_models(engine_params, models)
        serving = engine.make_serving(engine_params)
        # resolve once at load — a conflicting query-class config should fail
        # deploy/reload, not the first query
        query_class = resolve_query_class(pairs)
        # resident placement + bucket warmup run on the INCOMING pairs
        # before the swap is visible: on a /reload the old model keeps
        # serving while the new generation's params cross the link and
        # its shape buckets compile, then the swap installs model +
        # warmed set + resident scorers atomically (hot-swap = eviction
        # of the old generation's entries AND retirement of its device
        # params)
        sharding_info = self._place_mesh(pairs)
        incoming = self._place_resident(pairs)
        warmed = self._warm_buckets(pairs, serving)
        eng = self.variant.engine_id
        with self._swap_lock:
            self._sharding_info = sharding_info
            self.engine, self.engine_params = engine, engine_params
            self.instance_id = instance_id
            self._swap_generation += 1
            self.pairs, self.serving = pairs, serving
            self.query_class = query_class
            if self._buckets.warmed:
                self._bucket_evictions_total.inc(engine_id=eng)
            gen = self._buckets.install(warmed)
            self._bucket_entries.set(len(warmed), engine_id=eng)
            outgoing, self._resident = self._resident, incoming
        # retire OUTSIDE the lock: an in-flight dispatch that already
        # read the old scorer finishes against still-live params, then
        # every later read sees `retired` and falls back to the freshly
        # swapped host mirror — stale weights can never answer
        for sc in outgoing:
            sc.retire()
        self._resident_params_bytes.set(
            sum(sc.placed_bytes for sc in incoming), engine_id=eng
        )
        self._resident_models.set(len(incoming), engine_id=eng)
        # stamp the generation the new placements went live under — the
        # /device.json placement table keys eviction decisions by it
        self.devwatch.set_generation(gen)
        log.info(
            "serving engine instance %s (generation %d, %d resident)",
            instance_id, gen, len(incoming),
        )

    def _serving_mesh(self):
        """The mesh to shard serving params over, or None.

        Gate: ``PIO_TPU_MESH_SERVE=1`` enables sharded serving over the
        context mesh; ``0``/unset keeps the single-device placement every
        existing deploy runs (sharding changes device placement, so it is
        opt-in per server, not inferred from mesh presence)."""
        flag = knobs.knob_str("PIO_TPU_MESH_SERVE").strip().lower()
        if flag not in ("1", "on", "true"):
            return None
        mesh = self.ctx.mesh
        if mesh is None or self.ctx.num_devices <= 1:
            return None
        return mesh

    def _place_mesh(self, pairs) -> Optional[dict]:
        """Shard each model's serving factor tables over the serving mesh
        (partition-rule placement inside the scorer; see ops/topn.py).

        Runs on the INCOMING pairs before the swap, like residency: the
        scorers build eagerly here so placement cost and failures land at
        deploy, not inside the first live query. A model whose placement
        fails (budget, shapes) serves single-device instead — counted by
        ``pio_tpu_shard_gather_fallback_total``."""
        # the incoming generation's sharded footprint replaces the old
        # one wholesale (placements rebuild below)
        self.devwatch.ledger_clear("shard")
        mesh = self._serving_mesh()
        if mesh is None:
            return None
        eng = self.variant.engine_id
        placed = []
        for algo, m in pairs:
            # resident scorers read the same attribute at build time;
            # __dict__ write keeps frozen dataclass models settable
            try:
                m.__dict__["_serve_mesh"] = mesh
            except AttributeError:  # __slots__ model: no mesh channel
                continue
            if not hasattr(m, "scorer"):
                continue
            try:
                failpoint("shard.place")
                # prepare_for_serving usually built the sharded scorer
                # already (the mesh attaches before it in _load); rebuild
                # only when the cache predates the mesh or went host-mode
                sc = m.__dict__.get("_scorer")
                if sc is None or not getattr(sc, "mesh_sharded", False):
                    m.__dict__.pop("_scorer", None)
                    sc = m.scorer(warmup=True)
                info = sc.sharding_info() if sc is not None else None
            except Exception:
                log.exception(
                    "mesh placement failed for %s; serving single-device",
                    type(m).__name__,
                )
                m.__dict__.pop("_serve_mesh", None)
                m.__dict__.pop("_scorer", None)
                self._shard_gather_fallback_total.inc(engine_id=eng)
                continue
            if info is None:
                # scorer chose the host/replicated path (budget, 1-chip
                # mesh, host-forced mode): not a sharded placement
                self._shard_gather_fallback_total.inc(engine_id=eng)
                continue
            info = dict(info)
            info["model"] = type(m).__name__
            placed.append(info)
            # ledger: each chip holds bytesPerDevice of this model
            # (symmetric placement — device 0 stands for the set)
            self.devwatch.ledger_place(
                "shard", type(m).__name__,
                int(info["bytesPerDevice"]),
                name=f"sharded {type(m).__name__}",
            )
            self._shard_bytes_placed_total.inc(
                int(info["totalBytes"]), engine_id=eng
            )
            log.info(
                "sharded placement: %s over %d device(s), %d B/device",
                type(m).__name__, info["nDevices"], info["bytesPerDevice"],
            )
        return {
            "enabled": True,
            "meshDevices": self.ctx.num_devices,
            "models": placed,
        }

    def _place_resident(self, pairs) -> list:
        """Build + place device-resident scorers for the incoming pairs
        (``PIO_TPU_DEVICE_RESIDENT`` gate — see server/residency.py).
        Each scorer is attached to its model as ``_resident`` so the
        algorithm's predict/batch_predict dispatch through the device
        params; a template without a scorer (or a build failure) keeps
        its host-mirror path."""
        from pio_tpu.server import residency

        if not residency.enabled():
            return []
        eng = self.variant.engine_id

        # bound cells: these callbacks run inside every score_wire
        # dispatch — per-call labels() resolution is measurable there
        h2d_cell = self._h2d_bytes_total.labels(eng)
        donation_cells = {
            outcome: self._donation_total.labels(eng, outcome)
            for outcome in ("hit", "miss")
        }

        def on_h2d(nbytes: int) -> None:
            h2d_cell.inc(float(nbytes))

        def on_donation(outcome: str) -> None:
            donation_cells[outcome].inc()

        placed = []
        for algo, m in pairs:
            try:
                sc = algo.resident_scorer(m)
            except Exception:
                log.exception(
                    "resident_scorer failed for %s; model serves from "
                    "the host mirror", type(algo).__name__,
                )
                self._resident_fallback_total.inc(engine_id=eng)
                continue
            if sc is None:
                continue
            sc.bind(on_h2d=on_h2d, on_donation=on_donation)
            sc.prealloc(self._buckets.buckets)
            m._resident = sc
            placed.append(sc)
            log.info(
                "resident scorer %r placed: %d param bytes, wire=%s",
                sc.name, sc.placed_bytes, sc.wire,
            )
        return placed

    def _bucket_warm_enabled(self) -> bool:
        """Warm the bucket ladder only where batched dispatches can
        actually happen — a micro-batching server or a batch-lane device
        worker. A plain per-request deploy (most tests, `pio deploy`
        without the env) must not pay len(buckets) compiles at boot.
        ``PIO_TPU_BUCKET_WARMUP=0`` force-disables, ``=1``
        force-enables."""
        flag = knobs.knob_str("PIO_TPU_BUCKET_WARMUP")
        if flag == "0":
            return False
        if flag == "1":
            return True
        if knobs.knob_float("PIO_TPU_SERVE_MICROBATCH_US") > 0:
            return True
        return self._lane_drainer is not None

    def _warm_buckets(self, pairs, serving) -> list:
        """Compile the bucket ladder for ``pairs`` by dispatching each
        bucket once with a representative query (``algo.warmup_query``).
        Returns the warmed bucket list — empty when warmup is disabled
        or no algorithm can mint a warmup query (the ladder then warms
        lazily on first live dispatch, counted as retraces)."""
        if not self._bucket_warm_enabled() or not pairs:
            return []
        wq = None
        for algo, m in pairs:
            try:
                wq = algo.warmup_query(m)
            except Exception:
                log.exception(
                    "warmup_query failed for %s", type(algo).__name__
                )
            if wq is not None:
                break
        if wq is None:
            log.info(
                "no algorithm provided a warmup query; shape buckets "
                "warm lazily on first dispatch"
            )
            return []
        t0 = monotonic_s()
        warmed = []
        for b in self._buckets.buckets:
            try:
                # compile attribution: each bucket's first sweep is the
                # trace+compile; a hot-swap re-warm over an unchanged
                # ladder hits the jit cache and is NOT recounted
                with self.devwatch.span("bucket_warmup", key=("bucket", b)):
                    self._run_batch(pairs, serving, [wq] * b)
                warmed.append(b)
            except Exception:
                log.exception("bucket %d warmup dispatch failed", b)
                break
        log.info(
            "bucket warmup: compiled buckets %s in %.0f ms",
            warmed, (monotonic_s() - t0) * 1e3,
        )
        return warmed

    # -- handlers -----------------------------------------------------------
    def status(self, req: Request):
        self._pool_sync()
        return 200, {
            "status": "deployed" if self._deployed else "undeployed",
            "engineId": self.variant.engine_id,
            "engineFactory": self.variant.engine_factory,
            "engineInstanceId": self.instance_id,
            "startTime": self.start_time.isoformat(),
            "requestCount": self.stats.count,
        }

    # -- health/readiness (ISSUE 2) -----------------------------------------
    def _http_loop_alive(self):
        """Liveness: the attached server's accept-loop thread. When the
        server runs ``serve_forever`` in the main thread (or none is
        attached — embedded use), there is no thread to check: pass."""
        server = self._server
        t = getattr(server, "_thread", None) if server is not None else None
        if t is None:
            return True, "accept loop not thread-managed"
        return t.is_alive(), "accept loop thread " + (
            "alive" if t.is_alive() else "dead"
        )

    def _check_engine_ready(self):
        with self._swap_lock:
            ok = self._deployed and bool(self.pairs)
            iid = self.instance_id
        if not self._deployed:
            return False, "undeployed"
        return ok, f"instance {iid}" if ok else "no algorithms loaded"

    def _check_storage_ready(self):
        """Readiness: the metadata store must answer, and the deployed
        instance must still exist there (a vanished record means /reload
        can never succeed)."""
        rec = Storage.get_meta_data_engine_instances().get(self.instance_id)
        if rec is None:
            return False, f"instance {self.instance_id} not in metadata store"
        return True, "metadata store reachable"

    def _availability_good_total(self):
        eng = self.variant.engine_id
        total = self._queries_total.value(eng)
        errors = self._query_errors_total.value(eng)
        return total - errors, total

    def healthz(self, req: Request):
        ok, report = self.health.liveness()
        return (200 if ok else 503), report

    def readyz(self, req: Request):
        ok, report = self.health.readiness()
        return (200 if ok else 503), report

    def get_logs(self, req: Request):
        """Recent structured log entries from the in-process ring,
        filterable by minimum level and exact trace id."""
        n = int_param(req.params, "n", 100, lo=0, hi=slog.ring().cap)
        try:
            return 200, slog.logs_payload(
                n=n,
                level=req.params.get("level"),
                trace_id=req.params.get("trace_id"),
                logger=req.params.get("logger"),
            )
        except ValueError as e:
            raise HTTPError(400, str(e))

    def get_slo(self, req: Request):
        """Burn-rate evaluation of the configured SLOs against the live
        counters/histograms (empty when none were declared)."""
        if self.slo is None:
            return 200, {"slos": [], "configured": False}
        out = self.slo.evaluate()
        out["configured"] = True
        return 200, out

    def get_qos(self, req: Request):
        """Admission-control state: policy, bucket level, inflight/queue,
        shed counts by reason, breaker states, stale-cache stats."""
        if self.qos is None:
            return 200, {"enabled": False}
        return 200, self.qos.snapshot()

    def get_faults(self, req: Request):
        """Armed failpoints + trigger counts (pio_tpu.faults)."""
        from pio_tpu import faults

        return 200, faults.snapshot()

    def _shed(self, req: Request, reason: str, retry_after_s: float):
        """Turn a shed decision into a response: a stale-cache hit (when
        degradation is configured) answers 200 with ``X-Pio-Degraded``;
        otherwise 429 (rate limits) / 503 (everything else) with
        ``Retry-After``. ``pio_tpu_qos_shed_total`` counts only the
        actual rejections — degraded serves get their own counter."""
        if self.qos.stale is not None and req.body is not None:
            cached = self.qos.stale.get(cache_key(req.body))
            if cached is not None:
                self.qos.count_degraded()
                return 200, json_response(
                    cached, {DEGRADED_HEADER: DEGRADED_VALUE}
                )
        self.qos.count_shed(reason)
        status = 429 if reason in ("rate_limit", "key_rate_limit") else 503
        raise HTTPError(
            status, f"overloaded: {reason}",
            headers=retry_after_header(retry_after_s),
        )

    def _parse_query(self, body: Any, qc):
        if body is None:
            raise HTTPError(400, "query body required")
        if not isinstance(body, dict):
            raise HTTPError(400, "query body must be a JSON object")
        if qc is None:
            return body  # raw dict queries
        try:
            return params_from_dict(qc, body)
        except ParamsError as e:
            raise HTTPError(400, str(e))

    def list_plugins(self, req: Request):
        from pio_tpu.server.plugins import installed_plugins

        return 200, installed_plugins()

    def _slow_threshold_s(self) -> Optional[float]:
        """The slow-trace capture threshold in seconds, or None while
        there is no basis for one (fresh server, no SLO declared)."""
        ms = knobs.knob_float("PIO_TPU_SLOW_TRACE_MS")
        if ms > 0:
            return ms / 1e3
        slo = self.slo
        if slo is not None:
            thresholds = [
                o.threshold_s for o in slo.objectives
                if o.kind == "latency" and o.threshold_s
            ]
            if thresholds:
                return min(thresholds)
        # no declared objective: estimate p99 from the live distribution
        # once it has enough mass to mean something
        cell = self._e2e_cell
        if cell.count >= 64:
            return cell.quantile(0.99, pool=False)
        return None

    def get_hotpath(self, req: Request):
        """Per-stage latency budget (count/avg/p50/p95 + attributed
        fraction of the end-to-end average). ``?pool=0`` restricts a
        pool worker's answer to its own stripe."""
        pool = req.params.get("pool", "1") != "0"
        return 200, hotpath_payload(
            self.tracer, self._e2e_cell,
            stage_order=QUERY_STAGES + QUERY_SUBSTAGES, pool=pool,
            slow_threshold_s=self._slow_threshold_s(),
        )

    def get_profile(self, req: Request):
        """Device-profiler hook status (captures, armed, directory)."""
        return 200, self.profile_hook.to_dict()

    def post_profile(self, req: Request):
        """``?restart=1`` re-arms the first-N device-execution profiler
        for another capture window (admin-gated: profiling taxes the hot
        path and writes server-side files)."""
        self._check_admin(req)
        if req.params.get("restart") in ("1", "true"):
            n = int_param(req.params, "n", 0, lo=0)
            return 200, self.profile_hook.restart(n)
        return 200, self.profile_hook.to_dict()

    def enable_pool(self, idx: int, size: int, gen, shutdown_evt,
                    metrics_path: Optional[str] = None,
                    sidecar_ports=None) -> None:
        """Wire this worker into a serving pool: ``gen`` is a shared
        multiprocessing generation counter (a /reload on ANY worker bumps
        it; the others lazily reload before their next query), and
        ``shutdown_evt`` a shared event that /undeploy sets so the
        supervisor brings the whole pool down.

        ``metrics_path`` points at the supervisor-created shared-memory
        metrics segment; binding it makes ``GET /metrics`` on THIS worker
        report pool-wide sums (the kernel balances scrape connections
        across workers just like queries — without aggregation every
        scrape would see 1/size of the traffic)."""
        self._pool_idx = idx
        self._pool_size = size
        self._pool_gen = gen
        self._pool_shutdown = shutdown_evt
        self._seen_gen = gen.value
        #: loopback sidecar ports of EVERY pool worker (shared array,
        #: published as each worker's sidecar comes up) — the fan-out
        #: path that lets /traces.json merge all workers' private rings
        self._sidecar_ports = sidecar_ports
        # pool-mode probes: worker main loop beats the heartbeat; the
        # supervisor's /healthz poll catches a wedged loop. Readiness
        # additionally requires the shared metrics stripe (without it
        # this worker silently under-reports every pool-wide scrape).
        slog.set_worker(str(idx))
        # pool-unique trace ids (query-w2-17): SO_REUSEPORT workers would
        # otherwise mint colliding ids, making the merged view ambiguous
        self.tracer.set_worker(idx)
        self.health.add_liveness("event_loop", self.heartbeat.check)
        self.health.add_readiness("pool_stripe", self._check_pool_stripe)
        if metrics_path:
            from pio_tpu.obs.shm import PoolMetricsSegment

            try:
                seg = PoolMetricsSegment.open(metrics_path)
                self.obs.bind_pool_segment(seg, idx)
                # stripe generation export (ISSUE 11): the supervisor
                # bumps the segment word at every (re)spawn and negates
                # it at retirement; re-reading at scrape time lets
                # aggregators tell stripe adoption (counter
                # discontinuity) from traffic and spot retired stripes
                # whose retained totals will never move again
                self.obs.add_collector(
                    lambda: _stripe_generation_lines(seg)
                )
                if self.qos is not None:
                    # the admitted-counter stripes are live now; forget
                    # pre-bind totals so history doesn't drain the bucket
                    self.qos.on_pool_bound()
            except Exception:
                log.exception(
                    "pool metrics segment bind failed; this worker "
                    "exposes local-only metrics"
                )

    def _check_pool_stripe(self):
        if self.obs.pool_bound:
            return True, f"stripe {self._pool_idx} bound"
        return False, "shared metrics segment not bound"

    def enable_batch_lane(self, path: str, doorbell, resp_events,
                          device: bool) -> None:
        """Wire this pool worker into the cross-worker batch lane.

        The DEVICE worker opens the segment and runs the drainer thread
        (aggregating every stripe into one bucketed dispatch); every
        other worker gets a :class:`LaneClient` and ships its query
        bodies over instead of dispatching locally — batch occupancy
        scales with pool size instead of fragmenting per process."""
        eng = self.variant.engine_id
        try:
            seg = BatchLaneSegment.open(path)
        except Exception:
            log.exception(
                "batch lane segment open failed; worker %s serves "
                "locally", self._pool_idx,
            )
            return
        self._lane_seg = seg
        if device:
            def on_drain(n: int, batches: int) -> None:
                self._lane_drained_total.inc(n, engine_id=eng)
                self._lane_batches_total.inc(batches, engine_id=eng)
                self._lane_depth.set(seg.pending_depth(), engine_id=eng)

            self._lane_drainer = LaneDrainer(
                seg, self._lane_dispatch, doorbell, resp_events,
                on_drain=on_drain,
            ).start()
            self.health.add_liveness(
                "batch_lane", lambda: (
                    (True, "drainer alive")
                    if self._lane_drainer.thread is not None
                    and self._lane_drainer.thread.is_alive()
                    else (False, "drainer thread dead")
                ),
            )
            # the device worker now has batched dispatches to absorb:
            # warm the ladder if deploy happened before the lane came up
            if not self._buckets.warmed and self._deployed:
                with self._swap_lock:
                    pairs, serving = self.pairs, self.serving
                warmed = self._warm_buckets(pairs, serving)
                if warmed:
                    self._buckets.install(warmed)
                    self._bucket_entries.set(len(warmed), engine_id=eng)
            log.info("batch lane drainer up (device worker)")
        else:
            self._lane_client = LaneClient(
                seg, self._pool_idx, doorbell,
                resp_events[self._pool_idx],
            )
            log.info("batch lane client up (worker %s)", self._pool_idx)

    def _lane_dispatch(self, bodies: list) -> list:
        """Drainer-side service: parse each shipped body with THIS
        worker's snapshot and serve the whole cycle as one bucketed
        batch. Runs on the drainer thread — sync the pool generation
        first so a /reload elsewhere is honored here too.

        A body is either a JSON query body or a :class:`PackedQuery`
        (int8 lane wire): packed features dequantize with this worker's
        resident scales — identical to the submitter's, both came off
        the same trained model — so the rebuilt query re-quantizes to
        the exact codes that crossed the ring."""
        self._pool_sync()
        with self._swap_lock:
            qc = self.query_class
            serving = self.serving
            resident = list(self._resident)
        sc = resident[0] if len(resident) == 1 else None

        def to_query(b):
            if isinstance(b, PackedQuery):
                if sc is None or sc.scales is None \
                        or sc.query_factory is None:
                    raise ValueError(
                        "packed lane query but no resident int8 scorer "
                        "on the device worker"
                    )
                return sc.query_factory(sc.dequantize(b.codes))
            return self._parse_query(b, qc)

        queries = [serving.supplement(to_query(b)) for b in bodies]
        results, _fresh = self._predict_batch_bucketed(queries)
        return [_to_jsonable(r) for r in results]

    def _lane_pack(self, query) -> Optional[bytes]:
        """Wire-encode ``query`` as a packed int8 lane frame, or None to
        ship the JSON body. Packing is sound only when exactly ONE
        resident scorer serves on the int8 wire (the drainer dequantizes
        with the same training scales, making the round trip exact) and
        the query carries a dense feature vector."""
        resident = self._resident
        if len(resident) != 1:
            return None
        sc = resident[0]
        if sc.wire != "int8" or sc.retired or sc.query_factory is None:
            return None
        vec = getattr(query, "vector", None)
        if vec is None:
            return None
        try:
            return pack_query_i8(sc.quantize(vec(sc.in_dim))[0])
        except Exception:
            return None

    def _pool_sync(self) -> None:
        gen = self._pool_gen
        if gen is not None and gen.value != self._seen_gen:
            target = gen.value
            # mark the generation consumed only AFTER a successful load —
            # a transient reload failure must be retried on the next
            # query, not leave this worker on the stale model forever
            self._load(None)
            self._seen_gen = target

    def query(self, req: Request):  # pio: hotpath
        if not self._deployed:
            raise HTTPError(503, "undeployed")
        if req.packed is not None:
            # packed binary wire (PACKED_QUERY_CONTENT_TYPE): the body
            # never meets the JSON codec — hand the frame view to the
            # zero-copy path
            return self._query_packed(req)
        self._pool_sync()
        t0 = monotonic_s()
        error = True
        eng = self.variant.engine_id
        adm = None
        deadline = None
        bcall = None
        trace_id = None
        # cross-process propagation: adopt the caller's trace id (and the
        # span that issued the call) so one id names the whole waterfall
        in_tid, in_parent = parse_trace_header(req.header(TRACE_HEADER))
        try:
            if self.qos is not None:
                # deadline clock starts at receipt; a malformed header is
                # a client error, not silently "no deadline"
                try:
                    deadline = Deadline.from_header(
                        req.header(DEADLINE_HEADER),
                        default_ms=self.qos.policy.deadline_ms,
                    )
                except ValueError as e:
                    raise HTTPError(400, str(e))
                timeout_s = (
                    max(deadline.remaining_s(), 0.0)
                    if deadline is not None else None
                )
                adm = self.qos.admit(
                    priority=req.header(PRIORITY_HEADER),
                    timeout_s=timeout_s,
                )
                if not adm.ok:
                    out = self._shed(req, adm.reason, adm.retry_after_s)
                    error = False
                    return out
                if self._scorer_breaker is not None:
                    bcall = self._scorer_breaker.acquire()
                    if not bcall.allowed:
                        out = self._shed(req, "breaker", bcall.retry_after_s)
                        error = False
                        return out
            t_admitted = monotonic_s()
            with self.tracer.trace(
                "query", trace_id=in_tid, parent=in_parent
            ) as tr:
                trace_id = tr.trace_id
                # the trace opens only AFTER admission, but the request
                # began at socket read: rebase so the waterfall shows
                # accept at offset 0 instead of pretending the request
                # started at parse
                pre_s = req.read_s + (t_admitted - t0)
                tr.rebase(pre_s)
                tr.add_span("accept", req.read_s, rel_start_s=0.0)
                # admit runs from read-end to NOW (not to t_admitted):
                # the trace-open and rebase work just done is request
                # time, and end-aligning the span to the parse start
                # keeps the top-level stages tiling without overlap
                if adm is not None and adm.queue_wait_s > 0:
                    # time blocked in the concurrency limiter's queue —
                    # the tail end of the admit window
                    tr.add_span(
                        "admit.queue", adm.queue_wait_s,
                        rel_start_s=max(pre_s - adm.queue_wait_s, 0.0),
                    )
                rel_admit_end = tr.elapsed_s
                tr.add_span(
                    "admit", rel_admit_end - req.read_s,
                    rel_start_s=req.read_s,
                )
                # one consistent snapshot — a concurrent /reload must
                # not mix the old engine's query class with the new
                # engine's models. (The micro-batch path re-snapshots
                # in the worker; the batch is served from that
                # snapshot.) Inside the parse span: swap-lock wait is
                # request preparation time, and leaving it between
                # spans would leak it from the budget.
                with self._swap_lock:
                    pairs, serving, qc = (
                        self.pairs, self.serving, self.query_class
                    )
                query = self._parse_query(req.body, qc)
                query = serving.supplement(query)
                rel_parse_end = tr.elapsed_s
                tr.add_span(
                    "parse", rel_parse_end - rel_admit_end,
                    rel_start_s=rel_admit_end,
                )
                try:
                    if deadline is not None and deadline.expired():
                        # budget burned before execution (queue wait /
                        # parse) — shed before the model runs
                        raise DeadlineExceeded("deadline elapsed")
                    if self._lane_client is not None:
                        # cross-worker batch lane: ship the raw query
                        # body to the device worker (it re-parses with
                        # its own snapshot), block on the response cell.
                        # Any lane trouble falls back to local solo
                        # dispatch — the lane is an optimization, never
                        # a correctness dependency.
                        rel_exec = tr.elapsed_s
                        tr.add_span(
                            "queue", rel_exec - rel_parse_end,
                            rel_start_s=rel_parse_end,
                        )
                        timeout_s = None
                        if deadline is not None:
                            timeout_s = max(
                                0.005,
                                min(self._lane_client.timeout_s,
                                    deadline.remaining_s() - 0.01),
                            )
                        try:
                            result = self._lane_client.submit(
                                req.body, timeout_s=timeout_s,
                                packed=self._lane_pack(query),
                            )
                            self._lane_enqueued_total.inc(engine_id=eng)
                        except LaneFallback as lf:
                            self._lane_fallback_total.inc(
                                engine_id=eng, reason=lf.reason
                            )
                            if lf.reason == "full":
                                self._lane_full_total.inc(engine_id=eng)
                            result = self._predict_one(query)
                        tr.add_span(
                            "execute", tr.elapsed_s - rel_exec,
                            rel_start_s=rel_exec,
                        )
                    elif self._batcher is not None \
                            and self._batcher.active() \
                            and not self._evloop_front:
                        # (bypassed on the evloop front: submit parks
                        # the calling thread for the batch window, and
                        # that thread IS the event loop)
                        result = self._batcher.submit(
                            query, span_sink=tr, deadline=deadline
                        )
                    else:
                        # no batcher: "queue" is just the pre-dispatch
                        # bookkeeping (deadline check) between parse end
                        # and execute start — end-aligned so the stages
                        # tile with no gap in the hotpath budget
                        rel_exec = tr.elapsed_s
                        tr.add_span(
                            "queue", rel_exec - rel_parse_end,
                            rel_start_s=rel_parse_end,
                        )
                        t_dev = monotonic_s()
                        with self.profile_hook.capture():
                            predictions = [
                                algo.predict(m, query)
                                for algo, m in pairs
                            ]
                        tr.add_span(
                            "execute.device", monotonic_s() - t_dev
                        )
                        result = serving.serve(query, predictions)
                        tr.add_span(
                            "execute", tr.elapsed_s - rel_exec,
                            rel_start_s=rel_exec,
                        )
                except DeadlineExceeded:
                    out = self._shed(req, "deadline", 0.0)
                    error = False
                    return out
                except HTTPError:
                    raise
                except Exception:
                    if bcall is not None:
                        bcall.failure()
                    raise
                rel_ser = tr.elapsed_s
                if bcall is not None:
                    bcall.success()
                out = _to_jsonable(result)
                for blocker in QUERY_BLOCKERS:
                    try:
                        # output blockers see (query, prediction) and
                        # veto the response with ValueError → client 400
                        blocker(req.body, out)
                    except ValueError as e:
                        raise HTTPError(400, str(e))
                pr_id = None
                if self.feedback:
                    pr_id = uuid.uuid4().hex
                    if isinstance(out, dict):
                        out = {**out, "prId": pr_id}
                    self._log_feedback(req.body, out, pr_id)
                for sniffer in QUERY_SNIFFERS:
                    try:
                        sniffer(req.body, out)
                    except Exception:
                        log.exception("query sniffer failed")
                if self.qos is not None and self.qos.stale is not None \
                        and req.body is not None:
                    # feed the degradation cache with the fresh answer
                    self.qos.stale.put(cache_key(req.body), out)
                error = False
                # inside the trace → this record carries the trace id,
                # joining /logs.json?trace_id=... to /traces.json
                log.info(
                    "served query engine=%s ms=%.3f", eng,
                    (monotonic_s() - t0) * 1e3,
                )
                # serialize covers everything between the model result
                # and handing the response to the writer — JSON
                # conversion, blockers/sniffers, the stale-cache feed
                # and the served-query log line — end-aligned so it
                # tiles flush against both execute and write. The same
                # mark anchors the write span at HANDLER completion,
                # not at the socket write: the return path between them
                # (router unwind, the finally block's accounting) is
                # real request time, and leaving it between spans would
                # break the tiling the hotpath budget sums over
                rel_done_s = tr.elapsed_s
                tr.add_span(
                    "serialize", rel_done_s - rel_ser,
                    rel_start_s=rel_ser,
                )

                def _written(write_s: float, _tr=tr, _rel=rel_done_s):
                    # fires after the response bytes hit the socket: the
                    # last stage of the waterfall, and the only moment
                    # the TRUE end-to-end latency (accept→write) exists.
                    # ONE clock read for both: a second elapsed_s after
                    # the span observe would put the observe's own cost
                    # into e2e but no stage, eroding attribution
                    done_s = _tr.elapsed_s
                    _tr.add_span(
                        "write", done_s - _rel, rel_start_s=_rel
                    )
                    _tr.extend_total()
                    self._e2e_cell.observe(
                        done_s, exemplar=_tr.trace_id
                    )

                req.on_written = _written
                # echo the id so an untraced caller learns which trace
                # its request minted (and a traced one confirms adoption)
                return 200, json_response(
                    out, {TRACE_HEADER: tr.trace_id}
                )
        finally:
            if bcall is not None:
                # exits that never reached the scorer (parse 400,
                # deadline shed, undeployed 503) must still release a
                # half-open probe grant or the breaker wedges in
                # HALF_OPEN with all grants leaked; no-op after
                # success()/failure()
                bcall.cancel()
            if adm is not None:
                adm.release()
            dur_s = monotonic_s() - t0
            self.stats.record(dur_s * 1e3, error)
            self._request_cell.observe(dur_s, exemplar=trace_id)
            self._queries_total.inc(engine_id=eng)
            if error:
                self._query_errors_total.inc(engine_id=eng)

    def _query_packed(self, req: Request):  # pio: hotpath=zerocopy
        """Packed int8 query path: the body bytes the HTTP front read
        off the socket ARE the lane frame — validated structurally,
        admitted through the same QoS gate as JSON queries, and written
        straight into the shm ring slot by ``LaneClient.submit_packed``.
        The device worker's response comes back as ready JSON bytes and
        is returned without re-decoding. No JSON codec, no intermediate
        dict, no ``bytes()`` copies anywhere on this path — the
        ``hotpath-zero-copy`` rule proves it from this root.

        Span accounting mirrors :meth:`query` (same end-aligned tiling
        over QUERY_STAGES), with "parse" covering only the frame
        validation — which is the point of the fast path."""
        self._pool_sync()  # pio: disable=hotpath-zero-copy
        t0 = monotonic_s()
        error = True
        eng = self.variant.engine_id
        adm = None
        deadline = None
        bcall = None
        trace_id = None
        in_tid, in_parent = parse_trace_header(req.header(TRACE_HEADER))
        try:
            frame = req.packed
            if not packed_frame_ok(frame):
                self._fastpath_cells["invalid"].inc()
                raise HTTPError(400, "malformed packed query frame")
            if self.qos is not None:
                try:
                    deadline = Deadline.from_header(
                        req.header(DEADLINE_HEADER),
                        default_ms=self.qos.policy.deadline_ms,
                    )
                except ValueError as e:
                    raise HTTPError(400, str(e))
                timeout_s = (
                    max(deadline.remaining_s(), 0.0)
                    if deadline is not None else None
                )
                adm = self.qos.admit(
                    priority=req.header(PRIORITY_HEADER),
                    timeout_s=timeout_s,
                )
                if not adm.ok:
                    # no stale-cache key for a binary body: shed is a
                    # plain 429/503 (raised inside _shed)
                    # pio: disable=hotpath-zero-copy
                    out = self._shed(req, adm.reason, adm.retry_after_s)
                    error = False
                    return out
                if self._scorer_breaker is not None:
                    bcall = self._scorer_breaker.acquire()
                    if not bcall.allowed:
                        # pio: disable=hotpath-zero-copy
                        out = self._shed(
                            req, "breaker", bcall.retry_after_s
                        )
                        error = False
                        return out
            t_admitted = monotonic_s()
            with self.tracer.trace(
                "query", trace_id=in_tid, parent=in_parent
            ) as tr:
                trace_id = tr.trace_id
                pre_s = req.read_s + (t_admitted - t0)
                tr.rebase(pre_s)
                tr.add_span("accept", req.read_s, rel_start_s=0.0)
                if adm is not None and adm.queue_wait_s > 0:
                    tr.add_span(
                        "admit.queue", adm.queue_wait_s,
                        rel_start_s=max(pre_s - adm.queue_wait_s, 0.0),
                    )
                rel_admit_end = tr.elapsed_s
                tr.add_span(
                    "admit", rel_admit_end - req.read_s,
                    rel_start_s=req.read_s,
                )
                # "parse" here is only the frame check already done —
                # end-aligned so the stage tiling matches the JSON path
                rel_parse_end = tr.elapsed_s
                tr.add_span(
                    "parse", rel_parse_end - rel_admit_end,
                    rel_start_s=rel_admit_end,
                )
                try:
                    if deadline is not None and deadline.expired():
                        raise DeadlineExceeded("deadline elapsed")
                    rel_exec = tr.elapsed_s
                    tr.add_span(
                        "queue", rel_exec - rel_parse_end,
                        rel_start_s=rel_parse_end,
                    )
                    if self._lane_client is not None:
                        timeout_s = None
                        if deadline is not None:
                            timeout_s = max(
                                0.005,
                                min(self._lane_client.timeout_s,
                                    deadline.remaining_s() - 0.01),
                            )
                        try:
                            resp = self._lane_client.submit_packed(
                                frame, timeout_s=timeout_s
                            )
                            self._lane_enqueued_total.inc(engine_id=eng)
                            self._fastpath_cells["hit"].inc()
                        except LaneFallback as lf:
                            self._lane_fallback_total.inc(
                                engine_id=eng, reason=lf.reason
                            )
                            if lf.reason == "full":
                                self._lane_full_total.inc(engine_id=eng)
                            # pio: disable=hotpath-zero-copy
                            resp = self._query_packed_local(frame)
                    else:
                        # no lane (solo worker): the local fallback
                        # decodes the frame once — off the proven path
                        # pio: disable=hotpath-zero-copy
                        resp = self._query_packed_local(frame)
                    tr.add_span(
                        "execute", tr.elapsed_s - rel_exec,
                        rel_start_s=rel_exec,
                    )
                except DeadlineExceeded:
                    # pio: disable=hotpath-zero-copy
                    out = self._shed(req, "deadline", 0.0)
                    error = False
                    return out
                except HTTPError:
                    raise
                except Exception:
                    if bcall is not None:
                        bcall.failure()
                    raise
                rel_ser = tr.elapsed_s
                if bcall is not None:
                    bcall.success()
                error = False
                log.info(
                    "served packed query engine=%s ms=%.3f", eng,
                    (monotonic_s() - t0) * 1e3,
                )
                rel_done_s = tr.elapsed_s
                tr.add_span(
                    "serialize", rel_done_s - rel_ser,
                    rel_start_s=rel_ser,
                )

                def _written(write_s: float, _tr=tr, _rel=rel_done_s):
                    # one clock read for the span AND e2e (see query())
                    done_s = _tr.elapsed_s
                    _tr.add_span(
                        "write", done_s - _rel, rel_start_s=_rel
                    )
                    _tr.extend_total()
                    self._e2e_cell.observe(
                        done_s, exemplar=_tr.trace_id
                    )

                req.on_written = _written
                return 200, RawResponse(
                    resp,
                    content_type="application/json; charset=UTF-8",
                    headers={TRACE_HEADER: tr.trace_id},
                )
        finally:
            if bcall is not None:
                bcall.cancel()
            if adm is not None:
                adm.release()
            dur_s = monotonic_s() - t0
            self.stats.record(dur_s * 1e3, error)
            self._request_cell.observe(dur_s, exemplar=trace_id)
            self._queries_total.inc(engine_id=eng)
            if error:
                self._query_errors_total.inc(engine_id=eng)

    def _query_packed_local(self, frame) -> bytes:
        """Local fallback for the packed wire (solo worker, or the lane
        shed this request): decode the frame with this worker's resident
        scales and predict solo. The unpack copies the codes once — this
        is the non-zero-copy fallback, deliberately OFF the
        zerocopy-marked path (its call sites are suppressed)."""
        pq = unpack_query_i8(frame)
        with self._swap_lock:
            serving = self.serving
            resident = list(self._resident)
        sc = resident[0] if len(resident) == 1 else None
        if sc is None or sc.scales is None or sc.query_factory is None:
            self._fastpath_cells["unavailable"].inc()
            raise HTTPError(
                400,
                "packed queries need exactly one int8 resident scorer",
            )
        result = None
        if sc.result_factory is not None and not sc.retired:
            # direct wire dispatch: the frame's codes ARE this scorer's
            # wire encoding, so skip dequantize → Query → re-quantize
            # and map the argmax code straight to the template's result
            failpoint("scorer.dispatch.packed")
            try:
                out = sc.score_wire(pq.codes.reshape(1, -1))
                result = sc.result_factory(int(out[0]))
            except RuntimeError:
                # a hot swap retired the scorer mid-dispatch: fall back
                # to the generic path, whose predict re-resolves the
                # resident (or the host mirror the swap installed)
                result = None
        if result is None:
            query = serving.supplement(
                sc.query_factory(sc.dequantize(pq.codes))
            )
            result = self._predict_one(query)
        self._fastpath_cells["local"].inc()
        return json.dumps(_to_jsonable(result)).encode("utf-8")

    def pack_query_body(self, body) -> Optional[bytes]:
        """Encode a JSON-style query body as the packed int8 wire frame
        (``PACKED_QUERY_CONTENT_TYPE``), or None when the deployment
        can't serve packed queries (no single int8 resident scorer).
        Test/bench helper — a real producer packs features client-side
        with the published scales."""
        with self._swap_lock:
            qc = self.query_class
            serving = self.serving
        query = serving.supplement(self._parse_query(body, qc))
        return self._lane_pack(query)

    def _log_feedback(self, query_body, result, pr_id: str):
        """Reference: query server POSTs back to the Event Server with prId;
        in-process we write straight to the event store."""
        if self.feedback_app_id is None:
            return
        try:
            Storage.get_levents().insert(
                Event(
                    event="predict",
                    entity_type="pio_pr",
                    entity_id=pr_id,
                    properties={"query": query_body, "prediction": result},
                    pr_id=pr_id,
                ),
                self.feedback_app_id,
            )
        except Exception:
            log.exception("feedback logging failed")

    def _predict_one(self, query):
        """Per-query predict + serve from one consistent snapshot."""
        failpoint("scorer.dispatch.solo")
        with self._swap_lock:
            pairs, serving = self.pairs, self.serving
        t_dev = monotonic_s()
        with self.profile_hook.capture():
            predictions = [algo.predict(m, query) for algo, m in pairs]
        # lands on whatever trace is active here: the request trace
        # (solo/fallback path) — no-op when called untraced
        add_active_span("execute.device", monotonic_s() - t_dev)
        return serving.serve(query, predictions)

    def _run_batch(self, pairs, serving, queries: list):
        """One ``batch_predict`` dispatch per algorithm over the whole
        (already bucket-shaped) batch, then per-query serving combine."""
        per_algo = []
        t_dev = monotonic_s()
        with self.profile_hook.capture():
            for algo, m in pairs:
                got = dict(algo.batch_predict(m, list(enumerate(queries))))
                per_algo.append([got[i] for i in range(len(queries))])
        # one device observation per BATCH (on the microbatch trace via
        # the active-trace contextvar) — per-member device cost is the
        # amortization the batcher exists to buy, so attributing it once
        # is the honest accounting
        add_active_span("execute.device", monotonic_s() - t_dev)
        return [
            serving.serve(q, [pa[i] for pa in per_algo])
            for i, q in enumerate(queries)
        ]

    def _predict_batch(self, queries: list):
        """Micro-batch dispatch (bucketed); results only."""
        return self._predict_batch_bucketed(queries)[0]

    def _predict_batch_bucketed(self, queries: list):
        """Serve a micro-batch through the shape-bucket cache: chunk to
        the max bucket, pad each chunk up to its bucket (replicating the
        last query — padding rows ride the same compiled program and are
        sliced off), dispatch. Returns ``(results, fresh)`` where
        ``fresh`` is True when any chunk hit a cold bucket — a retrace
        the warmup sweep should have absorbed; the micro-batcher's probe
        discards such samples as compile transients."""
        failpoint("scorer.dispatch.batch")
        eng = self.variant.engine_id
        with self._swap_lock:
            pairs, serving = self.pairs, self.serving

        def on_dispatch(n: int, bucket: int, fresh: bool) -> None:
            self._bucket_dispatch_total.inc(engine_id=eng, bucket=str(bucket))
            self._bucket_occ_cell.observe(n / bucket)
            if fresh:
                self._bucket_retrace_total.inc(engine_id=eng)
                # a live retrace IS a compile the warmup should have
                # absorbed — attribute it (count only; the dispatch
                # isn't individually timed here)
                self.devwatch.record_compile("bucket_dispatch")

        return dispatch_bucketed(
            self._buckets, queries,
            lambda qs: self._run_batch(pairs, serving, qs),
            on_dispatch=on_dispatch,
        )

    def get_stats(self, req: Request):
        window_s = float_param(req.params, "window", 0.0, lo=0.0)
        if window_s > 0:
            out = self.stats.window(window_s)
        else:
            out = self.stats.to_dict()
            stages = self.stage_summary()
            if stages:
                out["stages"] = stages
        if self._batcher is not None:
            out["microbatch"] = self._batcher.to_dict()
        out["buckets"] = self._buckets.to_dict()
        resident = self._resident
        # measuredBytes: backend memory_stats total beside the estimated
        # paramBytes (None on ledger-only backends — the drift gauge
        # covers the live case); device memory can't be split between
        # the residency and sharding placements, so both blocks carry
        # the same device-level measurement
        measured = self.devwatch.measured_bytes()
        out["residency"] = {
            "enabled": bool(resident),
            "paramBytes": sum(sc.placed_bytes for sc in resident),
            "measuredBytes": measured,
            "scorers": [sc.to_dict() for sc in resident],
        }
        # which route answered: per-model top-N scorer dispatch counts
        # (device vs host mirror) with the routing probe's measurements
        out["topnScorers"] = []
        for _algo, m in self.pairs:
            sc = getattr(m, "__dict__", {}).get("_scorer")
            if sc is not None:
                out["topnScorers"].append(
                    {"model": type(m).__name__, **sc.route_info()}
                )
        with self._swap_lock:
            sharding = self._sharding_info
        out["sharding"] = (
            dict(sharding) if sharding else {"enabled": False}
        )
        if sharding:
            out["sharding"]["measuredBytes"] = measured
        if self._lane_drainer is not None:
            out["batchLane"] = {
                "role": "drainer",
                "cycles": self._lane_drainer.cycles,
                "drained": self._lane_drainer.drained,
                "pendingDepth": self._lane_seg.pending_depth(),
            }
        elif self._lane_client is not None:
            out["batchLane"] = {
                "role": "client",
                "worker": self._pool_idx,
                "timeoutS": self._lane_client.timeout_s,
            }
        if self._pool_idx is not None:
            # pool mode: these are ONE worker's numbers (the kernel
            # balanced this connection here); pool-wide totals live on
            # /metrics (shared-memory aggregation)
            out["worker"] = self._pool_idx
            out["poolSize"] = self._pool_size
            if self.obs.pool_bound:
                out["pool"] = {
                    "requestCount": int(
                        self._queries_total.value(self.variant.engine_id)
                    ),
                    "errorCount": int(
                        self._query_errors_total.value(self.variant.engine_id)
                    ),
                }
        return 200, out

    def get_device(self, req: Request):
        """Device telemetry snapshot (ISSUE 17): per-device bytes
        (measured or ledger-kept), budget headroom, the compile
        attribution table, and placements by serving generation —
        schema in docs/observability.md."""
        return 200, self.devwatch.payload()

    def stage_summary(self) -> dict:
        """Per-stage latency summary from the stage histograms: count,
        mean and interpolated p50/p95/p99 in milliseconds."""
        hist = self.tracer.stage_histogram
        out = {}
        if hist is None:
            return out
        for stage in QUERY_STAGES:
            cell = hist.labels(self.variant.engine_id, stage)
            n = cell.count
            if n <= 0:
                continue
            out[stage] = {
                "count": int(n),
                "avgMs": round(cell.sum / n * 1e3, 3),
                "p50Ms": _q_ms(cell, 0.5),
                "p95Ms": _q_ms(cell, 0.95),
                "p99Ms": _q_ms(cell, 0.99),
            }
        return out

    def _compat_metric_lines(self) -> list:
        """Extra exposition lines kept from the pre-obs server: the
        latency summary (quantile convention) and micro-batch counters —
        existing scrapes and the bench parse these."""
        from pio_tpu.obs import escape_label_value

        s = self.stats.to_dict()
        eng = escape_label_value(self.variant.engine_id)
        lab = f'engine_id="{eng}"'
        lines = []
        if s["avgMs"] is not None:
            lines += [
                "# TYPE pio_tpu_query_latency_ms summary",
                f'pio_tpu_query_latency_ms{{{lab},quantile="0.5"}} '
                f"{s['p50Ms']}",
                f'pio_tpu_query_latency_ms{{{lab},quantile="0.95"}} '
                f"{s['p95Ms']}",
                f'pio_tpu_query_latency_ms{{{lab},quantile="0.99"}} '
                f"{s['p99Ms']}",
                # _sum/_count complete the summary convention so
                # rate(_sum)/rate(_count) windowed averages work
                f"pio_tpu_query_latency_ms_sum{{{lab}}} "
                f"{s['avgMs'] * s['requestCount']}",
                f"pio_tpu_query_latency_ms_count{{{lab}}} "
                f"{s['requestCount']}",
            ]
        if self._batcher is not None:
            mb = self._batcher.to_dict()
            lines += [
                "# TYPE pio_tpu_microbatch_batches_total counter",
                f"pio_tpu_microbatch_batches_total{{{lab}}} {mb['batches']}",
                "# TYPE pio_tpu_microbatch_queries_total counter",
                f"pio_tpu_microbatch_queries_total{{{lab}}} "
                f"{mb['batchedQueries']}",
            ]
        return lines

    def get_metrics(self, req: Request):
        """Prometheus text exposition from the obs registry: request and
        error counters, per-stage latency histograms, plus the legacy
        summary + micro-batch lines via the compat collector. In pool
        mode counters/histograms are POOL-WIDE (shared-memory sums)."""
        return 200, metrics_response(self.obs.render())

    def get_traces(self, req: Request):
        """Recent request traces (ring buffer), slowest first. ``n`` is
        clamped to the ring capacity; negatives/non-ints are a 400.

        ``?slow=1`` serves the tail-capture ring (threshold breaches
        only); ``?id=<trace_id>`` looks up ONE trace across both rings.
        In pool mode every worker holds a private ring, so the answer is
        merged across the pool via each sibling's loopback sidecar;
        ``?local=1`` restricts to this worker (and is what the fan-out
        itself sends, so forwarding cannot recurse)."""
        n = int_param(req.params, "n", 20, lo=0, hi=self.tracer._ring_cap)
        local_only = req.params.get("local") == "1"
        tid = req.params.get("id")
        if tid:
            found = self.tracer.find(tid)
            if found is None and not local_only:
                for t in self._pool_traces(req.params):
                    if t.get("id") == tid:
                        found = t
                        break
            if found is None:
                raise HTTPError(404, f"trace {tid} not in any ring")
            return 200, {"traces": [found]}
        slow = req.params.get("slow") in ("1", "true")
        if slow:
            traces = self.tracer.slow(n)
        else:
            order = req.params.get("order", "slowest")
            traces = self.tracer.recent(n, slowest=(order != "recent"))
        if not local_only:
            siblings = self._pool_traces(req.params)
            if siblings:
                merged = {t["id"]: t for t in traces}
                for t in siblings:
                    merged.setdefault(t.get("id"), t)
                key = (
                    (lambda t: t.get("wallTime") or 0.0)
                    if (not slow and req.params.get("order") == "recent")
                    else (lambda t: t.get("totalMs") or 0.0)
                )
                traces = sorted(
                    merged.values(), key=key, reverse=True
                )[:n]
        return 200, {"traces": traces}

    def _pool_traces(self, params) -> list:
        """Fan ``/traces.json`` out to every SIBLING pool worker's
        loopback sidecar and return their traces (empty outside pool
        mode). The forwarded query carries ``local=1`` so a sibling
        answers from its own ring instead of fanning out again. A worker
        whose sidecar is still coming up (port 0) or mid-restart is
        skipped — a partial merged view beats a 500."""
        ports = self._sidecar_ports
        if ports is None:
            return []
        import json as _json
        from urllib.parse import urlencode
        from urllib.request import urlopen

        fwd = {k: v for k, v in dict(params).items() if k != "local"}
        fwd["local"] = "1"
        qs = urlencode(fwd)
        out = []
        for i in range(len(ports)):
            port = ports[i]
            if i == self._pool_idx or port <= 0:
                continue
            try:
                with urlopen(
                    f"http://127.0.0.1:{port}/traces.json?{qs}",
                    timeout=0.5,
                ) as resp:
                    payload = _json.loads(resp.read().decode("utf-8"))
                out.extend(payload.get("traces", []))
            except Exception:
                continue
        return out

    def _check_admin(self, req: Request):
        if self.admin_key is not None:
            if not keys_equal(req.bearer_key(), self.admin_key):
                raise HTTPError(401, "invalid admin accessKey")
        elif req.client_addr not in ("127.0.0.1", "::1"):
            raise HTTPError(
                403, "admin routes are loopback-only without an admin key"
            )

    def reload(self, req: Request):
        """Hot-swap to the newest COMPLETED instance (reference /reload).

        In pool mode the shared generation counter is bumped, so every
        sibling worker reloads before serving its next query — one admin
        POST rolls the whole pool."""
        self._check_admin(req)
        self._load(None)
        if self._pool_gen is not None:
            with self._pool_gen.get_lock():
                self._pool_gen.value += 1
                self._seen_gen = self._pool_gen.value
        return 200, {"engineInstanceId": self.instance_id}

    def deploy_verified(self, req: Request):
        """Manifest-verified generation swap (the router deploy path).

        The router pushes ``{engineInstanceId, manifest}``; every shard
        record named by the manifest is re-hashed from THIS member's
        store (sha256 + size) before the swap — a mismatch answers 409
        and the current generation keeps serving. Only after
        verification does the instance hot-swap in, exactly like
        /reload (pool siblings follow via the shared generation
        counter, which re-resolves to the latest COMPLETED instance —
        the rollout target in the fabric flow)."""
        from pio_tpu.router.deploy import DeployVerifyError, verify_instance

        self._check_admin(req)
        body = req.body if isinstance(req.body, dict) else {}
        instance_id = body.get("engineInstanceId")
        if not instance_id:
            raise HTTPError(400, "engineInstanceId is required")
        try:
            report = verify_instance(
                Storage.get_model_data_models(),
                instance_id,
                expected=body.get("manifest"),
            )
        except DeployVerifyError as e:
            raise HTTPError(409, f"deploy verification failed: {e}") from e
        self._load(instance_id)
        if self._pool_gen is not None:
            with self._pool_gen.get_lock():
                self._pool_gen.value += 1
                self._seen_gen = self._pool_gen.value
        report["engineInstanceId"] = self.instance_id
        report["verified"] = True
        return 200, report

    def deploy_report(self, req: Request):
        """Generation report (GET /deploy.json): the instance this
        member currently serves, its manifest sha256 set, and the
        monotone swap generation — the rollout controller's incumbent
        discovery and byte-identity witness (a rollback must leave the
        sha set exactly where a rollout found it)."""
        from pio_tpu.router.deploy import load_manifest, manifest_digests

        shas = []
        try:
            manifest = load_manifest(
                Storage.get_model_data_models(), self.instance_id
            )
            if manifest is not None:
                shas = sorted(
                    sha for sha, _size
                    in manifest_digests(manifest).values()
                )
        except Exception:
            pass  # unsharded blob / store hiccup: report without shas
        return 200, {
            "engineInstanceId": self.instance_id,
            "engineId": self.variant.engine_id,
            "manifestSha256": shas,
            "generation": self._swap_generation,
        }

    def undeploy(self, req: Request):
        self._check_admin(req)
        self._deployed = False
        self.devwatch.stop()
        devicewatch.deactivate(self.devwatch)
        if self._batcher is not None:
            self._batcher.stop()
        if self._lane_drainer is not None:
            # answer in-flight lane slots before the workers die so no
            # sibling blocks out its full timeout during teardown
            self._lane_drainer.stop()
            self._lane_drainer = None
        server, shutdown_evt = self._server, self._pool_shutdown

        def _after():
            # fires once the reply is flushed to the socket, so shutdown
            # can never race the client's read (a fixed timer would);
            # stop() runs in its own thread because it blocks until the
            # accept loop exits. In pool mode the shared event tells the
            # supervisor to bring down every sibling worker too.
            if shutdown_evt is not None:
                shutdown_evt.set()
            if server is not None:
                threading.Thread(target=server.stop, daemon=True).start()

        if server is not None or shutdown_evt is not None:
            req.after_response = _after
        return 200, {"message": "undeployed"}

    def attach_server(self, server) -> None:
        """Let /undeploy stop ``server`` (the CLI deploy path attaches;
        embedded servers keep the flag-only behavior unless they opt in)."""
        self._server = server

    def mark_evloop_front(self) -> None:
        """The evloop HTTP front runs handlers inline in its event loop:
        disable the in-process micro-batcher hand-off (its submit parks
        the calling thread for the batch window, and that thread IS the
        loop). Cross-worker batching via the shm lane still applies —
        its submit-side wait is bounded by the lane timeout."""
        self._evloop_front = True


def create_query_server(
    variant: EngineVariant,
    host: str = "0.0.0.0",
    port: int = 8000,
    instance_id: Optional[str] = None,
    ctx: Optional[ComputeContext] = None,
    feedback: bool = False,
    feedback_app_id: Optional[int] = None,
    admin_key: Optional[str] = None,
    reuse_port: bool = False,
    slos: Optional[List[str]] = None,
    qos: Optional[Any] = None,
) -> Tuple[Any, QueryServerService]:
    from pio_tpu.server.plugins import load_plugins_from_env

    load_plugins_from_env()
    service = QueryServerService(
        variant, instance_id, ctx, feedback, feedback_app_id, admin_key,
        slos=slos, qos=qos,
    )
    front = knobs.knob_str(
        "PIO_TPU_HTTP_FRONT"
    ).strip().lower() or "threaded"
    if front not in ("threaded", "evloop"):
        log.warning(
            "PIO_TPU_HTTP_FRONT=%r is not threaded|evloop; using "
            "threaded", front,
        )
        front = "threaded"
    if front == "evloop" and ssl_context_from_env() is not None:
        # the evloop front has no TLS path: refusing to downgrade the
        # transport silently, serve threaded instead
        log.warning(
            "PIO_TPU_HTTP_FRONT=evloop ignored: TLS is configured and "
            "only the threaded front terminates it"
        )
        front = "threaded"
    if front == "evloop":
        from pio_tpu.server.evfront import EvLoopHTTPServer

        server = EvLoopHTTPServer(
            service.router, host, port, name="pio-tpu-queryserver",
            ssl_context=None, reuse_port=reuse_port,
            registry=service.obs,
        )
        service.mark_evloop_front()
    else:
        server = JsonHTTPServer(
            service.router, host, port, name="pio-tpu-queryserver",
            reuse_port=reuse_port,
        )
    return server, service
