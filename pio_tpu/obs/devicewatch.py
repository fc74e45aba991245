"""Device telemetry plane — live HBM accounting + compile attribution
(ISSUE 17).

The server observed engines (queries, stages, replication, training
progress) but ran blind to its accelerators: every device-memory fact
in the tree was an estimate (``per_device_nbytes`` bookkeeping) and
every compile an inference from retrace counters. This module is the
third telemetry plane, mirroring the fleet (ISSUE 11) and training
(ISSUE 16) planes, with three surfaces:

- **Sampler** — :class:`DeviceWatch` periodically reads per-device
  ``Device.memory_stats()`` (bytes_in_use / peak / limit) where the
  backend supports it and falls back to a book-kept ledger (resident
  scorers, shard placements, donated buffers, stream carry) on
  backends that don't (CPU). Sampling runs on its OWN thread — no
  device sync is ever injected into a dispatch path.
- **Compile attribution** — the in-tree jit entry points (bucket
  warmup, resident scorer programs, stream dispatch, trainer steps)
  wrap their cache-fresh dispatches in :func:`compile_span`, so every
  trace+compile lands in ``pio_tpu_xla_compile_total{site}`` and a
  ``pio_tpu_xla_compile_seconds{site}`` histogram with trace
  exemplars. Steady-state serving must show the counters FLAT — the
  ISSUE-7 "zero retraces" claim becomes a directly monitored
  invariant. ("Compile" here means a dispatch whose site-level program
  cache had no entry for the shape key: the span brackets jit's
  trace+compile entry. A shape the global jit cache already holds —
  e.g. a hot-swap re-warm over an unchanged bucket ladder — is NOT
  recounted, matching what XLA actually does.)
- **The real compile path** — :func:`watch_xla_compiles` listens to
  JAX's own monitoring events, so what it traced, what it lowered, what
  the backend compiled and what it loaded from the persistent
  compilation cache are counted apart, process wide, whichever site
  dispatched (:func:`xla_totals`; ``pio_tpu_xla_trace*`` /
  ``pio_tpu_xla_lower*`` / ``pio_tpu_xla_backend_compile*`` /
  ``pio_tpu_xla_cache_load*``), and by program
  (:func:`xla_by_program`). The site counters above infer a compile
  from a shape key new to the site; these count JAX's own calls.
- **Endpoints** — ``payload()`` renders ``GET /device.json`` on the
  query server and the trainer status sidecar; the fleet aggregator
  federates it into ``/fleet.json`` as a per-member ``devices`` block
  (the budget-driven-eviction input of ROADMAP item 6); ``pio top``
  polls it into a live terminal table and ``pio dashboard`` renders
  ``/devices.html``.

Like trainwatch, the active watch is a module GLOBAL under a lock (not
a contextvar): the status sidecar's HTTP thread must see the watch the
driver thread activated. Library code records through the module-level
no-op hooks (``ledger_place``/``record_compile``/…) which cost one
``None`` check when no watch is active.

Headroom is accounted against ``PIO_TPU_DEVICE_BUDGET_BYTES`` (the
same env :mod:`pio_tpu.parallel.partition` enforces at placement):
``pio_tpu_device_budget_headroom_bytes = budget - max(bytes_in_use)``.
When live ``memory_stats()`` and the ledger disagree the gap is
exported as ``pio_tpu_device_estimate_drift_bytes{device}`` — the
estimate-honesty gauge ROADMAP item 3 asked for.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from pio_tpu.utils import knobs
from pio_tpu.obs.metrics import (
    REGISTRY,
    MetricsRegistry,
    monotonic_s,
)

log = logging.getLogger("pio_tpu.obs.devicewatch")

#: sampler interval; the thread wakes, samples, sleeps — never touches
#: a dispatch path
INTERVAL_ENV = "PIO_TPU_DEVICEWATCH_INTERVAL_S"
DEFAULT_INTERVAL_S = 2.0

#: shared with pio_tpu.parallel.partition (placement enforcement reads
#: the same budget this plane reports headroom against)
BUDGET_ENV = "PIO_TPU_DEVICE_BUDGET_BYTES"

#: set to ``0`` to keep the sampler thread off (payload() then samples
#: on demand — the endpoint still answers, just without a fresh series)
SAMPLER_ENV = "PIO_TPU_DEVICEWATCH"

#: documented compile-attribution sites (the jit entry points wrapped
#: in-tree); cells are pre-created per site so pool-mode shm mirroring
#: sees them before the bind
COMPILE_SITES = (
    "bucket_warmup",     # deploy-time bucket ladder sweep (query server)
    "bucket_dispatch",   # a LIVE dispatch that retraced (should be 0)
    "resident_scorer",   # device-resident scorer program per bucket
    "stream_dispatch",   # streamed-feed chunk program (training h2d path)
    "train_step",        # staged/full trainer chunk programs
)

#: ledger categories the fallback accounting books under
LEDGER_CATEGORIES = ("resident", "donated", "shard", "stream")

#: compile latencies span warmup-sweep milliseconds to multi-second
#: first traces; the default request-latency buckets top out too low
COMPILE_BUCKETS = (
    0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)


def _register_families(reg: MetricsRegistry) -> dict:
    """Create (or fetch — registration is idempotent) the device
    families on ``reg``. Gauges never bind to the pool segment, so the
    per-device series are safe on a pool worker's registry."""
    return {
        "in_use": reg.gauge(
            "pio_tpu_device_bytes_in_use",
            "Bytes currently allocated on the device (memory_stats "
            "where supported, else the book-kept ledger)",
            ("device",),
        ),
        "peak": reg.gauge(
            "pio_tpu_device_peak_bytes",
            "High-water allocation mark per device",
            ("device",),
        ),
        "limit": reg.gauge(
            "pio_tpu_device_limit_bytes",
            "Allocatable byte limit the backend reports per device",
            ("device",),
        ),
        "headroom": reg.gauge(
            "pio_tpu_device_budget_headroom_bytes",
            "PIO_TPU_DEVICE_BUDGET_BYTES minus the busiest device's "
            "bytes_in_use (only set when a budget is configured)",
        ),
        "drift": reg.gauge(
            "pio_tpu_device_estimate_drift_bytes",
            "memory_stats bytes_in_use minus the book-kept ledger for "
            "the device (set when both sides have data and disagree)",
            ("device",),
        ),
        "compile_total": reg.counter(
            "pio_tpu_xla_compile_total",
            "Trace+compile entries attributed per in-tree jit site; "
            "steady-state serving must hold these flat",
            ("site",),
        ),
        "compile_seconds": reg.histogram(
            "pio_tpu_xla_compile_seconds",
            "Wall seconds of attributed trace+compile dispatches, with "
            "trace exemplars",
            ("site",),
            buckets=COMPILE_BUCKETS,
        ),
    }


# the process-global families exist from import on (trainer sidecar and
# stream/partition hooks render through REGISTRY)
_register_families(REGISTRY)


def _active_trace_id() -> Optional[str]:
    try:
        from pio_tpu.obs.tracing import active_trace

        h = active_trace()
        return h.trace_id if h is not None else None
    except Exception:
        return None


def shape_key(tree: Any) -> tuple:
    """Hashable per-leaf shape tuple for ``fresh``-keying a pytree
    dispatch (a chunk with new leaf shapes is a new program)."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:
        leaves = [tree]
    return tuple(tuple(getattr(leaf, "shape", ())) for leaf in leaves)


class DeviceWatch:
    """Per-process (or per-daemon) device telemetry hub.

    The query server holds one on its per-instance registry; a training
    run activates one on the process-global registry for the sidecar.
    All mutation is lock-guarded host bookkeeping — the only device
    interaction is ``memory_stats()`` reads from the sampler thread.
    """

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        interval_s: Optional[float] = None,
        budget_bytes: Optional[int] = None,
        stats_fn: Optional[Callable[[], List[tuple]]] = None,
    ):
        reg = registry if registry is not None else REGISTRY
        self.registry = reg
        fams = _register_families(reg)
        self._g_in_use = fams["in_use"]
        self._g_peak = fams["peak"]
        self._g_limit = fams["limit"]
        self._g_headroom = fams["headroom"]
        self._g_drift = fams["drift"]
        self._compile_total = fams["compile_total"]
        self._compile_seconds = fams["compile_seconds"]
        # pre-created site cells: pool shm slots must exist before any
        # enable_pool bind, and hot-path increments skip labels()
        self._compile_cells = {
            s: self._compile_total.labels(s) for s in COMPILE_SITES
        }
        for s in COMPILE_SITES:
            self._compile_seconds.labels(s)
        if interval_s is None:
            interval_s = knobs.knob_float(INTERVAL_ENV)
        self.interval_s = max(0.05, float(interval_s))
        if budget_bytes is None:
            budget_bytes = knobs.knob_int(BUDGET_ENV)
        self.budget_bytes = int(budget_bytes)
        self._stats_fn = stats_fn
        self._lock = threading.Lock()
        #: (category, key) → placement row; the CPU-fallback accounting
        self._ledger: Dict[Tuple[str, str], dict] = {}
        #: (site, key) freshness set backing :meth:`fresh`
        self._seen: set = set()
        #: site → compile table row (count, seconds, last trace)
        self._compiles: Dict[str, dict] = {}
        self._generation: Optional[int] = None
        self._peaks: Dict[str, int] = {}
        self._rows: List[dict] = []
        self._mode = "ledger"
        self._samples = 0
        self._started_at = monotonic_s()
        self._stop_ev = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- compile attribution -----------------------------------------------
    def fresh(self, site: str, key: Any) -> bool:
        """First sighting of ``(site, key)``? ``key=None`` is always
        fresh (unconditional sites like the warmup sweep own their own
        dedup via bucket keys)."""
        if key is None:
            return True
        k = (site, key)
        with self._lock:
            if k in self._seen:
                return False
            self._seen.add(k)
            return True

    def record_compile(
        self,
        site: str,
        seconds: Optional[float] = None,
        trace_id: Optional[str] = None,
    ) -> None:
        cell = self._compile_cells.get(site)
        if cell is not None:
            cell.inc()
        else:
            self._compile_total.inc(site=site)
        with self._lock:
            row = self._compiles.setdefault(
                site,
                {"count": 0, "seconds": 0.0, "lastS": None,
                 "lastTraceId": None},
            )
            row["count"] += 1
            if seconds is not None:
                row["seconds"] = round(row["seconds"] + float(seconds), 6)
                row["lastS"] = round(float(seconds), 6)
            if trace_id:
                row["lastTraceId"] = trace_id
        if seconds is not None:
            self._compile_seconds.observe(
                float(seconds), exemplar=trace_id, site=site
            )

    @contextlib.contextmanager
    def span(self, site: str, key: Any = None):
        """Bracket a possibly-compiling dispatch: yields True (and
        records count + wall seconds + trace exemplar) when ``key`` is
        fresh for ``site``, False (no record, no timing) otherwise."""
        if not self.fresh(site, key):
            yield False
            return
        t0 = monotonic_s()
        yield True
        self.record_compile(
            site, monotonic_s() - t0, trace_id=_active_trace_id()
        )

    def compile_counts(self) -> Dict[str, int]:
        with self._lock:
            return {s: r["count"] for s, r in self._compiles.items()}

    # -- ledger -------------------------------------------------------------
    def ledger_place(
        self,
        category: str,
        key: Any,
        nbytes: int,
        device: int = 0,
        name: Optional[str] = None,
    ) -> None:
        """Book ``nbytes`` resident under ``(category, key)``; replaces
        a prior placement under the same key (re-place = resize)."""
        with self._lock:
            self._ledger[(category, str(key))] = {
                "category": category,
                "key": str(key),
                "name": name or str(key),
                "bytes": int(nbytes),
                "device": int(device),
                "generation": self._generation,
            }

    def ledger_release(self, category: str, key: Any) -> None:
        with self._lock:
            self._ledger.pop((category, str(key)), None)

    def ledger_clear(self, category: Optional[str] = None) -> None:
        with self._lock:
            if category is None:
                self._ledger.clear()
                return
            for k in [k for k in self._ledger if k[0] == category]:
                del self._ledger[k]

    def stream_carry(self, delta: int) -> None:
        """Streamed-feed in-flight bytes: chunks add on put, release on
        (non-retained) dispatch or feed finalize; floored at zero."""
        with self._lock:
            row = self._ledger.get(("stream", "carry"))
            if row is None:
                row = {
                    "category": "stream", "key": "carry",
                    "name": "stream carry", "bytes": 0, "device": 0,
                    "generation": self._generation,
                }
                self._ledger[("stream", "carry")] = row
            row["bytes"] = max(0, row["bytes"] + int(delta))

    def ledger_bytes(self, device: Optional[int] = None) -> int:
        with self._lock:
            return sum(
                row["bytes"] for row in self._ledger.values()
                if device is None or row["device"] == int(device)
            )

    def set_generation(self, gen: int) -> None:
        """Stamp the serving generation (hot-swap bump). Placements
        booked before the swap installed (generation still unknown)
        are restamped with the generation they went live under."""
        with self._lock:
            self._generation = int(gen)
            for row in self._ledger.values():
                if row["generation"] is None:
                    row["generation"] = int(gen)

    # -- sampling -----------------------------------------------------------
    def _device_stats(self) -> List[tuple]:
        """``[(label, memory_stats_or_None, device_index)]`` for every
        visible device; synthetic rows from the ledger when no backend
        is importable at all."""
        if self._stats_fn is not None:
            return self._stats_fn()
        try:
            import jax

            devices = jax.devices()
        except Exception:
            devices = None
        if not devices:
            with self._lock:
                idxs = sorted(
                    {row["device"] for row in self._ledger.values()}
                ) or [0]
            return [(f"device:{i}", None, i) for i in idxs]
        out = []
        for i, d in enumerate(devices):
            stats = None
            try:
                ms = d.memory_stats()
                if ms and ms.get("bytes_in_use") is not None:
                    stats = ms
            except Exception:
                stats = None
            label = f"{getattr(d, 'platform', 'device')}:" \
                    f"{getattr(d, 'id', i)}"
            out.append((label, stats, i))
        return out

    def sample(self) -> List[dict]:
        """One telemetry pass: read (or book-keep) every device's bytes,
        update the gauges, compute headroom and estimate drift. Host
        work + guarded ``memory_stats`` reads only — never a sync."""
        from pio_tpu.faults import failpoint

        failpoint("devicewatch.sample")
        entries = self._device_stats()
        live = any(stats is not None for _, stats, _ in entries)
        rows: List[dict] = []
        max_in_use = 0
        for label, stats, idx in entries:
            ledger = self.ledger_bytes(device=idx)
            if stats is not None:
                in_use = int(stats.get("bytes_in_use") or 0)
                peak = int(stats.get("peak_bytes_in_use") or in_use)
                limit = stats.get("bytes_limit")
                limit = int(limit) if limit else None
                source = "memory_stats"
            else:
                in_use, peak, limit = ledger, ledger, None
                source = "ledger"
            with self._lock:
                peak = max(self._peaks.get(label, 0), peak, in_use)
                self._peaks[label] = peak
            drift = (
                in_use - ledger
                if (stats is not None and ledger > 0) else None
            )
            rows.append({
                "device": label,
                "bytesInUse": in_use,
                "peakBytes": peak,
                "limitBytes": limit,
                "ledgerBytes": ledger,
                "driftBytes": drift,
                "source": source,
            })
            max_in_use = max(max_in_use, in_use)
            self._g_in_use.set(float(in_use), device=label)
            self._g_peak.set(float(peak), device=label)
            if limit is not None:
                self._g_limit.set(float(limit), device=label)
            if drift is not None:
                self._g_drift.set(float(drift), device=label)
        if self.budget_bytes > 0:
            self._g_headroom.set(float(self.budget_bytes - max_in_use))
        with self._lock:
            self._rows = rows
            self._mode = "live" if live else "ledger"
            self._samples += 1
        return rows

    def measured_bytes(self) -> Optional[int]:
        """Backend-measured total bytes_in_use from the last sample, or
        None when only the ledger is available (CPU) — the honesty
        companion to the estimated ``paramBytes`` in ``/stats.json``."""
        with self._lock:
            if self._mode != "live":
                return None
            return sum(
                r["bytesInUse"] for r in self._rows
                if r["source"] == "memory_stats"
            )

    # -- payload ------------------------------------------------------------
    # pio: endpoint=/device.json
    def payload(self) -> dict:
        """The ``GET /device.json`` body (schema in
        docs/observability.md). Always samples inline — sample() is
        host-only work and /device.json is a telemetry endpoint, not
        the dispatch hot path; serving the background thread's last
        pass instead would leave scrapes up to interval_s stale (a
        scrape right after placement would show an empty device)."""
        from pio_tpu.faults import failpoint

        failpoint("devicewatch.payload")
        self.sample()
        with self._lock:
            rows = [dict(r) for r in self._rows]
            by_category: Dict[str, int] = {}
            placements = []
            for row in self._ledger.values():
                by_category[row["category"]] = (
                    by_category.get(row["category"], 0) + row["bytes"]
                )
                placements.append(dict(row))
            compiles = {
                s: dict(r) for s, r in sorted(self._compiles.items())
            }
            generation = self._generation
            samples = self._samples
            mode = self._mode
        placements.sort(
            key=lambda p: (
                p["generation"] if p["generation"] is not None else -1,
                p["category"], p["name"],
            )
        )
        max_in_use = max((r["bytesInUse"] for r in rows), default=0)
        return {
            "mode": mode,
            # pio: disable=wallclock-duration (asOf is a true timestamp)
            "asOf": time.time(),
            "uptimeS": round(monotonic_s() - self._started_at, 3),
            "intervalS": self.interval_s,
            "samples": samples,
            "sampler": self._thread is not None,
            "budgetBytes": self.budget_bytes or None,
            "headroomBytes": (
                self.budget_bytes - max_in_use
                if self.budget_bytes > 0 else None
            ),
            "generation": generation,
            "devices": rows,
            "ledger": {
                "totalBytes": sum(by_category.values()),
                "byCategory": by_category,
            },
            "placements": placements,
            "compiles": {
                "total": sum(r["count"] for r in compiles.values()),
                "sites": compiles,
            },
            "xla": xla_totals(),
        }

    # -- sampler thread -----------------------------------------------------
    def start(self) -> "DeviceWatch":
        """Spawn the background sampler (idempotent). Daemon thread:
        the plane must never hold a process open."""
        if self._thread is not None:
            return self
        self._stop_ev.clear()
        t = threading.Thread(
            target=self._run, name="pio-devicewatch", daemon=True
        )
        self._thread = t
        t.start()
        return self

    def _run(self) -> None:
        while True:
            try:
                self.sample()
            except Exception:
                log.exception("device sample failed")
            if self._stop_ev.wait(self.interval_s):
                return

    def stop(self) -> None:
        self._stop_ev.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
        self._thread = None


# ---------------------------------------------------------------------------
# module-global active watch (the trainwatch discipline: a LOCKED global,
# not a contextvar — the sidecar HTTP thread must see the driver's watch)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[DeviceWatch] = None
_ACTIVE_LOCK = threading.Lock()
#: last deactivated watch — bench reads a finished training run's peaks
_LAST: Optional[DeviceWatch] = None


def activate(watch: DeviceWatch) -> None:
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = watch


def deactivate(watch: Optional[DeviceWatch] = None) -> None:
    """Clear the active watch; with ``watch`` given, only when it is
    still the active one (a later activation wins)."""
    global _ACTIVE, _LAST
    with _ACTIVE_LOCK:
        if watch is None or _ACTIVE is watch:
            if _ACTIVE is not None:
                _LAST = _ACTIVE
            _ACTIVE = None


def active_watch() -> Optional[DeviceWatch]:
    return _ACTIVE


def last_watch() -> Optional[DeviceWatch]:
    """The most recently deactivated watch (bench post-mortems)."""
    return _ACTIVE or _LAST


@contextlib.contextmanager
def watching(watch: DeviceWatch, sample: bool = True):
    """Activate ``watch`` (and run its sampler) for a scope — the
    training driver wraps the run so the status sidecar can serve
    ``/device.json`` while steps stream."""
    activate(watch)
    if sample:
        watch.start()
    try:
        yield watch
    finally:
        if sample:
            watch.stop()
        deactivate(watch)


# ---------------------------------------------------------------------------
# JAX's compile path: its own monitoring events, process totals and by program
# ---------------------------------------------------------------------------

#: jax 0.9.0 ``jax/_src/dispatch.py``: ``log_elapsed_time`` brackets each
#: step of the compile path and reports it twice, as a scalar (the start
#: time) when the step begins and as a duration when it ends, both with
#: ``fun_name``. Tracing nests (a jitted function traced inside another
#: is an event inside the outer's), and whatever a trace runs eagerly
#: compiles inside it: the begin events are what lets each step be charged
#: its own seconds and not its children's.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: one duration event per ``compile_or_get_cached`` call, a
#: persistent-cache hit included
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: jax 0.9.0 ``jax/_src/compiler.py``: fired inside that call, before it
#: ends, only when the executable came from the persistent cache
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: by-program rows kept; the smallest folds into :data:`_OTHER_PROGRAMS`
MAX_PROGRAMS = 64
_OTHER_PROGRAMS = "(other)"

_XLA_LOCK = threading.Lock()
_XLA_TOTALS = {"traces": 0, "trace_s": 0.0, "lowers": 0, "lower_s": 0.0,
               "compiles": 0, "compile_s": 0.0,
               "cache_loads": 0, "cache_load_s": 0.0}
_XLA_BY_PROGRAM: Dict[str, dict] = {}
_XLA_WATCHING = False
#: ``.loaded``: a retrieval event is pending; ``.open``: the seconds of the
#: ended steps inside each step still open on this thread, outermost first
_XLA_THREAD = threading.local()


#: step of the compile path -> (totals' count key, seconds key, the two
#: process-global counter families beside the inferred per-site ones)
_XLA_KINDS = {
    "trace": ("traces", "trace_s", REGISTRY.counter(
        "pio_tpu_xla_traces_total",
        "Jitted functions JAX traced to a jaxpr (its jaxpr-trace events; "
        "a function traced inside another counts too)",
    ), REGISTRY.counter(
        "pio_tpu_xla_trace_seconds_total",
        "Wall seconds of those traces, each less the traces inside it",
    )),
    "lower": ("lowers", "lower_s", REGISTRY.counter(
        "pio_tpu_xla_lowers_total",
        "Jaxprs JAX lowered to an MLIR module (its jaxpr-to-MLIR events)",
    ), REGISTRY.counter(
        "pio_tpu_xla_lower_seconds_total",
        "Wall seconds of those lowerings",
    )),
    "compile": ("compiles", "compile_s", REGISTRY.counter(
        "pio_tpu_xla_backend_compiles_total",
        "Programs the XLA backend compiled (JAX's backend-compile events "
        "that were not persistent-cache loads)",
    ), REGISTRY.counter(
        "pio_tpu_xla_backend_compile_seconds_total",
        "Wall seconds of those backend compiles",
    )),
    "cache_load": ("cache_loads", "cache_load_s", REGISTRY.counter(
        "pio_tpu_xla_cache_loads_total",
        "Executables loaded from JAX's persistent compilation cache",
    ), REGISTRY.counter(
        "pio_tpu_xla_cache_load_seconds_total",
        "Wall seconds of those persistent-cache loads",
    )),
}
_XLA_EVENT_KIND = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
                   _BACKEND_COMPILE_EVENT: "compile"}


def _program_name(fun_name: Optional[str]) -> str:
    """One name a program through the three steps: a trace reports the
    function's ``__name__`` (``chunk_staged``), lowering and compiling the
    module's (``jit(chunk_staged)``); both read ``jit_chunk_staged``, the
    name the program has in a device trace."""
    if not fun_name:
        return "(unnamed)"
    head, paren, rest = fun_name.partition("(")
    if paren and rest.endswith(")"):
        return f"{head}_{rest[:-1]}"
    return f"jit_{fun_name}"


def _charge_program(name: str, s_key: str, seconds: float,
                    traced: bool) -> None:
    """Under ``_XLA_LOCK``. A table that is full folds its cheapest row
    into ``(other)`` first, so the costly programs keep their names."""
    row = _XLA_BY_PROGRAM.get(name)
    if row is None:
        if len(_XLA_BY_PROGRAM) >= MAX_PROGRAMS:
            cheapest = min(
                (n for n in _XLA_BY_PROGRAM if n != _OTHER_PROGRAMS),
                key=lambda n: sum(
                    v for k, v in _XLA_BY_PROGRAM[n].items() if k != "n"))
            folded = _XLA_BY_PROGRAM.pop(cheapest)
            other = _XLA_BY_PROGRAM.setdefault(
                _OTHER_PROGRAMS, dict.fromkeys(folded, 0))
            for k, v in folded.items():
                other[k] += v
        row = _XLA_BY_PROGRAM.setdefault(name, {
            "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_load_s": 0.0, "n": 0})
    row[s_key] += seconds
    row["n"] += traced


def _on_xla_begin(event: str, _start_time: float, **_kw) -> None:
    if event in _XLA_EVENT_KIND:
        if not hasattr(_XLA_THREAD, "open"):
            _XLA_THREAD.open = []
        _XLA_THREAD.open.append(0.0)


def _on_xla_duration(event: str, duration: float, fun_name=None,
                     **_kw) -> None:
    if event == _CACHE_RETRIEVAL_EVENT:
        _XLA_THREAD.loaded = True
        return
    kind = _XLA_EVENT_KIND.get(event)
    if kind is None:
        return
    if kind == "compile" and getattr(_XLA_THREAD, "loaded", False):
        kind = "cache_load"
        _XLA_THREAD.loaded = False
    duration = max(0.0, float(duration))
    # a step that began before anything listened has no entry: all its own
    still_open = getattr(_XLA_THREAD, "open", None)
    inside = still_open.pop() if still_open else 0.0
    if still_open:
        still_open[-1] += duration
    own = max(0.0, duration - inside)
    n_key, s_key, count, seconds = _XLA_KINDS[kind]
    with _XLA_LOCK:
        _XLA_TOTALS[n_key] += 1
        _XLA_TOTALS[s_key] += own
        _charge_program(_program_name(fun_name), s_key, own,
                        traced=kind == "trace")
    count.inc()
    seconds.inc(own)


def watch_xla_compiles() -> None:
    """Start counting what JAX traces, lowers, compiles and loads from its
    cache, once per process. Called where a
    :class:`~pio_tpu.parallel.context.ComputeContext` is
    built, which every entry point does before its first program; JAX is
    imported by then. Touches no dispatch path: JAX calls the listeners
    from inside its own trace, lowering and compile calls."""
    global _XLA_WATCHING
    with _XLA_LOCK:
        if _XLA_WATCHING:
            return
        _XLA_WATCHING = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_xla_begin)
    monitoring.register_event_duration_secs_listener(_on_xla_duration)


def xla_totals() -> Optional[dict]:
    """``{traces, trace_s, lowers, lower_s, compiles, compile_s,
    cache_loads, cache_load_s}`` of this process so far, or ``None`` when
    nothing is listening. A step's seconds are its own: a trace less the
    traces (and whatever else of these) inside it, so the four sum to the
    wall time the compile path took."""
    with _XLA_LOCK:
        return dict(_XLA_TOTALS) if _XLA_WATCHING else None


def xla_by_program() -> Optional[dict]:
    """The same seconds by program, ``{name: {trace_s, lower_s, compile_s,
    cache_load_s, n}}`` (``jit_chunk_staged``, ``jit_accum``; a jitted
    function traced inside a program, as a Pallas kernel's wrapper, has a
    row of its own; ``n``: times traced), at most :data:`MAX_PROGRAMS`
    names and ``(other)``; each column sums to its total. ``None`` when
    nothing is listening."""
    with _XLA_LOCK:
        if not _XLA_WATCHING:
            return None
        return {name: dict(row) for name, row in _XLA_BY_PROGRAM.items()}


# ---------------------------------------------------------------------------
# no-op hooks: library code calls these unconditionally; one None check
# when no watch is active
# ---------------------------------------------------------------------------

def record_compile(
    site: str,
    seconds: Optional[float] = None,
    trace_id: Optional[str] = None,
) -> None:
    w = _ACTIVE
    if w is not None:
        w.record_compile(site, seconds, trace_id=trace_id)


@contextlib.contextmanager
def compile_span(site: str, key: Any = None):
    """Module-level :meth:`DeviceWatch.span` against the active watch
    (yields False untimed when none is active or the key is stale)."""
    w = _ACTIVE
    if w is None:
        yield False
        return
    with w.span(site, key=key) as fresh:
        yield fresh


def ledger_place(
    category: str,
    key: Any,
    nbytes: int,
    device: int = 0,
    name: Optional[str] = None,
) -> None:
    w = _ACTIVE
    if w is not None:
        w.ledger_place(category, key, nbytes, device=device, name=name)


def ledger_release(category: str, key: Any) -> None:
    w = _ACTIVE
    if w is not None:
        w.ledger_release(category, key)


def ledger_clear(category: Optional[str] = None) -> None:
    w = _ACTIVE
    if w is not None:
        w.ledger_clear(category)


def stream_carry(delta: int) -> None:
    w = _ACTIVE
    if w is not None:
        w.stream_carry(delta)


def set_generation(gen: int) -> None:
    w = _ACTIVE
    if w is not None:
        w.set_generation(gen)
