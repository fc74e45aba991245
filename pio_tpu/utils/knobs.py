"""Canonical ``PIO_TPU_*`` configuration-knob registry.

Every environment knob the server reads is declared here exactly once —
name, parse kind, default, and the one-line doc that feeds the generated
"Configuration knobs" table in docs/operations.md. Readers go through
:func:`knob_int` / :func:`knob_float` / :func:`knob_str` (or
:func:`knob_raw` where *unset vs set* is significant), which pull the
default and positivity constraint from the declaration — so two modules
can never again disagree about what an unset knob means.

``pio lint`` enforces the discipline both ways: ``knob-default-drift``
flags any literal ``os.environ[...]`` / ``env_int(...)`` read of a
``PIO_TPU_*`` name that bypasses this registry or disagrees with it,
and ``knob-doc-drift`` keeps the docs table and this file in lockstep.
``pio lint --dump-contracts`` emits the whole inventory as JSON.

Parse discipline matches :mod:`pio_tpu.utils.envutil`: numeric knobs
warn and fall back to the declared default on garbage instead of
crashing at import time. String knobs are returned verbatim (callers
own ``strip()``/``lower()`` normalisation — several are tri-state flags
like ``auto``/``host``/``0`` where exact semantics live at the call
site).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from pio_tpu.utils import envutil


@dataclass(frozen=True)
class Knob:
    """One declared configuration knob."""

    name: str
    kind: str                      # "int" | "float" | "str"
    default: object                # the value an unset env means
    doc: str
    positive: bool = False         # numeric knobs: reject <= 0 values

    def default_repr(self) -> str:
        """The default as it appears in the docs table cell."""
        return "(empty)" if self.default == "" else str(self.default)


_DECLARATIONS: Tuple[Knob, ...] = (
    # -- serving fronts / HTTP plumbing ---------------------------------
    Knob("PIO_TPU_HTTP_FRONT", "str", "threaded",
         "HTTP front implementation: `threaded` or `evloop`"),
    Knob("PIO_TPU_HTTP_BACKLOG", "int", 128, "listen(2) backlog for "
         "both fronts", positive=True),
    Knob("PIO_TPU_HTTP_IDLE_TIMEOUT_S", "float", 30.0,
         "idle keep-alive connection timeout, seconds", positive=True),
    Knob("PIO_TPU_HTTP_MAX_PIPELINE", "int", 16,
         "max pipelined requests parsed per evloop read burst",
         positive=True),
    Knob("PIO_TPU_MAX_BODY_MB", "float", 4096.0,
         "hard cap on any request body, MB", positive=True),
    Knob("PIO_TPU_MAX_JSON_BODY_MB", "float", 64.0,
         "cap on JSON request bodies, MB", positive=True),
    Knob("PIO_TPU_SSL_CERTFILE", "str", "",
         "TLS certificate path; unset serves plaintext"),
    Knob("PIO_TPU_SSL_KEYFILE", "str", "",
         "TLS private-key path (defaults to the certfile)"),
    # -- query serving ---------------------------------------------------
    Knob("PIO_TPU_SERVE_DEVICE", "str", "auto",
         "scoring placement: `auto`, `host`, or `device`"),
    Knob("PIO_TPU_SERVE_WIRE", "str", "auto",
         "serve-path wire encoding override"),
    Knob("PIO_TPU_DEVICE_RESIDENT", "str", "auto",
         "pin model params device-resident: `auto`/`1`/`0`"),
    Knob("PIO_TPU_MESH_SERVE", "str", "0",
         "serve through the worker mesh instead of in-process"),
    Knob("PIO_TPU_SERVE_MICROBATCH_US", "float", 0.0,
         "micro-batching window, microseconds; 0 disables"),
    Knob("PIO_TPU_SERVE_MICROBATCH_ADAPTIVE", "str", "1",
         "`0` pins the micro-batch window instead of adapting it"),
    Knob("PIO_TPU_BATCH_LANE", "str", "1",
         "`0` disables the shared-memory batch lane to mesh workers"),
    Knob("PIO_TPU_BATCH_BUCKETS", "str", "",
         "comma-separated batch-size bucket ladder override"),
    Knob("PIO_TPU_BUCKET_WARMUP", "str", "",
         "`1`/`0` force or forbid bucket warm-up compilation"),
    Knob("PIO_TPU_LANE_SLOTS", "int", 64,
         "batch-lane slots per worker", positive=True),
    Knob("PIO_TPU_LANE_SLOT_BYTES", "int", 16384,
         "payload bytes per batch-lane slot", positive=True),
    Knob("PIO_TPU_LANE_TIMEOUT_S", "float", 0.25,
         "batch-lane reply wait before falling back to HTTP",
         positive=True),
    Knob("PIO_TPU_MB_REPROBE_S", "float", 30.0,
         "seconds between micro-batch mode reprobes"),
    Knob("PIO_TPU_HEARTBEAT_MAX_AGE_S", "float", 30.0,
         "worker heartbeat age before the pool restarts it",
         positive=True),
    # -- SLO / QoS / degrade ---------------------------------------------
    Knob("PIO_TPU_SLO", "str", "",
         "SLO spec, e.g. `p99:200ms,availability:0.999`"),
    Knob("PIO_TPU_QOS", "str", "",
         "QoS admission spec (class weights and shed policy)"),
    Knob("PIO_TPU_SLOW_TRACE_MS", "float", 0.0,
         "emit a trace for requests slower than this; 0 disables"),
    # -- observability ---------------------------------------------------
    Knob("PIO_TPU_LOG_JSON", "str", "",
         "`1` renders console logs as JSON lines"),
    Knob("PIO_TPU_LOG_RING", "int", 512,
         "in-memory log ring capacity backing /logs.json"),
    Knob("PIO_TPU_PROFILE", "str", "",
         "directory for device profiler traces; unset disables"),
    Knob("PIO_TPU_PROFILE_EXECUTIONS", "int", 8,
         "executions captured per profile burst", positive=True),
    Knob("PIO_TPU_DEVICEWATCH", "str", "1",
         "`0` disables the device telemetry sampler"),
    Knob("PIO_TPU_DEVICEWATCH_INTERVAL_S", "float", 2.0,
         "device sampler period, seconds"),
    Knob("PIO_TPU_DEVICE_BUDGET_BYTES", "int", 0,
         "per-chip HBM budget; 0 means the library default"),
    Knob("PIO_TPU_FLEET_TARGETS", "str", "",
         "comma-separated `name=host:port` members to scrape"),
    Knob("PIO_TPU_FLEET_INTERVAL_S", "float", 5.0,
         "fleet scrape period, seconds", positive=True),
    Knob("PIO_TPU_TRAIN_STATUS_PORT", "int", 0,
         "port for the training status endpoint; 0 disables"),
    Knob("PIO_TPU_TRAIN_STATUS_URL", "str", "",
         "dashboard override for the training status URL"),
    # -- training / models -----------------------------------------------
    Knob("PIO_TPU_TRAIN_STREAM_MB", "float", 64.0,
         "streamed training-batch chunk size, MB; <= 0 disables"),
    Knob("PIO_TPU_ALS_STREAM_MB", "float", 30.0,
         "streamed ALS edge-shipment chunk size, MB; <= 0 disables"),
    Knob("PIO_TPU_LOGREG_STREAM_MB", "float", 8.0,
         "streamed logreg feature chunk size, MB; <= 0 disables"),
    Knob("PIO_TPU_EMBED_PALLAS_OVER_MB", "float", 2048.0,
         "embedding table size above which the Pallas kernel is used"),
    Knob("PIO_TPU_EVAL_APP", "str", "",
         "default app name for template evaluation runs"),
    Knob("PIO_TPU_NO_NATIVE", "str", "",
         "any value disables the native (graft) fast paths"),
    # -- distributed -----------------------------------------------------
    Knob("PIO_TPU_COORDINATOR", "str", "",
         "multi-process coordinator `host:port`; unset = single host"),
    Knob("PIO_TPU_NUM_PROCESSES", "str", "",
         "world size for multi-process init; unset = single process"),
    Knob("PIO_TPU_PROCESS_ID", "str", "",
         "this process's rank for multi-process init"),
    # -- storage / durability --------------------------------------------
    Knob("PIO_TPU_HOME", "str", "",
         "state directory root; unset means `~/.pio_tpu`"),
    Knob("PIO_TPU_DURABILITY", "str", "batch",
         "event-log durability mode: `commit`, `batch`, or `os`"),
    Knob("PIO_TPU_SHARDED_PERSIST", "str", "0",
         "`1` persists model shards from every process"),
    Knob("PIO_TPU_BLOB_ACCESS_KEY", "str", "",
         "access key for the blob storage backend"),
    Knob("PIO_TPU_PARTLOG_PARTITIONS", "int", 4,
         "partitioned-log partition count", positive=True),
    Knob("PIO_TPU_PARTLOG_SEGMENT_BYTES", "int", 4 * 1024 * 1024,
         "partitioned-log segment roll size, bytes", positive=True),
    Knob("PIO_TPU_PARTLOG_REPLICAS", "str", "",
         "comma-separated follower `host:port` replica addresses"),
    Knob("PIO_TPU_REPL_MIN_ACKS", "int", 1,
         "follower acks required per append (1 when replicas are "
         "configured, else 0)", positive=False),
    Knob("PIO_TPU_REPL_ACK_TIMEOUT_S", "float", 2.0,
         "replication ack wait, seconds", positive=True),
    Knob("PIO_TPU_REPL_CONNECT_DEADLINE_S", "float", 10.0,
         "replication connect retry deadline, seconds", positive=True),
    # -- router / rollout ------------------------------------------------
    Knob("PIO_TPU_ROUTER_BURN_LIMIT", "float", 2.0,
         "SLO burn rate above which the router sheds a member",
         positive=True),
    Knob("PIO_TPU_ROUTER_LAG_SOFT_BYTES", "float", 64.0 * 1024 * 1024,
         "replication lag where router scoring starts to penalise",
         positive=True),
    Knob("PIO_TPU_ROUTER_HEDGE_MS", "float", 0.0,
         "hedged second request delay, milliseconds; 0 disables"),
    # -- faults / plugins / debug ----------------------------------------
    Knob("PIO_TPU_FAULTS", "str", "",
         "failpoint spec, e.g. `router.pick=error:0.1`"),
    Knob("PIO_TPU_PLUGINS", "str", "",
         "comma-separated plugin modules imported at server start"),
    Knob("PIO_TPU_DEBUG_SYNC", "str", "",
         "`1`/`raise`/`log` arms the instrumented lock runtime"),
)

#: name -> declaration; THE canonical knob inventory
KNOBS: Dict[str, Knob] = {k.name: k for k in _DECLARATIONS}


def get(name: str) -> Knob:
    """The declaration for ``name`` (KeyError when unregistered)."""
    return KNOBS[name]


def all_knobs() -> Tuple[Knob, ...]:
    """Every declaration, sorted by name."""
    return tuple(sorted(_DECLARATIONS, key=lambda k: k.name))


def _lookup(name: str, kind: str, fallback) -> Optional[Knob]:
    k = KNOBS.get(name)
    if k is None:
        if fallback is None:
            raise KeyError(f"unregistered knob {name!r} (declare it in "
                           f"pio_tpu/utils/knobs.py)")
        return None
    if k.kind != kind:
        raise TypeError(f"knob {name} is declared {k.kind}, read as {kind}")
    return k


def knob_int(name: str, fallback: Optional[int] = None) -> int:
    """Registry-backed :func:`envutil.env_int`. ``fallback`` applies
    only to *unregistered* names (scratch knobs in tests)."""
    k = _lookup(name, "int", fallback)
    if k is None:
        return envutil.env_int(name, int(fallback))
    return envutil.env_int(name, int(k.default), positive=k.positive)


def knob_float(name: str, fallback: Optional[float] = None) -> float:
    """Registry-backed :func:`envutil.env_float`."""
    k = _lookup(name, "float", fallback)
    if k is None:
        return envutil.env_float(name, float(fallback))
    return envutil.env_float(name, float(k.default), positive=k.positive)


def knob_str(name: str, fallback: Optional[str] = None) -> str:
    """String knob read: the raw env value, or the declared default
    when unset. No normalisation — tri-state flags keep their call-site
    semantics."""
    k = _lookup(name, "str", fallback)
    default = fallback if k is None else k.default
    raw = os.environ.get(name)
    return str(default) if raw is None else raw


def knob_raw(name: str) -> Optional[str]:
    """The raw env value or ``None`` — for knobs where *unset* is
    semantically different from any set value (e.g. distributed init
    and TLS config). The name must still be registered."""
    get(name)
    return os.environ.get(name)


#: markers bounding the generated table in docs/operations.md
TABLE_BEGIN = "<!-- knob-table:begin -->"
TABLE_END = "<!-- knob-table:end -->"


def markdown_table() -> str:
    """The docs/operations.md "Configuration knobs" table body —
    regenerate with ``python -m pio_tpu.utils.knobs``. The
    ``knob-doc-drift`` lint rule asserts the doc matches."""
    lines = ["| Knob | Type | Default | Description |",
             "| --- | --- | --- | --- |"]
    for k in all_knobs():
        lines.append(
            f"| `{k.name}` | {k.kind} | `{k.default_repr()}` | {k.doc} |"
        )
    return "\n".join(lines)


if __name__ == "__main__":  # pragma: no cover - doc regeneration helper
    print(markdown_table())
