"""Training telemetry plane — step-stream metrics, live progress, run ledger.

Serving got deep traces, SLOs and fleet federation; training exposed only
the four coarse ``pio_tpu_train_stage_seconds`` phases and two stream
counters. This module is the training-side plane (ISSUE 16):

- **StepRecorder**: the per-run telemetry hub. Training loops report
  step batches into it (loss window, examples, per-step seconds, h2d
  bytes, stream overlap); it feeds the step-stream metric families
  (``pio_tpu_train_steps_total``, ``pio_tpu_train_loss``,
  ``pio_tpu_train_step_seconds``, ``pio_tpu_train_examples_total``) and
  renders the ``/train.json`` progress payload the trainer status
  sidecar serves (phase, step/epoch/ETA, loss window, feed stats,
  per-device resident bytes).
- **Active-recorder hooks**: training loops call the module-level
  :func:`record_steps` / :func:`record_h2d` / :func:`set_phase` etc.,
  which are cheap no-ops unless a run activated a recorder — algorithm
  code never threads a recorder through its signatures, and library
  callers (tests, bench) pay nothing.
- **Run registry**: every ``run_train`` appends a flat JSON record to
  ``$PIO_TPU_HOME/runs/<engine-id>.jsonl``; ``pio runs`` lists the
  ledger and diffs consecutive runs with the same direction-aware
  regression logic bench's history ledger uses (:func:`delta_rows` is
  the shared core — bench delegates here).

Failpoints: ``trainwatch.record`` / ``trainwatch.payload`` /
``trainwatch.append`` (fault-injection surface for the telemetry plane —
a broken recorder must never break training itself, and the run-ledger
append is torn-write-testable).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from pio_tpu.utils import knobs
from pio_tpu.obs.metrics import REGISTRY, monotonic_s

#: steps retired by training loops (streamed or staged), per algorithm
_STEPS = REGISTRY.counter(
    "pio_tpu_train_steps_total",
    "Optimizer steps retired by training loops",
    ("algo",),
)

#: most recent training loss (ALS has no per-step loss; absent there)
_LOSS = REGISTRY.gauge(
    "pio_tpu_train_loss",
    "Most recent training loss reported by the step stream",
    ("algo",),
)

#: per-step wall seconds — steps inside one compiled scan chunk share
#: the chunk's mean (per-step timing is unmeasurable inside lax.scan),
#: so each observation covers one recorded step batch
_STEP_SECONDS = REGISTRY.histogram(
    "pio_tpu_train_step_seconds",
    "Mean per-step wall seconds over each recorded step batch",
    ("algo",),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0),
)

#: training examples consumed (batch rows for SGD loops, rating edges
#: for the ALS normal-equation accumulators)
_EXAMPLES = REGISTRY.counter(
    "pio_tpu_train_examples_total",
    "Training examples consumed by training loops",
    ("algo",),
)


class StepRecorder:
    """Per-run telemetry hub behind ``/train.json``.

    Thread-safe by design: the training loop writes from the driver
    thread while the status sidecar's HTTP thread reads payloads, so
    every mutation and snapshot takes the internal lock. One recorder
    covers one run (possibly several algorithms in sequence — each
    :meth:`begin_algo` resets the per-algo window but keeps run totals).
    """

    def __init__(self, run_id: str, engine_id: str = "", *,
                 loss_window: int = 64):
        self._lock = threading.Lock()
        self.run_id = run_id
        self.engine_id = engine_id
        self.started_s = monotonic_s()
        self.phase = "start"
        self.algo = ""
        self.algo_index = -1
        self.algo_started_s: Optional[float] = None
        self.total_steps = 0
        self.steps_done = 0
        self.examples_done = 0
        self.n_batches = 0
        self.streamed = False
        self.n_stream = 0
        self.solve_impl: Optional[Dict[str, str]] = None
        self.gather_impl: Optional[Dict[str, str]] = None
        self.accum_impl: Optional[Dict[str, str]] = None
        self.attn_impl: Optional[Dict[str, str]] = None
        self.ssm_impl: Optional[str] = None
        self.counters: Optional[Dict[str, float]] = None
        self.params_per_device_bytes = 0
        self.h2d_bytes = 0
        self.overlap_ratio: Optional[float] = None
        self.step_seconds = 0.0
        self.last_loss: Optional[float] = None
        self.losses: collections.deque = collections.deque(
            maxlen=max(1, loss_window)
        )
        self.phases: Dict[str, float] = {}

    # -- writes (training loop side) ------------------------------------

    def set_phase(self, name: str) -> None:
        with self._lock:
            self.phase = name

    def set_phase_seconds(self, name: str, dur_s: float) -> None:
        with self._lock:
            self.phases[name] = round(float(dur_s), 3)

    def begin_algo(self, algo: str, *, total_steps: int,
                   n_batches: int = 0, streamed: bool = False,
                   n_stream: int = 0, per_device_bytes: int = 0) -> None:
        """Open one algorithm's training window (resets per-algo
        progress; run-level totals like h2d bytes accumulate across)."""
        with self._lock:
            self.algo = algo
            self.algo_index += 1
            self.algo_started_s = monotonic_s()
            self.total_steps = int(total_steps)
            self.steps_done = 0
            self.examples_done = 0
            self.step_seconds = 0.0
            self.n_batches = int(n_batches)
            self.streamed = bool(streamed)
            self.n_stream = int(n_stream)
            self.solve_impl = None
            self.gather_impl = None
            self.accum_impl = None
            self.attn_impl = None
            self.ssm_impl = None
            self.counters = None
            self.params_per_device_bytes = int(per_device_bytes)
            self.last_loss = None
            self.losses.clear()

    def record_steps(self, n: int, *,
                     losses: Optional[Sequence[float]] = None,
                     examples: int = 0,
                     dur_s: Optional[float] = None) -> None:
        """Report ``n`` retired steps (one drained scan chunk, one
        streamed span, or one ALS chunk with ``n=0`` + edge examples)."""
        from pio_tpu.faults import failpoint

        failpoint("trainwatch.record")
        with self._lock:
            algo = self.algo or "unknown"
            self.steps_done += int(n)
            self.examples_done += int(examples)
            if n:
                _STEPS.inc(int(n), algo=algo)
            if examples:
                _EXAMPLES.inc(int(examples), algo=algo)
            if losses is not None and len(losses) > 0:
                for v in losses:
                    self.losses.append(float(v))
                self.last_loss = float(losses[-1])
                _LOSS.set(self.last_loss, algo=algo)
            if dur_s is not None and n > 0:
                self.step_seconds += float(dur_s)
                _STEP_SECONDS.observe(float(dur_s) / int(n), algo=algo)

    def record_h2d(self, nbytes: int) -> None:
        with self._lock:
            self.h2d_bytes += int(nbytes)

    def set_stream(self, streamed: bool, n_stream: int = 0) -> None:
        """Late stream-mode stamp (ALS decides streaming after its
        algo window opened)."""
        with self._lock:
            self.streamed = bool(streamed)
            self.n_stream = int(n_stream)

    def set_solve_impl(self, impl: Dict[str, str]) -> None:
        """Which implementation solves each side's normal equations (ALS
        decides from entity count, rank and platform: ``_solve_impl``)."""
        with self._lock:
            self.solve_impl = dict(impl)

    def set_gather_impl(self, impl: Dict[str, str]) -> None:
        """Which table layout each half-step gathers its factor rows
        from (ALS decides from platform, rank and the table's bytes:
        ``_gather_impl``)."""
        with self._lock:
            self.gather_impl = dict(impl)

    def set_accum_impl(self, impl: Dict[str, str]) -> None:
        """Whether the fused kernel or XLA sums each half-step's normal
        equations (ALS decides from platform, rank, block width and
        operand size: ``_accum_impl``)."""
        with self._lock:
            self.accum_impl = dict(impl)

    def set_attn_impl(self, impl: Dict[str, str]) -> None:
        """What runs the attention tiles of each kind of attention layer
        (the sequence template decides from platform, dtype and shapes:
        ``ring.attention_impl``)."""
        with self._lock:
            self.attn_impl = dict(impl)

    def set_ssm_impl(self, impl: str) -> None:
        """What runs the chunks of the Mamba-2 mixers (``pallas`` / ``xla``;
        ``none`` without one: ``seq_layers.ssd_impl``)."""
        with self._lock:
            self.ssm_impl = impl

    def set_counters(self, counters: Dict[str, float]) -> None:
        """What the algorithm's program counted over its steps (the
        sequence template: routed and dropped pairs, the expert passes, the
        attention tiles, a sparse layer's selected pairs, key blocks and
        tied rows, a Mamba-2 layer's ``ssm_chunks``,
        ``ssm_head_blocks`` and ``ssm_state_absmax``), known once the
        trained state is read back."""
        with self._lock:
            self.counters = dict(counters)

    def set_overlap(self, ratio: float) -> None:
        with self._lock:
            self.overlap_ratio = float(ratio)

    # -- reads (sidecar / registry side) --------------------------------

    # pio: endpoint=/train.json
    def payload(self) -> dict:
        """The ``/train.json`` body (see docs/observability.md)."""
        from pio_tpu.faults import failpoint

        failpoint("trainwatch.payload")
        with self._lock:
            now = monotonic_s()
            elapsed = now - self.started_s
            algo_elapsed = (
                now - self.algo_started_s
                if self.algo_started_s is not None else None
            )
            progress = (
                self.steps_done / self.total_steps
                if self.total_steps > 0 else None
            )
            eta = None
            if (algo_elapsed and self.steps_done > 0
                    and self.total_steps > self.steps_done):
                rate = self.steps_done / algo_elapsed
                if rate > 0:
                    eta = round(
                        (self.total_steps - self.steps_done) / rate, 1
                    )
            eps = None
            if algo_elapsed and algo_elapsed > 0 and self.examples_done:
                eps = round(self.examples_done / algo_elapsed, 1)
            epoch = (
                round(self.steps_done / self.n_batches, 3)
                if self.n_batches > 0 else None
            )
            return {
                "runId": self.run_id,
                "engineId": self.engine_id,
                "phase": self.phase,
                "algo": self.algo or None,
                "algoIndex": self.algo_index if self.algo_index >= 0
                else None,
                "elapsedSeconds": round(elapsed, 3),
                "step": self.steps_done,
                "totalSteps": self.total_steps,
                "epoch": epoch,
                "progress": round(progress, 4)
                if progress is not None else None,
                "etaSeconds": eta,
                "examples": self.examples_done,
                "examplesPerSecond": eps,
                "loss": self.last_loss,
                "lossWindow": [round(v, 6) for v in self.losses],
                "stream": {
                    "streamed": self.streamed,
                    "chunks": self.n_stream,
                    "h2dBytes": self.h2d_bytes,
                    "overlapRatio": self.overlap_ratio,
                },
                "paramsPerDeviceBytes": self.params_per_device_bytes,
                "counters": self.counters,
                "ssmImpl": self.ssm_impl,
                "phases": dict(self.phases),
            }

    def summary(self) -> dict:
        """Flat step summary for the run-ledger record."""
        with self._lock:
            now = monotonic_s()
            algo_elapsed = (
                now - self.algo_started_s
                if self.algo_started_s is not None else None
            )
            eps = None
            if algo_elapsed and algo_elapsed > 0 and self.examples_done:
                eps = round(self.examples_done / algo_elapsed, 1)
            window_mean = (
                round(sum(self.losses) / len(self.losses), 6)
                if self.losses else None
            )
            return {
                "algo": self.algo or None,
                "steps": self.steps_done,
                "examples": self.examples_done,
                "examples_per_sec": eps,
                "final_loss": round(self.last_loss, 6)
                if self.last_loss is not None else None,
                "loss_window_mean": window_mean,
                "h2d_bytes": self.h2d_bytes,
                "overlap_ratio": self.overlap_ratio,
                "streamed": self.streamed,
                "stream_chunks": self.n_stream,
                "solve_impl": self.solve_impl,
                "gather_impl": self.gather_impl,
                "accum_impl": self.accum_impl,
                "attn_impl": self.attn_impl,
                "ssm_impl": self.ssm_impl,
            }


# ---------------------------------------------------------------------------
# active recorder — module-global (NOT a contextvar: the sidecar HTTP
# thread must see the driver thread's recorder)
# ---------------------------------------------------------------------------

_ACTIVE_LOCK = threading.Lock()
_ACTIVE: Optional[StepRecorder] = None


def activate(rec: StepRecorder) -> None:
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = rec


def deactivate() -> None:
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = None


def active_recorder() -> Optional[StepRecorder]:
    return _ACTIVE


@contextlib.contextmanager
def recording(rec: StepRecorder):
    """Install ``rec`` as the process's active recorder for the block."""
    activate(rec)
    try:
        yield rec
    finally:
        deactivate()


def set_phase(name: str) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_phase(name)


def begin_algo(algo: str, **kw) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.begin_algo(algo, **kw)


def record_steps(n: int, **kw) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.record_steps(n, **kw)


def record_h2d(nbytes: int) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.record_h2d(nbytes)


def set_overlap(ratio: float) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_overlap(ratio)


def set_stream(streamed: bool, n_stream: int = 0) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_stream(streamed, n_stream)


def set_solve_impl(impl: Dict[str, str]) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_solve_impl(impl)


def set_gather_impl(impl: Dict[str, str]) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_gather_impl(impl)


def set_accum_impl(impl: Dict[str, str]) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_accum_impl(impl)


def set_attn_impl(impl: Dict[str, str]) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_attn_impl(impl)


def set_ssm_impl(impl: str) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_ssm_impl(impl)


def set_counters(counters: Dict[str, float]) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.set_counters(counters)


# ---------------------------------------------------------------------------
# direction-aware deltas — the regression core of ``pio runs --diff``
# ---------------------------------------------------------------------------


def delta_rows(prev: dict, cur: dict,
               fields: Sequence[Tuple[str, str]],
               threshold: float) -> Tuple[list, list]:
    """``(rows, regressed_fields)`` comparing two flat records.

    ``fields`` are ``(name, direction)`` pairs, direction ``"up"`` or
    ``"down"`` (the *good* direction). Each row is
    ``(field, prev, cur, delta_str, tag)``; a field moves onto the
    regressed list when it moves AGAINST its direction by more than
    ``threshold`` (fractional). Non-numeric or missing values skip.
    """
    rows: list = []
    regressed: list = []
    for field, direction in fields:
        a, b = prev.get(field), cur.get(field)
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
            continue
        pct = (b - a) / a if a else None
        if pct is None:
            tag = ""
            delta = "n/a"
        else:
            delta = f"{pct * 100:+.1f}%"
            bad = pct < -threshold if direction == "up" else pct > threshold
            good = pct > threshold if direction == "up" else pct < -threshold
            tag = "  REGRESSION" if bad else ("  improved" if good else "")
            if bad:
                regressed.append(field)
        rows.append((field, a, b, delta, tag))
    return rows, regressed


# ---------------------------------------------------------------------------
# run registry — $PIO_TPU_HOME/runs/<engine-id>.jsonl, one flat record
# per run_train (COMPLETED and FAILED both: a crashed run is trend data)
# ---------------------------------------------------------------------------

DEFAULT_RUN_THRESHOLD = 0.05

#: run-ledger trajectory fields and their good direction; ``phase_*``
#: durations join dynamically (direction "down") when diffing
RUN_FIELDS: Tuple[Tuple[str, str], ...] = (
    ("train_seconds", "down"),
    ("examples_per_sec", "up"),
    ("final_loss", "down"),
    ("loss_window_mean", "down"),
    ("overlap_ratio", "up"),
)


def runs_path(engine_id: str) -> str:
    home = knobs.knob_str("PIO_TPU_HOME") or os.path.expanduser("~/.pio_tpu")
    return os.path.join(home, "runs", f"{engine_id}.jsonl")


def run_record(*, run_id: str, engine_id: str, status: str,
               train_seconds: float, phases: Dict[str, float],
               params_hash: str, step_summary: Optional[dict] = None,
               num_devices: Optional[int] = None,
               platform: Optional[str] = None,
               device_kind: Optional[str] = None,
               shard_manifest: Optional[str] = None,
               timestamp: Optional[str] = None,
               error: Optional[str] = None,
               device_scopes: Optional[dict] = None,
               xla: Optional[dict] = None) -> dict:
    """One runs.jsonl row. Flat where it matters: the step summary's
    numeric fields are lifted to the top level so :func:`delta_rows`
    can diff two rows directly. ``device_scopes`` is a profiled run's
    :func:`pio_tpu.obs.profile.reduce_scopes` result (``pio train
    --profile-dir`` on a TPU): lifted as ``scope_<path>_s``,
    ``device_busy_s`` and ``device_idle_pct``. An ALS run's
    ``solve_impl`` (``{"user", "item"}``: ``resident_cg`` / ``xla_cg`` /
    ``cholesky`` / ``lu``), ``gather_impl`` (``{"user", "item"}``:
    ``packed`` / ``plain``) and ``accum_impl`` (``{"user", "item"}``:
    ``fused`` / ``xla``) are lifted beside them, and so is a sequence run's
    ``attn_impl`` (``{"mla"}`` or ``{"full", "window", "sparse"}``:
    ``pallas`` /
    ``xla``; ``index`` beside ``sparse`` where the indexer's kernels ran)
    and ``ssm_impl`` (``pallas`` / ``xla`` / ``none``). ``xla`` is
    the process's
    :func:`pio_tpu.obs.devicewatch.xla_totals` at the run's end: what JAX's
    compile path took of the run, lifted as ``xla_trace_s``,
    ``xla_lower_s``, ``xla_compile_s`` and ``xla_cache_load_s``."""
    if timestamp is None:
        import datetime as _dt

        timestamp = _dt.datetime.now(_dt.timezone.utc).isoformat(
            timespec="seconds"
        )
    rec: Dict[str, Any] = {
        "run_id": run_id,
        "engine_id": engine_id,
        "timestamp": timestamp,
        "status": status,
        "params_hash": params_hash,
        "train_seconds": round(float(train_seconds), 3),
        "num_devices": num_devices,
        "platform": platform,
        "device_kind": device_kind,
        "shard_manifest": shard_manifest,
    }
    for name, dur in (phases or {}).items():
        rec[f"phase_{name}"] = round(float(dur), 3)
    if step_summary:
        rec["step_summary"] = dict(step_summary)
        for key in ("examples_per_sec", "final_loss", "loss_window_mean",
                    "overlap_ratio", "steps", "examples", "solve_impl",
                    "gather_impl", "accum_impl", "attn_impl", "ssm_impl"):
            if step_summary.get(key) is not None:
                rec[key] = step_summary[key]
    if device_scopes:
        for path, sec in device_scopes["scope_s"].items():
            rec[f"scope_{path}_s"] = round(float(sec), 6)
        busy, window = device_scopes["busy_s"], device_scopes["window_s"]
        rec["device_busy_s"] = round(float(busy), 6)
        if window > 0:
            rec["device_idle_pct"] = round(100.0 * (1.0 - busy / window), 3)
    if xla:
        for key in ("trace_s", "lower_s", "compile_s", "cache_load_s"):
            rec[f"xla_{key}"] = round(float(xla[key]), 3)
    if error:
        rec["error"] = error[-500:]
    return rec


def append_run(record: dict, path: Optional[str] = None) -> str:
    """Append one record to the engine's ledger; returns the path."""
    from pio_tpu.faults import failpoint

    failpoint("trainwatch.append")
    if path is None:
        path = runs_path(record.get("engine_id") or "unknown")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")
    return path


def read_runs(engine_id: Optional[str] = None,
              path: Optional[str] = None) -> List[dict]:
    """All parseable ledger rows (malformed lines — torn appends — are
    skipped, never fatal)."""
    if path is None:
        if engine_id is None:
            raise ValueError("read_runs needs engine_id or path")
        path = runs_path(engine_id)
    out: List[dict] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(row, dict):
                    out.append(row)
    except OSError:
        pass
    return out


def run_delta_table(prev: dict, cur: dict,
                    threshold: float = DEFAULT_RUN_THRESHOLD) -> Tuple[list, list]:
    """``(table_lines, regressed_fields)`` for two run-ledger rows —
    the static :data:`RUN_FIELDS` plus every ``phase_*`` duration, the
    compile path's ``xla_*`` seconds and every profiled run's ``scope_*`` /
    ``device_*`` number both rows carry (direction "down": a slower phase,
    compile or scope is a regression)."""
    fields = list(RUN_FIELDS)
    lower_is_better = sorted(
        k for k in cur
        if k.startswith(("phase_", "xla_", "scope_", "device_busy_s",
                         "device_idle_pct")) and k in prev
    )
    fields.extend((k, "down") for k in lower_is_better)
    rows, regressed = delta_rows(prev, cur, fields, threshold)
    width = max([24] + [len(row[0]) for row in rows])  # scope paths are long
    lines = [
        f"run delta vs {prev.get('run_id') or '?'} "
        f"({prev.get('timestamp') or '?'}), threshold "
        f"{threshold * 100:.1f}%:",
        f"  {'field':<{width}} {'prev':>12} {'now':>12} {'delta':>9}",
    ]
    for field, a, b, delta, tag in rows:
        lines.append(f"  {field:<{width}} {a:>12} {b:>12} {delta:>9}{tag}")
    if not rows:
        lines.append("  (no comparable numeric fields)")
    return lines, regressed
