"""JAX profiler hooks: the serving capture (``PIO_TPU_PROFILE=dir``) and
the reduction of a trace to device seconds per named scope.

Training already supports ``--profile-dir`` (a trace of the whole run);
serving needs something narrower — profiling every query forever would
drown the trace and tax the hot path. This hook captures ONE
``jax.profiler`` trace covering the first N device executions after
deploy (N from ``PIO_TPU_PROFILE_EXECUTIONS``, default 8: enough to see
both the bucket-compile execution and warm steady-state dispatches),
then gets out of the way. On a long-lived deploy the interesting window
is rarely the first N executions, so the hook can be re-armed at
runtime: :meth:`DeviceProfileHook.restart` rotates the output into a
numbered subdirectory (``capture-0001`` …) and captures the NEXT N
executions — exposed as ``POST /debug/profile.json?restart=1`` on the
query server. View with tensorboard/xprof.

**Scopes.** The trainers wrap their device math in ``jax.named_scope``
(``als.solve``, ``als.normal_eq/gather`` …; the vocabulary is in
docs/observability.md). :func:`reduce_scopes` turns a trace directory
into device self-seconds per scope path, and :class:`ScopeCapture`
traces a block into a temporary directory and keeps only that
reduction. Their two callers are the existing profiling paths:
``train_als(stats=)`` and ``pio train --profile-dir``.

How a device event gets its scope (looked at by hand in a v5e trace,
PR 26): ``jax.profiler.ProfileData`` exposes an event's own stats only,
and the operation's JAX name sits one level up, in the plane's
event-metadata table, as the stat ``tf_op``
(``jit(finalize)/als.item/als.solve/cg/while/body/closed_call/mul:``).
So this module reads the ``.xplane.pb`` itself: a protobuf wire-format
walk over the five message types of ``xplane.proto`` it needs, with no
dependency.
"""

from __future__ import annotations

import glob
import logging
import os
import re
import shutil
import tempfile
import threading
from contextlib import contextmanager
from typing import Dict, Iterator, Optional, Tuple

from pio_tpu.utils import knobs

log = logging.getLogger("pio_tpu.obs")

ENV_DIR = "PIO_TPU_PROFILE"
ENV_N = "PIO_TPU_PROFILE_EXECUTIONS"


class DeviceProfileHook:
    """Context manager factory wrapped around the device-execute stage.

    Inert (zero overhead beyond one attribute check) unless constructed
    with a directory — the serving services build it from the
    environment via :func:`from_env`.
    """

    def __init__(self, directory: str = "", first_n: int = 8):
        self.directory = directory
        self.first_n = first_n
        self._lock = threading.Lock()
        self._seen = 0
        self._active = False
        self._done = not directory
        self._captures = 0  # completed/aborted capture windows

    @classmethod
    def from_env(cls) -> "DeviceProfileHook":

        directory = knobs.knob_str(ENV_DIR)
        return cls(directory, knobs.knob_int(ENV_N))

    @property
    def enabled(self) -> bool:
        return bool(self.directory) and not self._done

    def to_dict(self) -> dict:
        """Status for ``GET /debug/profile.json``."""
        with self._lock:
            return {
                "configured": bool(self.directory),
                "directory": self.directory,
                "firstN": self.first_n,
                "seen": self._seen,
                "active": self._active,
                "armed": bool(self.directory) and not self._done,
                "captures": self._captures,
            }

    def restart(self, first_n: int = 0) -> dict:
        """Re-arm for the next ``first_n`` (default: the configured N)
        device executions, rotating output into a fresh numbered
        subdirectory so earlier captures survive. Safe while a capture
        is mid-flight — the active trace is stopped first."""
        with self._lock:
            if not self.directory:
                return {"restarted": False,
                        "message": f"{ENV_DIR} not configured"}
            if self._active:
                try:
                    import jax

                    jax.profiler.stop_trace()
                except Exception:
                    log.exception("profile stop during restart failed")
                self._active = False
            if first_n > 0:
                self.first_n = first_n
            self._captures += 1
            base = self.directory.rstrip("/").rsplit("/capture-", 1)[0]
            self.directory = os.path.join(
                base, f"capture-{self._captures:04d}"
            )
            self._seen = 0
            self._done = False
            log.info(
                "profile hook re-armed: next %d executions -> %s",
                self.first_n, self.directory,
            )
        return self.to_dict() | {"restarted": True}

    @contextmanager
    def capture(self):
        """Wrap one device execution; starts the trace on the first
        call, stops it after ``first_n``. Any profiler failure disables
        the hook rather than failing the query."""
        if self._done:
            yield
            return
        with self._lock:
            start = not self._active and self._seen == 0
            if start:
                try:
                    import jax

                    jax.profiler.start_trace(self.directory)
                    self._active = True
                    log.info(
                        "profiling first %d device executions -> %s",
                        self.first_n, self.directory,
                    )
                except Exception:
                    log.exception("PIO_TPU_PROFILE start failed; disabled")
                    self._done = True
        try:
            yield
        finally:
            with self._lock:
                if self._active:
                    self._seen += 1
                    if self._seen >= self.first_n:
                        try:
                            import jax

                            jax.profiler.stop_trace()
                            log.info(
                                "profile trace written to %s", self.directory
                            )
                        except Exception:
                            log.exception("PIO_TPU_PROFILE stop failed")
                        self._active = False
                        self._done = True


# ---------------------------------------------------------------------------
# trace -> device seconds per named scope
# ---------------------------------------------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_OP_NAME_STAT = "tf_op"
#: a scope a program wrote with ``jax.named_scope``: one lowercase atom
_SCOPE_ATOM = re.compile(r"^[a-z][a-z0-9_]*$")
#: atoms JAX's own control flow and transforms put on the name stack
_JAX_ATOMS = frozenset((
    "while", "body", "cond", "closed_call", "scan", "shard_map", "pjit",
    "checkpoint", "remat", "rematted_computation", "core_call",
    "custom_jvp_call",
    "custom_vjp_call", "custom_lin",
))
_JAX_BRANCH = re.compile(r"^branch_\d+_fun$")
#: a scope that a transform wrapped whole: ``transpose(jvp(seq.mtp))``
_TRANSFORMED = re.compile(r"^(?:(?:transpose|jvp|vmap)\()+([^()]*)\)+$")


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``memoryview`` for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(entry) -> dict:
    """The value message of a ``map<int64, Message>`` entry, by field."""
    out: dict = {}
    for num, value in _fields(entry):
        if num == 2:
            for fnum, fvalue in _fields(value):
                out.setdefault(fnum, []).append(fvalue)
    return out


def scope_path(op_name: str, prefix: str) -> Optional[str]:
    """``jit(f)/als.item/als.solve/cg/while/body/closed_call/mul:`` ->
    ``als.item/als.solve/cg``: from the first segment that starts with
    ``prefix`` (a segment a transform wrapped, ``transpose(jvp(seq.mtp))``,
    counts as the scope inside), the segments a program named (those with the prefix, and
    bare lowercase atoms that are not JAX's own), less the last segment,
    which is the primitive. ``None`` when no segment has the prefix."""
    segments = [_TRANSFORMED.sub(r"\1", seg)
                for seg in op_name.rsplit(":", 1)[0].split("/")]
    for first, seg in enumerate(segments):
        if seg.startswith(prefix):
            break
    else:
        return None
    kept = [segments[first]]
    for seg in segments[first + 1:-1]:
        if seg == kept[-1]:
            continue  # transpose(jvp(s))/jvp(s): one scope, named twice
        if seg.startswith(prefix) or (
                _SCOPE_ATOM.match(seg) and seg not in _JAX_ATOMS
                and not _JAX_BRANCH.match(seg)):
            kept.append(seg)
    return "/".join(kept)


def _device_planes(path: str) -> Iterator[Tuple[dict, dict, list, list]]:
    """Per TPU plane of the file: ``{metadata id: JAX op name}`` (the
    ``tf_op`` stat, ``""`` without one), ``{metadata id: event name}``
    (an operation's HLO text, a module's ``jit_f(fingerprint)``), and the
    ``XLA Ops`` and ``XLA Modules`` events as ``(metadata id, start_s,
    duration_s)``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:
            continue
        top: dict = {}
        for pnum, value in _fields(plane):
            top.setdefault(pnum, []).append(value)
        if not _DEVICE_PLANE.match(_text(top.get(2, [b""])[0])):
            continue
        stat_id = None
        for entry in top.get(5, ()):
            meta = _map_entry(entry)
            if _text(meta.get(2, [b""])[0]) == _OP_NAME_STAT:
                stat_id = meta[1][0]
        names: Dict[int, str] = {}
        plain: Dict[int, str] = {}
        for entry in top.get(4, ()):
            meta = _map_entry(entry)
            mid = meta.get(1, [0])[0]
            plain[mid] = _text(meta.get(2, [b""])[0])
            names[mid] = ""
            for stat in meta.get(5, ()):
                fields = dict(_fields(stat))
                if fields.get(1) == stat_id and 5 in fields:
                    names[mid] = _text(fields[5])
        lines = {}
        for line in top.get(3, ()):
            name, t0_ns, events = "", 0, []
            for lnum, value in _fields(line):
                if lnum == 2:
                    name = _text(value)
                elif lnum == 3:
                    t0_ns = value
                elif lnum == 4:
                    events.append(value)
            if name not in (_OPS_LINE, _MODULES_LINE):
                continue
            out = []
            for event in events:
                fields = dict(
                    (n, v) for n, v in _fields(event) if n in (1, 2, 3))
                out.append((fields.get(1, 0),
                            t0_ns * 1e-9 + fields.get(2, 0) * 1e-12,
                            fields.get(3, 0) * 1e-12))
            lines[name] = out
        yield (names, plain, lines.get(_OPS_LINE, []),
               lines.get(_MODULES_LINE, []))


def _self_seconds(events) -> Dict[int, float]:
    """Seconds per key, each event less what its nested events cover: an
    enclosing ``while`` is not counted again for its body."""
    total: Dict[int, float] = {}
    stack: list = []  # (end, key)
    for key, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            total[stack[-1][1]] -= dur
        total[key] = total.get(key, 0.0) + dur
        stack.append((start + dur, key))
    return total


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` a profiler session left under the
    directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_scopes(trace_dir: str, prefix: str = "als.") -> dict:
    """Device seconds per named scope of one profiler trace.

    Returns ``window_s`` (first device operation's start to the last
    one's end), ``busy_s`` (union of the operations' intervals),
    ``scope_s`` (``{scope path: self seconds}``, see :func:`scope_path`),
    ``unscoped_s`` (operations outside every scope: loop counters, the
    copies of loop carries, programs of other code), ``renamed_s`` (the
    part of ``unscoped_s`` in operations whose name is none of JAX's: XLA
    rewrote them and gave them a name of its own with no path, so the
    scope they were written under is lost; ``{that name: seconds}``, as
    ``ragged-dot-none``, the TPU's grouped-matmul kernel) and ``program_s``
    (``{jit name: seconds}`` from the ``XLA Modules`` line). ``scope_s``
    and ``unscoped_s`` sum to the operations' total self time, which is
    ``busy_s`` unless operations overlap. Operations are the ``XLA Ops``
    line only; with several chips every number is the mean over chips.
    Raises when the directory holds no trace or the trace no TPU plane.
    """
    planes = list(_device_planes(find_xplane(trace_dir)))
    if not planes:
        raise ValueError(f"the trace under {trace_dir} has no TPU plane")
    n = len(planes)
    lo, hi, busy, unscoped = float("inf"), float("-inf"), 0.0, 0.0
    scope_s: Dict[str, float] = {}
    renamed_s: Dict[str, float] = {}
    program_s: Dict[str, float] = {}
    for names, plain, ops, modules in planes:
        end = None
        for _key, start, dur in sorted(ops, key=lambda e: e[1]):
            if end is None or start > end:
                busy += dur
                end = start + dur
            elif start + dur > end:
                busy += start + dur - end
                end = start + dur
        for _key, start, dur in ops or modules:
            lo, hi = min(lo, start), max(hi, start + dur)
        for key, sec in _self_seconds(ops).items():
            name = names.get(key, "")
            path = scope_path(name, prefix)
            if path is None:
                unscoped += sec / n
                own = name.rstrip(":")
                if own and "/" not in own:
                    renamed_s[own] = renamed_s.get(own, 0.0) + sec / n
            else:
                scope_s[path] = scope_s.get(path, 0.0) + sec / n
        for key, _start, dur in modules:
            name = plain.get(key, "").split("(", 1)[0]
            program_s[name] = program_s.get(name, 0.0) + dur / n
    return {
        "window_s": max(0.0, hi - lo),
        "busy_s": busy / n,
        "scope_s": scope_s,
        "unscoped_s": unscoped,
        "renamed_s": renamed_s,
        "program_s": program_s,
    }


def device_stats(seen: Optional[dict]) -> dict:
    """A :class:`ScopeCapture` result as the JSON-plain ``stats`` entries
    the trainers report (``train_als``, ``train_seqrec``); ``{}`` when
    nothing was captured."""
    if seen is None:
        return {}
    stats = {
        "device_scope_s": dict(seen["scope_s"]),
        "device_unscoped_s": seen["unscoped_s"],
        "device_busy_s": seen["busy_s"],
        "device_program_s": dict(seen["program_s"]),
    }
    if seen.get("renamed_s"):
        stats["device_renamed_s"] = dict(seen["renamed_s"])
    return stats


class ScopeCapture:
    """``with ScopeCapture() as cap: ...`` traces the block into a
    temporary directory, reduces it with :func:`reduce_scopes` into
    ``cap.result`` and removes the directory. For profiling calls only:
    starting and stopping a profiler session takes seconds. Does
    nothing, and leaves ``result`` ``None``, when the backend is not a
    TPU (a CPU trace has no device plane) or a profiler session is
    already running (that session's owner reduces its own trace). A
    capture that fails is logged and never fails the block."""

    def __init__(self, prefix: str = "als."):
        self.prefix = prefix
        self.result: Optional[dict] = None
        self._dir: Optional[str] = None

    def __enter__(self) -> "ScopeCapture":
        import jax

        if jax.default_backend() != "tpu":
            return self
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0  # device planes are all it reads
        self._dir = tempfile.mkdtemp(prefix="pio_tpu_scopes_")
        try:
            jax.profiler.start_trace(self._dir, profiler_options=options)
        except RuntimeError as exc:  # a session is already running
            log.info("scope capture skipped: %s", exc)
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._dir is None:
            return
        import jax

        try:
            jax.profiler.stop_trace()
            if exc_type is None:
                self.result = reduce_scopes(self._dir, self.prefix)
        except Exception:
            log.exception("scope capture failed; no device scopes")
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
