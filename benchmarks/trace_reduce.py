"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer readers use. Reads the file with ``jax.profiler.ProfileData`` and
nothing else.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Modules`` has one event per executed
program (``jit_accum(<fingerprint>)``) and whose line ``XLA Ops`` has the
operations, nested (a ``while`` spans its body); and a plane ``/host:CPU``
with one line per thread that holds the ``TraceAnnotation`` spans. Device
and host events share one clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
PYTHON_LINE = "python"
MIN_GAP_S = 20e-6  # shorter gaps are the device's own launch latency
NAME_CHARS = 96  # an op's name is its whole HLO line: keep the head


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]}`` with events as ``(name, start_s, duration_s)``; host events
    carry their thread's line name first."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events
                    ]
            devices[plane.name] = {
                "ops": lines.get(OPS_LINE, []),
                "modules": lines.get(MODULES_LINE, []),
            }
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (line.name, e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                )
    return {"devices": devices, "host": host}


def union(intervals, lo: float, hi: float):
    """Merged ``[(start, end)]`` of the intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events) -> dict:
    """Seconds per operation name, each event less what its nested events
    cover (an enclosing ``while`` is not counted again for its body)."""
    total = {}
    stack = []  # (end, name)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            total[stack[-1][1]] -= dur
        total[name] = total.get(name, 0.0) + dur
        stack.append((start + dur, name))
    return total


def module_name(event_name: str) -> str:
    """``jit_accum(1234567)`` -> ``jit_accum``."""
    return event_name.split("(", 1)[0]


def reduce(path: str, annotation: str) -> dict:
    """The traced window is the host span named ``annotation`` (the harness
    wraps exactly one call in it). Returns ``window_s``, ``busy_s`` (union
    of device-op intervals inside the window, averaged over chips),
    ``module_s`` (seconds per program name, summed over chips and divided
    by their number), and the two ``breakdown`` lists: device operations by
    self time, and idle gaps summed by where they fall and what the host was
    doing in them."""
    trace = load(path)
    spans = [(s, s + d) for _line, name, s, d in trace["host"] if name == annotation]
    if not spans:
        raise ValueError(f"the trace has no host span named {annotation!r}")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    n = len(devices)

    busy, module_s, op_s, gaps = 0.0, {}, {}, []
    for plane in devices.values():
        ops = plane["ops"] or plane["modules"]
        merged = union(((s, s + d) for _n, s, d in ops), lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, s, d in plane["modules"]:
            if lo <= s + d and s <= hi:
                key = module_name(name)
                module_s[key] = module_s.get(key, 0.0) + d / n
        inside = [(nm, s, d) for nm, s, d in plane["ops"] if lo <= s and s + d <= hi]
        for name, sec in self_times(inside).items():
            op_s[name] = op_s.get(name, 0.0) + sec / n
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])

    def host_doing(g0, g1):
        """The host span that covers most of the gap: the Python thread's
        (``PjitFunction(..)``, ``DevicePut``, ..) if any does, else any."""
        best = {True: ("no host span", 0.0), False: ("no host span", 0.0)}
        for line, name, s, d in trace["host"]:
            if name == annotation:
                continue
            ov = min(g1, s + d) - max(g0, s)
            if ov > best[line == PYTHON_LINE][1]:
                best[line == PYTHON_LINE] = (name, ov)
        return best[True][0] if best[True][1] > 0 else best[False][0]

    first_op = min((s for p in devices.values() for _n, s, _d in (p["ops"] or p["modules"])
                    if s >= lo), default=hi)
    by_name = {}
    for g0, g1 in gaps:
        if g1 - g0 < MIN_GAP_S:
            continue
        where = ("before the first device op" if g1 <= first_op + 1e-9
                 else "after the last device op" if g1 >= hi - 1e-9
                 else "inside the call")
        name = f"{where}: {host_doing(g0, g1)}"
        by_name[name] = by_name.get(name, 0.0) + (g1 - g0) / n
    idle_gaps = [[k[:NAME_CHARS], v] for k, v in
                 sorted(by_name.items(), key=lambda kv: -kv[1])[:10]]
    device_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": hi - lo,
        "busy_s": busy / n,
        "module_s": module_s,
        "device_ops": [[k[:NAME_CHARS], v] for k, v in device_ops],
        "idle_gaps": idle_gaps,
        "n_device_events": sum(len(p["ops"]) for p in devices.values()),
    }
