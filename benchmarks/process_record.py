"""What the ``setup.*`` readers share: the program's own record of its
process, ``stats["process"]`` of a call made with ``stats={}``
(``pio_tpu/obs/tracing.py`` ``ProcessTimeline.record``).

``marks`` are seconds since the OS started the process (``process_start``,
``pio_tpu_imported``, ``context_built``, ``first_call_enter``,
``first_call_exit``); ``calls`` the first four train calls of the process and
the newest as ``[call, start, end]`` (the harness's warm call is 1, a traced
run's profiled call 2); ``first_call.xla`` what JAX's compile path took inside
the first call (``trace_s``, ``lower_s``, ``compile_s``, ``cache_load_s``).
A program that keeps no such record, or a run without a chip, leaves every
reader with ``None``: what a CPU took to start is no number of this benchmark's.
"""


def record(reading):
    if not reading.get("trace"):
        return None
    rec = (reading.get("stats") or {}).get("process")
    return rec if rec and rec.get("first_call") else None


def between(reading, mark_a, mark_b):
    """Seconds from one mark to another, ``None`` without both."""
    rec = record(reading)
    marks = rec["marks"] if rec else {}
    if mark_a not in marks or mark_b not in marks:
        return None
    return marks[mark_b] - marks[mark_a]


def first_call_xla(reading, *keys):
    """The sum of the compile path's ``keys`` over the first call."""
    rec = record(reading)
    xla = rec["first_call"].get("xla") if rec else None
    return None if not xla else sum(xla[k] for k in keys)


def first_call_excess(reading):
    """The first call less the second: what a warm call costs beyond a call."""
    first = between(reading, "first_call_enter", "first_call_exit")
    if first is None:
        return None
    second = [end - start for call, start, end in record(reading)["calls"]
              if call == 2]
    return first - second[0] if second else None
