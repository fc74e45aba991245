"""What the per-layer readers of the program's own device scopes share.

``train_als(stats=)`` on a TPU traces its device phase and reduces it to
``stats["device_scope_s"]``: ``{scope path: device self-seconds}``, a path
being the program's ``jax.named_scope`` segments from the half-step down
(``als.item/als.normal_eq/gather``, ``als.user/als.solve/cg``, ``als.pack``).
A program without the scopes, or a run without a chip, leaves the key out,
and every reader then returns ``None``.
"""


def scope_seconds(reading, *segments):
    """Seconds of every scope path that holds all ``segments`` in order
    and adjacent (``("als.normal_eq", "gather")``), or a single one
    anywhere; ``None`` when the program reported no scopes."""
    scopes = reading["stats"].get("device_scope_s")
    if not scopes:
        return None
    want = "/" + "/".join(segments) + "/"
    return sum(s for path, s in scopes.items() if want in f"/{path}/")


def xla_counts(reading):
    """``stats["xla"]`` of a run on a chip: the process's real compiles and
    cache loads (``pio_tpu.obs.devicewatch.xla_totals``) with ``in_call``,
    the same over the stats call. ``None`` in a rehearsal: what the CPU's
    compiler took is no number of this benchmark's."""
    if not reading.get("trace"):
        return None
    return reading["stats"].get("xla")
