"""What the per-layer readers of the sequence trainer's device scopes share.

``train_seqrec(stats=)`` on a TPU traces its steps and reduces them to
``stats["device_scope_s"]``: ``{scope path: device self-seconds}``, a path
being the program's ``jax.named_scope`` segments (``seq.mla/attn``,
``seq.moe/experts``, ``seq.mtp/seq.mla/proj``, ``seq.opt``); forward and
backward operations of a scope share its path. The MTP module reuses the
layers' scopes under ``seq.mtp``. A program without the scopes, or a run
without a chip, leaves the key out, and every reader then returns ``None``.
"""

MTP = "seq.mtp"


def seconds(reading, *segments, mtp=None):
    """Seconds of every scope path that holds ``segments`` in order and
    adjacent. ``mtp=False`` leaves the MTP module's paths out, ``True``
    takes only those; ``None`` when the program reported no scopes."""
    scopes = reading["stats"].get("device_scope_s")
    if not scopes:
        return None
    want = "/" + "/".join(segments) + "/"
    total = 0.0
    for path, s in scopes.items():
        inside = f"/{MTP}/" in f"/{path}/"
        if want in f"/{path}/" and (mtp is None or mtp == inside):
            total += s
    return total


def roofline_pct(reading, least_key, *segments):
    """``100 * least seconds / scope seconds`` of a kernel's scope, the MTP
    module's share included (the cost counts its layer too)."""
    took = seconds(reading, *segments)
    if not took or least_key not in reading:
        return None
    return 100.0 * reading[least_key]["seconds"] / took
