"""Device seconds of one call's grouped expert matmuls themselves, whichever
kernel ran them (``SeqRecParams.expert_matmul``). The Pallas kernel keeps its
JAX name and stands under ``seq.moe/experts/gmm``. ``jax.lax.ragged_dot`` does
not: the TPU compiler rewrites it into a custom call named ``ragged-dot-none``
and drops the ``seq.moe/experts`` scope it was written under, so that kernel's
seconds stand among the unscoped ones, and the program reports what XLA
renamed by the name it gave (``device_renamed_s``, ``pio_tpu/obs/profile.py``).
``None`` where the program reports neither."""

from seq_scopes import seconds

KERNEL = "ragged-dot"


def renamed(reading):
    """The seconds of ``ragged-dot-*``, which no scope holds; ``None``
    without any."""
    found = [s for name, s in (
        reading["stats"].get("device_renamed_s") or {}).items()
        if name.startswith(KERNEL)]
    return sum(found) if found else None


def read(reading):
    inside = seconds(reading, "seq.moe", "experts", "gmm")
    outside = renamed(reading)
    if not inside and outside is None:
        return None
    return (inside or 0.0) + (outside or 0.0)
