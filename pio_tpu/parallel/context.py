"""ComputeContext — the TPU-native replacement for Spark's SparkContext.

Where the reference threads a ``SparkContext`` through every P-component
(``PDataSource.readTraining(sc)`` etc., ``core/.../controller/*.scala``,
UNVERIFIED paths; see SURVEY.md), this framework threads a
:class:`ComputeContext`: a ``jax.sharding.Mesh`` over the available devices
plus RNG and placement helpers. Components use it to shard host data onto the
mesh and to run pjit-compiled programs; XLA collectives over ICI/DCN do what
Spark shuffles and treeAggregate did.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger("pio_tpu.context")


def default_mesh(axis_names: Tuple[str, ...] = ("data",), devices=None):
    """Build a mesh over all devices (1-D ``data`` axis by default).

    Multi-axis: pass e.g. ``("data", "model")`` and a device array shaped
    accordingly, or let this helper fold all devices into the first axis.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devs = np.asarray(devices)
    if len(axis_names) == 1:
        devs = devs.reshape(-1)
    elif devs.ndim != len(axis_names):
        # fold everything into the leading axis, trailing axes size 1
        devs = devs.reshape((-1,) + (1,) * (len(axis_names) - 1))
    return Mesh(devs, axis_names)


@dataclasses.dataclass
class ComputeContext:
    """Carries the device mesh + RNG through DASE components.

    Attributes:
        mesh: the device mesh; None means "single default device".
        seed: base RNG seed for this run.
        batch_axis: mesh axis name training data shards over.
        model_axis: mesh axis name model tensors may shard over (tensor
            parallelism); usually size 1 in v1 configs but reserved so
            two-tower/MLP engines can scale (SURVEY.md §2.6).
    """

    mesh: Optional[object] = None
    seed: int = 0
    batch_axis: str = "data"
    model_axis: str = "model"
    #: checkpointing (WorkflowParams.checkpoint_every > 0): run_train sets
    #: ``checkpoint_base`` (a directory) + ``checkpoint_every``;
    #: Engine.train derives a per-algorithm CheckpointManager into
    #: ``checkpoint`` so concurrent algorithms never share snapshot state
    checkpoint: Optional[object] = None
    checkpoint_base: Optional[str] = None
    checkpoint_every: int = 0

    def __post_init__(self):
        # every entry point builds a context before its first program:
        # the one place that sees all of a process's compile path, and
        # the process timeline's ``context_built`` (JAX is imported and
        # the devices have been asked for by now)
        from pio_tpu.obs import devicewatch, tracing

        devicewatch.watch_xla_compiles()
        tracing.PROCESS.mark("context_built")

    @staticmethod
    def create(seed: int = 0, axis_names: Tuple[str, ...] = ("data",)):
        """Mesh over every local device. Logs where the process landed:
        with ``JAX_PLATFORMS`` unset, a chip held by another process
        makes JAX fall back to CPU with only a warning of its own."""
        ctx = ComputeContext(mesh=default_mesh(axis_names), seed=seed)
        log.info(
            "compute context: platform=%s device_kind=%s devices=%d",
            ctx.platform, ctx.device_kind, ctx.num_devices,
        )
        return ctx

    @staticmethod
    def local(seed: int = 0):
        """No mesh — single-device jit path (reference L* components)."""
        return ComputeContext(mesh=None, seed=seed)

    # -- helpers ------------------------------------------------------------
    def rng(self):
        import jax

        return jax.random.PRNGKey(self.seed)

    @property
    def num_devices(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod(list(self.mesh.shape.values())))

    def _first_device(self):
        if self.mesh is not None:
            return self.mesh.devices.flat[0]
        import jax

        return jax.devices()[0]

    @property
    def platform(self) -> str:
        """Backend the context's programs run on (``tpu``, ``cpu``...)."""
        return self._first_device().platform

    @property
    def device_kind(self) -> str:
        return self._first_device().device_kind

    def batch_sharding(self):
        """NamedSharding that shards dim 0 over the batch axis."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, PartitionSpec(self.batch_axis))

    def replicated_sharding(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, PartitionSpec())

    def shard_batch(self, arrays: Dict[str, np.ndarray], pad_value=0):
        """Pad dim 0 to a mesh multiple and place sharded. Adds ``mask``.

        The host→device materialization step: the analog of the reference
        handing an RDD to executors, minus the shuffle.
        """
        import jax
        import jax.numpy as jnp

        n = len(next(iter(arrays.values())))
        for k, v in arrays.items():
            if len(v) != n:
                raise ValueError(
                    f"all arrays must share dim-0 length; {k!r} has "
                    f"{len(v)} != {n}"
                )
        if self.mesh is None:
            out = {k: jnp.asarray(v) for k, v in arrays.items()}
            out["mask"] = jnp.ones((n,), dtype=jnp.float32)
            return out
        shards = self.mesh.shape[self.batch_axis]
        padded = -(-n // shards) * shards
        sharding = self.batch_sharding()
        out = {}
        for k, v in arrays.items():
            v = np.asarray(v)
            if len(v) != n:
                raise ValueError("all arrays must share dim-0 length")
            pv = np.full((padded,) + v.shape[1:], pad_value, dtype=v.dtype)
            pv[:n] = v
            out[k] = jax.device_put(pv, sharding)
        mask = np.zeros((padded,), dtype=np.float32)
        mask[:n] = 1.0
        out["mask"] = jax.device_put(mask, sharding)
        return out

    def shard_params(self, params, rules=None, template=None,
                     on_unmatched="replicate"):
        """Place a parameter pytree on the mesh under partition rules.

        ``rules`` is an ordered ``(path_regex, PartitionSpec)`` list;
        pass ``template`` instead to use the registered rule set
        (``"als"`` / ``"two_tower"`` / ``"seqrec"``). Returns
        ``(sharded_params, specs)``; with no mesh the params come back
        as single-device jnp arrays.
        """
        from pio_tpu.parallel import partition as _partition

        if rules is None:
            rules = _partition.rules_for(template) if template else []
        return _partition.shard_params(
            self.mesh, params, rules, on_unmatched=on_unmatched
        )

    def replicate(self, array):
        """Fully replicate an array over the mesh (broadcast analog)."""
        import jax

        if self.mesh is None:
            import jax.numpy as jnp

            return jnp.asarray(array)
        return jax.device_put(np.asarray(array), self.replicated_sharding())
