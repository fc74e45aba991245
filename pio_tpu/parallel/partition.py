"""Partition-rule registry: parameter-path regexes → ``PartitionSpec``s.

Templates used to hand-write one sharding dict per model
(``two_tower._tower_specs``, ``seqrec.param_specs``); every new tensor
meant another edit in bespoke code, and optimizer state had to be
threaded separately. This module replaces that with the rule pattern
from the exemplars (SNIPPETS.md [3]): an ordered list of
``(path_regex, PartitionSpec)`` pairs matched first-hit against the
``/``-joined tree path of every leaf. Optimizer-state inheritance is
free — ``re.search`` finds ``blocks/wq`` inside ``0/mu/blocks/wq``, and
the scalar guard keeps step counters replicated.

Rules are registered per template (``als`` / ``two_tower`` / ``seqrec``)
so training, persistence and serving all shard from one source of truth:
:meth:`ComputeContext.shard_params` applies them at train/deploy time,
the shard store records them in the shard manifest, and the query server
re-applies them when placing a model onto a serving mesh.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from pio_tpu.utils import knobs

#: Per-device parameter budget (bytes); 0 = unlimited. The OOM guard the
#: multichip proof leans on: set it below total model size and only a
#: sharded placement fits.
DEVICE_BUDGET_ENV = "PIO_TPU_DEVICE_BUDGET_BYTES"


class DeviceBudgetExceeded(RuntimeError):
    """A placement would exceed ``PIO_TPU_DEVICE_BUDGET_BYTES`` per chip."""


def tree_path_name(path: Sequence[Any]) -> str:
    """``/``-joined human name for a jax ``tree_flatten_with_path`` key path.

    ``DictKey('emb')`` → ``emb``, ``SequenceKey(0)`` → ``0``,
    ``GetAttrKey('mu')`` → ``mu``; unknown key types fall back to ``str``.
    """
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:  # pragma: no cover - future key types
            parts.append(str(k).strip("[].'\""))
    return "/".join(parts)


def _is_scalar_leaf(leaf: Any) -> bool:
    return np.ndim(leaf) == 0


def match_partition_rules(
    rules: Iterable[Tuple[str, Any]],
    pytree: Any,
    *,
    on_unmatched: str = "replicate",
):
    """Spec tree for ``pytree``: first rule whose regex ``search``es the
    leaf's ``/``-joined path wins; scalars are always replicated.

    ``on_unmatched``: ``"replicate"`` (default — unmatched leaves get
    ``PartitionSpec()``) or ``"error"`` (raise ``ValueError`` naming the
    leaf, for templates that want every tensor accounted for).
    """
    import jax

    from jax.sharding import PartitionSpec as P

    rules = list(rules)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(pytree)
    specs = []
    for path, leaf in leaves:
        name = tree_path_name(path)
        if _is_scalar_leaf(leaf):
            specs.append(P())
            continue
        for pat, spec in rules:
            if re.search(pat, name):
                specs.append(spec if isinstance(spec, P) else P(*spec))
                break
        else:
            if on_unmatched == "error":
                raise ValueError(
                    f"no partition rule matches leaf {name!r} "
                    f"(shape {np.shape(leaf)})"
                )
            specs.append(P())
    return jax.tree_util.tree_unflatten(treedef, specs)


def is_partition_spec(x: Any) -> bool:
    from jax.sharding import PartitionSpec as P

    return isinstance(x, P)


def spec_for_mesh(mesh, spec):
    """Project a spec onto ``mesh``: axis names the mesh doesn't carry
    become ``None`` (replicated on that dim).

    Lets one rule set serve both the full training mesh
    (``data×pipe×seq×model``) and a 1-D serving mesh (``("data",)``)
    without per-consumer rule forks.
    """
    from jax.sharding import PartitionSpec as P

    axes = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in axes else None
        # tuple of axis names on one dim
        kept = tuple(a for a in entry if a in axes)
        return kept if kept else None

    return P(*[keep(e) for e in spec])


def make_shard_and_gather_fns(mesh, specs):
    """Per-leaf ``(shard_fns, gather_fns)`` trees for a spec tree.

    ``shard_fns[leaf](x)`` places ``x`` on ``mesh`` under the leaf's
    spec (projected onto the mesh's axes); ``gather_fns[leaf](x)`` pulls
    it back to one host numpy array regardless of how it was sharded.
    """
    import jax

    from jax.sharding import NamedSharding

    def mk_shard(spec):
        sharding = NamedSharding(mesh, spec_for_mesh(mesh, spec))

        def shard_fn(x):
            return jax.device_put(x, sharding)

        return shard_fn

    def mk_gather(spec):
        def gather_fn(x):
            return np.asarray(jax.device_get(x))

        return gather_fn

    shard_fns = jax.tree_util.tree_map(
        mk_shard, specs, is_leaf=is_partition_spec
    )
    gather_fns = jax.tree_util.tree_map(
        mk_gather, specs, is_leaf=is_partition_spec
    )
    return shard_fns, gather_fns


# -- per-template rule registry ---------------------------------------------

_TEMPLATE_RULES: Dict[str, Callable[[], List[Tuple[str, Any]]]] = {}


def register_partition_rules(
    template: str, rules: Callable[[], List[Tuple[str, Any]]]
) -> None:
    """Register (or override) the rule list for a template name.

    ``rules`` is a zero-arg callable so ``PartitionSpec`` construction —
    a jax import — stays lazy until a mesh consumer needs it.
    """
    _TEMPLATE_RULES[template] = rules


def rules_for(template: str) -> List[Tuple[str, Any]]:
    """The registered rule list for ``template`` (raises KeyError)."""
    try:
        factory = _TEMPLATE_RULES[template]
    except KeyError:
        raise KeyError(
            f"no partition rules registered for template {template!r}; "
            f"known: {sorted(_TEMPLATE_RULES)}"
        ) from None
    return list(factory())


def _als_rules():
    from jax.sharding import PartitionSpec as P

    # factor matrices row-sharded over the entity (data) axis; indexes and
    # everything else replicated
    return [
        (r"(user_factors|item_factors)$", P("data", None)),
    ]


def _two_tower_rules():
    from jax.sharding import PartitionSpec as P

    # vocab-parallel embedding (ep), Megatron column/row MLP splits (tp);
    # the trained serving vectors row-shard over entities like ALS factors
    return [
        (r"(user_vectors|item_vectors)$", P("data", None)),
        (r"emb$", P("model", None)),
        (r"w1$", P(None, "model")),
        (r"b1$", P("model")),
        (r"w2$", P("model", None)),
        (r"b2$", P()),
    ]


def _seqrec_rules():
    from jax.sharding import PartitionSpec as P

    # layer-stacked blocks ride pipe on the leading (layer) dim; heads and
    # ffn hidden are tp column/row splits; embedding is vocab-sharded
    return [
        (r"blocks/(wq|wk|wv|w1)$", P("pipe", None, "model")),
        (r"blocks/(wo|w2)$", P("pipe", "model", None)),
        (r"blocks/b1$", P("pipe", "model")),
        # the mla/moe block (models/seq_layers.py): routed experts shard
        # by expert over model ([layer, expert, in, out]); attention, the
        # router, the shared expert, the dense layers and the MTP module's
        # own leaves are held whole
        (r"(blocks|mtp|window|full|sparse|moe)/e_(gate|up|down)$",
         P(None, "model")),
        (r"blocks/(attn_norm|q_a|q_norm|q_b|kv_a|kv_norm|kv_b|o_proj|ffn_norm|"
         r"router_w|router_b|s_gate|s_up|s_down)$", P()),
        # the gqa/moe block's stacks by kind of layer: as above, experts by
        # expert and the rest whole (its heads are not divided over model);
        # the stacks of layers that are one mixer alone likewise. A tied
        # table is ``emb`` alone: the same row shard serves lookup and logits
        (r"(dense|mtp|window|full|sparse|mamba|moe|attn|mlp)/", P()),
        (r"blocks/", P("pipe", None)),
        (r"(emb|head)$", P("model", None)),
        (r"(pos|lnf_g|lnf_b)$", P()),
    ]


register_partition_rules("als", _als_rules)
register_partition_rules("two_tower", _two_tower_rules)
register_partition_rules("seqrec", _seqrec_rules)


# -- placement budget --------------------------------------------------------


def device_budget_bytes() -> int:
    """Per-device parameter budget from the env; 0 = unlimited."""
    return knobs.knob_int(DEVICE_BUDGET_ENV)


def tree_nbytes(tree: Any) -> int:
    """Total bytes across array leaves (host or device)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None and hasattr(leaf, "size"):
            nbytes = leaf.size * np.dtype(
                getattr(leaf, "dtype", np.float32)
            ).itemsize
        total += int(nbytes or 0)
    return total


def assert_device_budget(
    nbytes: int, n_devices: int, what: str = "placement"
) -> None:
    """Raise :class:`DeviceBudgetExceeded` when ``nbytes`` spread over
    ``n_devices`` chips exceeds the per-device budget (no-op when the
    budget env is unset)."""
    budget = device_budget_bytes()
    if budget <= 0:
        return
    per_device = -(-nbytes // max(1, n_devices))
    if per_device > budget:
        raise DeviceBudgetExceeded(
            f"{what}: {per_device} B/device over {n_devices} device(s) "
            f"exceeds {DEVICE_BUDGET_ENV}={budget}"
        )


def per_device_nbytes(mesh, params: Any, specs: Any) -> int:
    """Bytes each device holds after placing ``params`` under ``specs``:
    sharded dims divide a leaf's footprint by the product of its mesh
    axis sizes; replicated leaves cost their full size per chip."""
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    spec_leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_partition_spec)
    total = 0
    for leaf, spec in zip(leaves, spec_leaves):
        nbytes = tree_nbytes(leaf)
        factor = 1
        for entry in spec_for_mesh(mesh, spec):
            if entry is None:
                continue
            for axis in (entry,) if isinstance(entry, str) else entry:
                factor *= int(mesh.shape[axis])
        total += -(-nbytes // max(1, factor))
    return total


def shard_params(
    mesh,
    params: Any,
    rules: Iterable[Tuple[str, Any]],
    *,
    on_unmatched: str = "replicate",
    enforce_budget: bool = True,
) -> Tuple[Any, Any]:
    """Match ``rules`` over ``params`` and place every leaf on ``mesh``.

    Returns ``(sharded_params, specs)``. With ``mesh=None`` the params
    pass through as single-device jnp arrays (specs still computed, all
    projected onto nothing — callers can ignore them).
    """
    import jax

    specs = match_partition_rules(rules, params, on_unmatched=on_unmatched)
    if mesh is None:
        import jax.numpy as jnp

        return jax.tree_util.tree_map(jnp.asarray, params), specs
    per_dev = per_device_nbytes(mesh, params, specs)
    if enforce_budget:
        assert_device_budget(per_dev, 1, "shard_params")
    shard_fns, _ = make_shard_and_gather_fns(mesh, specs)
    sharded = jax.tree_util.tree_map(lambda f, x: f(x), shard_fns, params)
    # device ledger (ISSUE 17): latest sharded training placement's
    # per-chip footprint, replaced on each call (the params it books
    # are superseded wholesale by the next placement)
    from pio_tpu.obs import devicewatch

    devicewatch.ledger_place(
        "shard", "shard_params", per_dev,
        name="shard_params per-device",
    )
    return sharded, specs
