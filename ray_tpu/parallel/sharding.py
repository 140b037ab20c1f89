"""Logical-axis sharding rules (the Megatron/t5x-style rule table).

Model code annotates arrays with *logical* dimension names ("batch", "seq",
"embed", "mlp", "heads", "vocab", "expert", "layers"); a ``ShardingRules``
table maps each logical name to zero or more mesh axes.  Changing the
parallelism strategy = changing the table, not the model.  XLA then inserts
the allreduce/allgather/reducescatter collectives implied by the placements
(scaling-book recipe; no NCCL-style explicit communication as in the
reference's DDP path, reference: python/ray/train/torch/config.py:95).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

from .mesh import (AXIS_DATA, AXIS_EXPERT, AXIS_FSDP, AXIS_PIPELINE,
                   AXIS_SEQ, AXIS_TENSOR)

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass
class ShardingRules:
    rules: Dict[str, MeshAxes] = field(default_factory=dict)

    def axes_for(self, logical: str) -> MeshAxes:
        return self.rules.get(logical)

    def replace(self, **updates: MeshAxes) -> "ShardingRules":
        merged = dict(self.rules)
        merged.update(updates)
        return ShardingRules(merged)


def default_rules() -> ShardingRules:
    """FSDP+TP+SP+EP layout for transformer LMs.

    - batch over (dp, fsdp): every data shard trains a distinct slice
    - embed dim sharded over tp for attention/MLP projections (Megatron)
    - the *other* matmul dim of each weight sharded over fsdp (ZeRO-3-style
      parameter sharding).  XLA does not gather such a weight for its layer:
      it turns every dot that meets it into a ring of partial dots, the
      shards going round by collective-permute (a windowed einsum).  A
      chunked loss's head is gathered once a step (``parallel/fsdp.on_rows``)
    - sequence over sp (ring/Ulysses context parallelism in ops/)
    - experts over ep
    """
    return ShardingRules({
        "batch": (AXIS_DATA, AXIS_FSDP),
        "seq": AXIS_SEQ,
        "embed": AXIS_FSDP,
        "heads": AXIS_TENSOR,
        "kv_heads": AXIS_TENSOR,
        "head_dim": None,
        "mlp": AXIS_TENSOR,
        "vocab": AXIS_TENSOR,
        "expert": AXIS_EXPERT,
        "layers": None,
        "stage": AXIS_PIPELINE,
        "norm": None,
    })


def logical_to_pspec(logical_axes: Sequence[Optional[str]],
                     rules: ShardingRules):
    """('batch','seq','embed') -> PartitionSpec((dp,fsdp), sp, fsdp)."""
    from jax.sharding import PartitionSpec
    entries = []
    used: set = set()
    for name in logical_axes:
        axes = rules.axes_for(name) if name is not None else None
        if axes is None:
            entries.append(None)
            continue
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        # A mesh axis may shard at most one dim of a given array.
        axes_t = tuple(a for a in axes_t if a not in used)
        used.update(axes_t)
        if not axes_t:
            entries.append(None)
        elif len(axes_t) == 1:
            entries.append(axes_t[0])
        else:
            entries.append(axes_t)
    return PartitionSpec(*entries)


def named_sharding(mesh, logical_axes: Sequence[Optional[str]],
                   rules: Optional[ShardingRules] = None):
    from jax.sharding import NamedSharding
    rules = rules or default_rules()
    return NamedSharding(mesh, logical_to_pspec(logical_axes, rules))


def shard_pytree(tree, logical_tree, mesh,
                 rules: Optional[ShardingRules] = None):
    """Device_put a pytree according to a parallel pytree of logical axes."""
    import jax
    rules = rules or default_rules()

    def place(x, logical):
        return jax.device_put(x, named_sharding(mesh, logical, rules))
    return jax.tree.map(place, tree, logical_tree,
                        is_leaf=lambda x: x is None)


def pspec_pytree(logical_tree, rules: Optional[ShardingRules] = None):
    """Parallel pytree of PartitionSpecs from a pytree of logical axes."""
    import jax
    rules = rules or default_rules()
    return jax.tree.map(
        lambda logical: logical_to_pspec(logical, rules), logical_tree,
        is_leaf=lambda x: isinstance(x, (tuple, list)) and all(
            isinstance(e, (str, type(None))) for e in x))


def constrain(x, logical_axes: Sequence[Optional[str]],
              rules: Optional[ShardingRules] = None):
    """with_sharding_constraint by logical names (inside jit)."""
    import jax
    rules = rules or default_rules()
    return jax.lax.with_sharding_constraint(
        x, logical_to_pspec(logical_axes, rules))
