"""A weight that a loop reads again and again, on a mesh whose ``fsdp`` axis
shards it.

``default_rules`` shards every weight's embed dimension over ``fsdp``.  Left
to itself the SPMD partitioner gathers a chunked loss's head inside the chunk
loop, once a chunk forward, recomputed and backward, and reduce-scatters its
gradient once a chunk.  On the chip (Mistral-7B, ``{fsdp: 4}``, one row of
4,096 a chip, 8 chunks; PERF.md, PR 37) those 16 gathers and 8
reduce-scatters a step, with nothing running beside them, were 5 % of the
step.  ``on_rows`` says what ZeRO-3 means there instead: the weight crosses
the ICI once for the whole call and its gradient once.

(The layers' weights are the partitioner's still: every dot that meets a
sharded weight is a ring of partial dots, the shards going round by
``collective-permute``.  Gathering a layer whole, in the layer or a layer
ahead, was tried on the chip in PR 37 and did not pay: PERF.md, section 6.)
"""

from __future__ import annotations

from typing import Callable, Optional

from .mesh import AXIS_DATA, AXIS_FSDP
from .sharding import default_rules, logical_to_pspec


def manual_mesh(mesh, rows: int) -> bool:
    """Whether a region manual over the whole of ``mesh`` can hold a model's
    own code on a batch of ``rows``: an ``fsdp`` axis over 1, no axis but
    ``dp`` beside it (``tp``, ``sp``, ``ep`` and ``pp`` split the code's own
    dimensions), and rows that divide over the chips."""
    if mesh is None or mesh.shape.get(AXIS_FSDP, 1) <= 1:
        return False
    if any(size > 1 for axis, size in mesh.shape.items()
           if axis not in (AXIS_DATA, AXIS_FSDP)):
        return False
    return rows % (mesh.shape.get(AXIS_DATA, 1) * mesh.shape[AXIS_FSDP]) == 0


def _fsdp_dim(spec) -> Optional[int]:
    """The dimension of a leaf whose layout names ``fsdp`` (None: none)."""
    for dim, entry in enumerate(spec):
        if entry == AXIS_FSDP or (isinstance(entry, tuple)
                                  and AXIS_FSDP in entry):
            return dim
    return None


def on_rows(fn: Callable, weight, rows, *, mesh, logical, dtype,
            reduce: bool):
    """``fn(weight, *rows)`` on each chip's rows, inside one region manual
    over the whole mesh (``manual_mesh`` holds): ``weight`` (logical axes
    ``logical``, placed by the default rules: another layout is brought to
    that one first) is gathered whole over ``fsdp`` ONCE for the call, however
    often ``fn`` reads it, and crosses as ``dtype``, the type ``fn``'s dots
    read it in.  ``fn`` is handed it in the type it is kept in, as without a
    mesh, so a loop in ``fn`` sums the gradient on each chip in that type
    (float32 for float32 master weights, as the partitioner's program does),
    and the sum leaves through one reduce-scatter whose sum over the chips
    is float32.  ``rows`` are arrays whose leading dimension is the batch;
    the result is summed over the chips where ``reduce``, and is rows itself
    otherwise."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    spec = logical_to_pspec(logical, default_rules())
    dim = _fsdp_dim(spec)
    batch = PartitionSpec((AXIS_DATA, AXIS_FSDP))

    @jax.custom_vjp
    def gather(w):
        return lax.all_gather(w.astype(dtype), AXIS_FSDP, axis=dim,
                              tiled=True).astype(w.dtype)

    def scatter(_, g):
        return (lax.psum_scatter(g.astype(jnp.float32), AXIS_FSDP,
                                 scatter_dimension=dim,
                                 tiled=True).astype(g.dtype),)

    gather.defvjp(lambda w: (gather(w), None), scatter)

    def local(weight, *rows):
        out = fn(weight if dim is None else gather(weight), *rows)
        return lax.psum(out, (AXIS_DATA, AXIS_FSDP)) if reduce else out

    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec,) + (batch,) * len(rows),
        out_specs=PartitionSpec() if reduce else batch,
        check_vma=False)(weight, *rows)
