"""Microbatched pipeline parallelism over the ``pp`` mesh axis.

The reference has no in-repo pipeline engine — it delegates PP to vLLM
(reference: llm/_internal/common/placement.py:47 sizes PG bundles as TP*PP)
or hands users the compiled-graph substrate to build their own (reference:
python/ray/dag/compiled_dag_node.py:804).  Here PP is a first-class GSPMD
strategy: transformer blocks are stacked [L, ...] and sharded over ``pp``
on the layer axis (each device keeps L/pp resident stage layers), and a
``shard_map`` island — manual only over ``pp``, all other mesh axes stay in
GSPMD auto mode — runs the GPipe schedule: at each of M + pp - 1 steps every
stage processes one microbatch and hands its activation to the next stage
with a single ICI hop (``lax.ppermute``).  Autodiff through the scan +
ppermute yields the reverse schedule for backward automatically.

Pipeline-bubble cost is the standard M/(M + pp - 1) utilization; raise
``num_microbatches`` to amortize.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from .mesh import AXIS_PIPELINE, get_global_mesh


def _pipeline_island(stage_params, x_mb, *, stage_body, axis_name: str,
                     num_stages: int, num_microbatches: int):
    """Runs inside shard_map: stage_params is this stage's [L/pp, ...]
    slice; x_mb is the full [M, mb, S, E] microbatched input (replicated
    over pp)."""
    stage = jax.lax.axis_index(axis_name)
    M = num_microbatches
    steps = M + num_stages - 1

    def step(buf, t):
        # Stage 0 feeds microbatch t (clipped; bubble steps recompute the
        # last microbatch and their output is never consumed), other stages
        # consume what the previous stage handed over.
        mb_idx = jnp.clip(t, 0, M - 1)
        x_t = jax.lax.dynamic_index_in_dim(x_mb, mb_idx, 0, keepdims=False)
        inp = jnp.where(stage == 0, x_t.astype(buf.dtype), buf)
        y = stage_body(stage_params, inp)
        # Hand to the next stage (i -> i+1); stage 0 receives zeros.
        perm = [(i, i + 1) for i in range(num_stages - 1)]
        nxt = jax.lax.ppermute(y, axis_name, perm)
        return nxt, y

    buf0 = jnp.zeros_like(x_mb[0])
    _, ys = jax.lax.scan(step, buf0, jnp.arange(steps))
    # Microbatch m leaves the last stage at step m + num_stages - 1.
    outs = ys[num_stages - 1:]
    # Broadcast the last stage's (only real) outputs to every pp rank so
    # the replicated lm_head/loss after the island sees correct values.
    mask = (stage == num_stages - 1).astype(outs.dtype)
    return jax.lax.psum(outs * mask, axis_name)


def pipeline_blocks(stacked_params, x, stage_body: Callable, *,
                    num_microbatches: int, mesh=None,
                    axis_name: str = AXIS_PIPELINE):
    """Run stacked transformer blocks as a microbatched pipeline.

    stacked_params: pytree with leading layer axis [L, ...], sharded over
        ``axis_name`` (the "layers" logical axis mapped to pp).
    x: [B, S, E] activations; B must divide by num_microbatches.
    stage_body(stage_params, h) -> h: applies one stage's layers.

    Returns [B, S, E].
    """
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        mesh = get_global_mesh()
    if mesh is None or axis_name not in mesh.axis_names:
        raise ValueError(f"pipeline_blocks needs a mesh with a "
                         f"{axis_name!r} axis")
    num_stages = mesh.shape[axis_name]
    B, S, E = x.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layers % num_stages:
        raise ValueError(
            f"layers ({n_layers}) must divide evenly over pp stages "
            f"({num_stages})")

    x_mb = x.reshape(M, B // M, S, E)
    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)

    body = partial(_pipeline_island, stage_body=stage_body,
                   axis_name=axis_name, num_stages=num_stages,
                   num_microbatches=M)
    island = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        axis_names={axis_name},  # manual over pp only; rest GSPMD
        check_vma=False,
    )
    out = island(stacked_params, x_mb)
    return out.reshape(B, S, E)
