"""Ring attention: context parallelism over the ICI ring.

Absent from the reference (SURVEY §2.4 SP/CP row: `grep -ri ring_attention`
over the reference returns nothing) — built natively for TPU.  The sequence
is sharded over the ``sp`` mesh axis; K/V blocks rotate around the ring via
``jax.lax.ppermute`` (one ICI hop per step) while each device accumulates
attention for its resident Q block with the flash-style online softmax
(running max + denominator), so the full [seq, seq] score matrix never
exists anywhere and per-device memory is O(seq/sp).

Compute/communication overlap: each ppermute transfers the next K/V block
while the current block's two matmuls run on the MXU — XLA schedules the
collective-permute asynchronously (start/done) around the dots.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ring_attention(q, k, v, *, axis_name: str, causal: bool = True,
                   scale: Optional[float] = None):
    """Attention over a sequence sharded on ``axis_name``.

    Must be called inside shard_map/pjit with ``axis_name`` bound.
    q/k/v: [B, H|Hkv, S_local, D] (local sequence shard, seq-contiguous
    layout: device i holds positions [i*S_local, (i+1)*S_local)).
    """
    B, H, Sl, D = q.shape
    _, Hkv, _, _ = k.shape
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale_ = scale if scale is not None else 1.0 / math.sqrt(D)
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    q32 = q.astype(jnp.float32)
    qpos = my_idx * Sl + jnp.arange(Sl)

    def step(s, carry):
        m, l, acc, kc, vc = carry
        src = (my_idx - s) % n  # which block we currently hold
        kpos = src * Sl + jnp.arange(Sl)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q32, kc.astype(jnp.float32),
                            preferred_element_type=jnp.float32) * scale_
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        m_blk = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        # Guard fully-masked blocks: exp(NEG_INF - NEG_INF) would be 1.
        safe = m_new > NEG_INF / 2
        corr = jnp.where(safe, jnp.exp(m - m_new), 1.0)
        e = jnp.where(safe, jnp.exp(scores - m_new), 0.0)
        l_new = l * corr + jnp.sum(e, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", e, vc.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        # Rotate K/V one hop around the ring: i -> i+1.
        perm = [(i, (i + 1) % n) for i in range(n)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return m_new, l_new, acc_new, kc, vc

    m0 = jnp.full((B, H, Sl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sl, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, Sl, D), jnp.float32)
    m, l, acc, _, _ = jax.lax.fori_loop(
        0, n, step, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh=None, *, axis_name: str = "sp",
                           causal: bool = True,
                           scale: Optional[float] = None,
                           in_spec=None):
    """Convenience wrapper: shard_map ring_attention over ``axis_name``.

    Arrays are [B, H, S, D] with S sharded over axis_name.  ``in_spec``
    overrides the full PartitionSpec when batch/head dims are also sharded
    (as inside a GSPMD forward: batch on (dp,fsdp), heads on tp); mesh=None
    uses the installed global mesh.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        from ..parallel.mesh import get_global_mesh
        mesh = get_global_mesh()
    spec = in_spec if in_spec is not None else P(None, None, axis_name, None)
    fn = partial(ring_attention, axis_name=axis_name, causal=causal,
                 scale=scale)
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)
    return wrapped(q, k, v)
