"""Ulysses-style sequence parallelism: all-to-all head<->sequence reshard.

Absent from the reference (SURVEY §2.4) — built natively.  Each device holds
a sequence shard of all heads; one all-to-all turns that into all tokens of
a head shard, local full-sequence attention runs (flash kernel), and a
second all-to-all restores the sequence-sharded layout.  Cost is two
all-to-alls of activation size vs ring's N ppermutes of K/V — better when
head count >= sp axis and sequences are long enough for the flash kernel to
dominate.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import reference_attention


def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = True,
                      scale: Optional[float] = None,
                      attn_fn=None):
    """Call inside shard_map: q/k/v [B, H, S_local, D] seq-sharded.

    H must be divisible by the axis size.  GQA note: K/V heads are
    repeated to full H before the swap when Hkv < axis size would make the
    all-to-all split impossible.
    """
    B, H, Sl, D = q.shape
    n = jax.lax.psum(1, axis_name)
    _, Hkv, _, _ = k.shape
    if Hkv % n:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if H % n:
        raise ValueError(f"heads {H} not divisible by axis size {n}")

    def swap(x):  # [B, h, S_local, D] -> [B, h/n, S, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def unswap(x):  # [B, h/n, S, D] -> [B, h, S_local, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = swap(q), swap(k), swap(v)
    fn = attn_fn or reference_attention
    out = fn(qh, kh, vh, causal=causal, scale=scale)
    return unswap(out)


def ulysses_attention_sharded(q, k, v, mesh=None, *, axis_name: str = "sp",
                              causal: bool = True,
                              scale: Optional[float] = None,
                              in_spec=None):
    import jax
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        from ..parallel.mesh import get_global_mesh
        mesh = get_global_mesh()
    spec = in_spec if in_spec is not None else P(None, None, axis_name, None)
    fn = partial(ulysses_attention, axis_name=axis_name, causal=causal,
                 scale=scale)
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)
    return wrapped(q, k, v)
