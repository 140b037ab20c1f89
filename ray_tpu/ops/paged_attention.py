"""Paged decode attention over a block-table KV cache.

The serving engine (llm/engine.py) keeps K/V in fixed-size pages with a
per-slot block table mapping sequence positions to pages.  One decode
step attends each slot's single query token over its pages.

Cache layout (per layer): ONE combined array

    kv_pages : [total_pages, page_size, 2 * num_kv_heads, head_dim]

with K at even and V at odd combined-head indices (k_h0, v_h0, k_h1,
...).  This is the layout the TPU ragged-paged-attention kernel reads
natively AND the layout whose per-token cache insert is a single
scatter with fully-contiguous [2*Hkv, D] windows at a leading
(page, offset) index — the earlier split-K/V, heads-leading layout put
the scatter window across the major axis, and the 48 resulting strided
scatters per decode step cost ~3x the model's matmuls (measured on
v5e: 22ms of a 28ms step).

Two execution paths, chosen statically at trace time:

- TPU: the pallas ragged-paged-attention kernel
  (jax.experimental.pallas.ops.tpu.ragged_paged_attention) —
  block-table-indexed async DMA of pages into VMEM with online softmax,
  so HBM traffic per step is the *live* KV only.  This is the kernel
  class the reference's serving stack reaches through vLLM's TPU
  backend (reference: python/ray/llm/_internal/serve/engines/vllm/).
- elsewhere (CPU tests): an exact jnp path that gathers pages and does
  dense masked attention — numerically the spec for the kernel.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def combine_kv(k, v):
    """Interleave per-head K and V ([..., Hkv, D] each) into the
    combined-head layout [..., 2*Hkv, D] the kernel reads."""
    stacked = jnp.stack([k, v], axis=-2)          # [..., Hkv, 2, D]
    shape = k.shape[:-2] + (2 * k.shape[-2], k.shape[-1])
    return stacked.reshape(shape)


def paged_decode_attention(q, kv_pages, block_table, seq_lens,
                           page_size: int):
    """One decode step of attention over the paged cache.

    q: [B, H, D] (one new token per slot); kv_pages:
    [NP, page, 2*Hkv, D] combined; block_table: [B, P] page ids;
    seq_lens: [B] sequence length INCLUDING the new token; 0 marks an
    empty slot, whose output row is unspecified.
    Returns [B, H, D].
    """
    from .attention import _on_tpu
    if _on_tpu():
        return _ragged_path(q, kv_pages, block_table, seq_lens)
    return _exact_path(q, kv_pages, block_table, seq_lens, page_size)


def _ragged_path(q, kv_pages, block_table, seq_lens):
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention)

    B, H, D = q.shape
    # Decode is the all-sequences-length-1 case of the ragged layout:
    # query token i belongs to sequence i.
    cu_q_lens = jnp.arange(B + 1, dtype=jnp.int32)
    num_seqs = jnp.array([B], jnp.int32)
    out = ragged_paged_attention(
        q, kv_pages,
        # The kernel prefetches the next sequence's pages and waits for
        # them only inside that sequence's kv loop: a sequence of length
        # 0 leaves its DMA unawaited and the core halts at kernel exit
        # (libtpu 0.0.34 checks the semaphores).  An empty slot reads one
        # position of whatever page its table names; callers discard it.
        kv_lens=jnp.maximum(seq_lens.astype(jnp.int32), 1),
        page_indices=block_table.astype(jnp.int32),
        cu_q_lens=cu_q_lens, num_seqs=num_seqs,
        sm_scale=1.0 / math.sqrt(D),
        # The auto-tuned block sizes overshoot the 16M scoped-vmem
        # default by a hair on v5e at decode shapes; v5e has 128M VMEM.
        vmem_limit_bytes=64 * 1024 * 1024)
    return out.astype(q.dtype)


def _exact_path(q, kv_pages, block_table, seq_lens, page_size: int):
    """Reference semantics: gather each sequence's pages and run dense
    masked attention.  Materializes [B, H, S_max, D] — fine for CPU
    tests, never the TPU path."""
    B, H, D = q.shape
    Hkv = kv_pages.shape[2] // 2
    P = block_table.shape[1]
    group = H // Hkv
    pages = jnp.take(kv_pages, block_table, axis=0)  # [B, P, page, 2Hkv, D]
    k = pages[:, :, :, 0::2, :]                      # [B, P, page, Hkv, D]
    v = pages[:, :, :, 1::2, :]
    k = k.reshape(B, P * page_size, Hkv, D).transpose(0, 2, 1, 3)
    v = v.reshape(B, P * page_size, Hkv, D).transpose(0, 2, 1, 3)
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    kv_pos = jnp.arange(P * page_size)
    mask = kv_pos[None, :] < seq_lens[:, None]          # [B, S_max]
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bhkd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
