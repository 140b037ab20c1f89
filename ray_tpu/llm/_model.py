"""Llama inference forward passes with a paged KV cache.

The training forward (models/llama.py) is full-sequence; inference needs
two extra programs, both jit-compiled with static shapes:

- ``prefill``: run a (padded) prompt through the model, returning the last
  valid position's logits and the per-layer K/V to seed the cache.
- ``decode_step``: one token per active slot, attending over the paged
  cache via block tables through ops/paged_attention.py — the pallas
  block-table kernel on TPU (page-granular DMA, no full-KV gather), the
  exact jnp path elsewhere.

Weights are the training pytree unchanged (init_params layout), so a
trained checkpoint serves directly.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..models.llama import LlamaConfig
from ..ops.norms import rms_norm
from ..ops.paged_attention import NEG_INF, paged_decode_attention
from ..ops.rope import rope_frequencies


def _rope_batched(x, cos, sin, positions):
    """x: [B, H, S, D]; positions: [B, S] (per-sequence absolute)."""
    c = cos[positions][:, None]          # [B, 1, S, D/2]
    s = sin[positions][:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _project_qkv(cfg, layer, h, positions):
    """h: [B, S, E]; positions: [B, S]."""
    dt = cfg.dtype
    q = jnp.einsum("bse,ehd->bhsd", h, layer["wq"].astype(dt))
    k = jnp.einsum("bse,ehd->bhsd", h, layer["wk"].astype(dt))
    v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"].astype(dt))
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    q = _rope_batched(q, cos, sin, positions)
    k = _rope_batched(k, cos, sin, positions)
    return q, k, v


def _mlp(cfg, layer, h):
    dt = cfg.dtype
    gate = jnp.einsum("bse,em->bsm", h, layer["w_gate"].astype(dt))
    up = jnp.einsum("bse,em->bsm", h, layer["w_up"].astype(dt))
    return jnp.einsum("bsm,me->bse", jax.nn.silu(gate) * up,
                      layer["w_down"].astype(dt))


def prefill(params: Dict[str, Any], tokens: jax.Array, length: jax.Array,
            cfg: LlamaConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """tokens: [1, S_pad]; length: [] valid prompt length.

    Returns (logits at the last valid position [vocab],
             k [L, S_pad, Hkv, D], v [L, S_pad, Hkv, D])."""
    dt = cfg.dtype
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = params["embed"].astype(dt)[tokens]

    def body(x, layer):
        with jax.named_scope("attn"):
            h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q, k, v = _project_qkv(cfg, layer, h, positions[None, :])
            # Causal masking suffices: queries at/after `length` are
            # padding whose logits are never read, and valid queries only
            # see valid (earlier) key positions.
            from ..ops.attention import reference_attention
            attn = reference_attention(q, k, v, causal=True)
            attn_out = jnp.einsum("bhsd,hde->bse", attn,
                                  layer["wo"].astype(dt))
            x = x + attn_out
        with jax.named_scope("mlp"):
            h2 = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            x = x + _mlp(cfg, layer, h2)
        # [S, Hkv, D] per layer for the cache.
        return x, (k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    with jax.named_scope("logits"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.clip(length - 1, 0, S - 1)
        logits = jnp.einsum("e,ev->v", x[0, last].astype(jnp.float32),
                            params["lm_head"].astype(jnp.float32))
    return logits, ks, vs




def write_prefill(kv_pages, ks, vs, page_ids, offs):
    """Scatter a prefilled prompt's K/V into every layer's pages in ONE
    device program (kv_pages: per-layer tuple of combined
    [NP, page, 2*Hkv, D] arrays, donated) — per-layer host-dispatched
    scatters would cost 2*layers dispatches per admission.

    ks/vs: [L, S_pad, Hkv, D] from prefill; page_ids/offs: [S_pad]
    (positions past the real prompt length point at reserved page 0, so
    the scatter shape is bucket-static)."""
    from ..ops.paged_attention import combine_kv
    kv = list(kv_pages)
    dt = kv[0].dtype
    with jax.named_scope("cache_write"):
        for li in range(len(kv)):
            comb = combine_kv(ks[li], vs[li]).astype(dt)  # [S_pad, 2Hkv, D]
            kv[li] = kv[li].at[page_ids, offs, :, :].set(comb)
    return tuple(kv)


def prefill_chunk(params: Dict[str, Any], kv_pages,
                  tokens: jax.Array, start: jax.Array, length: jax.Array,
                  block_table: jax.Array, cfg: LlamaConfig,
                  page_size: int):
    """Incremental (chunked) prefill: run ``length`` prompt tokens that
    begin at absolute position ``start`` through the model, writing
    their K/V into this sequence's pages and attending over ALL cache
    positions ``[0, start+length)`` — earlier chunks' K/V are read back
    from the paged cache, so a long prompt prefills as a series of small
    bounded programs interleaved with decode steps instead of one
    monolithic program that stalls every active decode (reference
    analog: vLLM chunked prefill / Sarathi-style piggybacking).

    tokens: [1, C] chunk-bucket-padded; start/length: scalars;
    block_table: [P] page ids for this sequence.  Returns (logits at the
    chunk's last valid position [vocab], new kv_pages).
    """
    import math as _math

    from ..ops.paged_attention import combine_kv

    dt = cfg.dtype
    _B, C = tokens.shape
    P = block_table.shape[0]
    S = P * page_size
    Hkv, D = cfg.kv_heads, cfg.head_dim
    group = cfg.heads // Hkv
    idx = jnp.arange(C)
    positions = start + idx                       # [C] absolute
    total = start + length
    valid = idx < length
    # Rope table lookups clamp; writes for padding rows land on reserved
    # page 0 (never referenced by any block table).
    rope_pos = jnp.minimum(positions, cfg.max_seq_len - 1)
    page_ids = jnp.where(
        valid, block_table[jnp.clip(positions // page_size, 0, P - 1)], 0)
    offs = jnp.where(valid, positions % page_size, 0)
    kv_pos = jnp.arange(S)
    x = params["embed"].astype(dt)[tokens]        # [1, C, E]

    n_layers = params["blocks"]["wq"].shape[0]
    kv_pages = list(kv_pages)
    # Jitted by callers (engine's prefill-chunk jit / disagg prefill): the
    # layer loop unrolls at trace time, it never dispatches op-by-op.
    for li in range(n_layers):  # ray-tpu: noqa[RT506]
        layer = jax.tree.map(lambda a, li=li: a[li], params["blocks"])
        kv = kv_pages[li]
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(cfg, layer, h, rope_pos[None, :])
        # Write this chunk's K/V first, then gather the WHOLE sequence
        # back from pages: chunk-internal causality rides the same mask
        # as cross-chunk context.
        with jax.named_scope("cache_write"):
            comb = combine_kv(k[0].transpose(1, 0, 2),
                              v[0].transpose(1, 0, 2)).astype(kv.dtype)
            kv = kv.at[page_ids, offs, :, :].set(comb)
        kv_pages[li] = kv
        pages = jnp.take(kv, block_table, axis=0)  # [P, page, 2Hkv, D]
        ks = pages[:, :, 0::2, :].reshape(S, Hkv, D)
        vs = pages[:, :, 1::2, :].reshape(S, Hkv, D)
        kh = ks.transpose(1, 0, 2)                 # [Hkv, S, D]
        vh = vs.transpose(1, 0, 2)
        if group > 1:
            kh = jnp.repeat(kh, group, axis=0)
            vh = jnp.repeat(vh, group, axis=0)
        scores = jnp.einsum("hcd,hsd->hcs", q[0], kh,
                            preferred_element_type=jnp.float32) \
            / _math.sqrt(D)
        mask = (kv_pos[None, :] <= positions[:, None]) & \
               (kv_pos[None, :] < total)           # [C, S]
        scores = jnp.where(mask[None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("hcs,hsd->hcd", probs.astype(vh.dtype), vh)
        attn_out = jnp.einsum("hcd,hde->ce", attn, layer["wo"].astype(dt))
        x = x + attn_out[None]
        h2 = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        x = x + _mlp(cfg, layer, h2)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.clip(length - 1, 0, C - 1)
    logits = jnp.einsum("e,ev->v", x[0, last].astype(jnp.float32),
                        params["lm_head"].astype(jnp.float32))
    return logits, tuple(kv_pages)


def decode_step(params: Dict[str, Any], kv_pages,
                tokens: jax.Array, positions: jax.Array,
                block_tables: jax.Array, active: jax.Array,
                cfg: LlamaConfig, page_size: int):
    """One decode step for every slot.

    tokens: [B] last sampled token per slot; positions: [B] their position;
    block_tables: [B, P] page ids; active: [B] bool.
    Returns (logits [B, vocab], new kv_pages) — cache arrays are updated
    in place via donation.

    Cache layout: a TUPLE of per-layer COMBINED page arrays
    ``[num_pages, page_size, 2*Hkv, D]`` (K even / V odd combined-head
    indices — the ragged-paged-attention kernel's native layout).  Each
    leaf takes exactly ONE scatter per step whose [2*Hkv, D] window is
    fully contiguous at a leading (page, offset) index — the layout this
    replaced (split K/V, heads leading) needed 48 strided scatters per
    step that cost ~3x the model's matmuls on v5e."""
    from ..ops.paged_attention import combine_kv
    dt = cfg.dtype
    B = tokens.shape[0]
    x = params["embed"].astype(dt)[tokens][:, None, :]     # [B, 1, E]
    seq_lens = jnp.where(active, positions + 1, 0)
    page_idx = jnp.take_along_axis(
        block_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    # Inactive slots park their write on reserved page 0 (never read)
    # instead of a predicated read-modify-write of live pages.
    page_idx = jnp.where(active, page_idx, 0)
    page_off = jnp.where(active, positions % page_size, 0)

    n_layers = params["blocks"]["wq"].shape[0]
    kv_pages = list(kv_pages)
    for li in range(n_layers):
        layer = jax.tree.map(lambda a, li=li: a[li], params["blocks"])
        kv = kv_pages[li]
        with jax.named_scope("attn"):
            h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q, k, v = _project_qkv(cfg, layer, h, positions[:, None])
            # ONE combined scatter: target kv[page_idx, page_off] is
            # [B, 2*Hkv, D] with a contiguous window per index.
            with jax.named_scope("cache_write"):
                comb = combine_kv(k[:, :, 0, :],
                                  v[:, :, 0, :]).astype(kv.dtype)
                kv = kv.at[page_idx, page_off, :, :].set(
                    comb, unique_indices=False)
            kv_pages[li] = kv
            attn = paged_decode_attention(q[:, :, 0, :], kv, block_tables,
                                          seq_lens, page_size)
            attn_out = jnp.einsum("bhd,hde->be", attn,
                                  layer["wo"].astype(dt))
            x = x + attn_out[:, None, :]
        with jax.named_scope("mlp"):
            h2 = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            x = x + _mlp(cfg, layer, h2)
    kv_pages = tuple(kv_pages)
    with jax.named_scope("logits"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        # bf16 reads with f32 MXU accumulation: casting lm_head to f32
        # would materialize a 4-byte copy of the largest matrix every step.
        logits = jnp.einsum("be,ev->bv", x[:, 0, :], params["lm_head"],
                            preferred_element_type=jnp.float32)
        return logits.astype(jnp.float32), kv_pages
