"""What every language model here shares beyond ``ops/``: the masked
next-token cross-entropy (fused, or in sequence chunks under remat) and the
remat wrapper of a block.  ``models/llama.py`` and ``models/afmoe.py`` both
build on it; neither holds a copy."""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp


def chunked_nll_sum(x, lm_head, targets, mask, num_chunks: int, dt):
    """Masked next-token NLL sum with the lm_head applied per sequence
    chunk under remat: peak logits memory is one chunk's [B, S/c, vocab]
    f32 slab (forward AND backward) instead of the full tensor."""
    B, S, E = x.shape
    assert S % num_chunks == 0, (S, num_chunks)
    c = S // num_chunks
    xs = jnp.swapaxes(x.reshape(B, num_chunks, c, E), 0, 1)
    ts = jnp.swapaxes(targets.reshape(B, num_chunks, c), 0, 1)
    ms = jnp.swapaxes(mask.reshape(B, num_chunks, c), 0, 1)

    @jax.checkpoint
    def chunk_nll(xc, tc, mc):
        logits = jnp.einsum("bse,ev->bsv", xc, lm_head.astype(dt),
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # promise_in_bounds: targets are token ids < vocab by
        # construction.  The default mode's NaN fill value poisons the
        # SPMD-partitioned gather when vocab is sharded (tp) — each
        # shard's locally-OOB rows fill NaN before the cross-shard
        # combine.
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1,
                                  mode="promise_in_bounds")[..., 0]
        return jnp.sum((lse - tgt) * mc)

    def body(acc, xtm):
        return acc + chunk_nll(*xtm), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (xs, ts, ms))
    return total


def next_token_loss(x, lm_head, batch: Dict[str, jax.Array],
                    loss_chunks: int, dt) -> jax.Array:
    """Masked mean next-token cross-entropy of the final hidden states
    x [B, S, E] under ``lm_head`` [E, V].  batch: tokens [B,S], optional
    loss_mask [B,S] and loss_denom.  ``loss_chunks`` > 0 computes the
    [B, S, vocab] logits in that many sequence chunks (scan + remat), so
    only ONE chunk's f32 logits are ever resident."""
    tokens = batch["tokens"]
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.concatenate(
            [jnp.ones_like(tokens[:, 1:]), jnp.zeros_like(tokens[:, :1])],
            axis=1)
    mask = mask.astype(jnp.float32)
    # Gradient-accumulation callers inject the FULL batch's token count
    # so per-microbatch means sum to exactly the unaccumulated loss even
    # with uneven masking (see spmd.make_lm_train_step).
    denom = batch.get("loss_denom")
    if denom is None:
        denom = jnp.maximum(jnp.sum(mask), 1.0)
    if loss_chunks:
        with jax.named_scope("loss"):
            return chunked_nll_sum(x, lm_head, targets, mask, loss_chunks,
                                   dt) / denom
    logits = jnp.einsum("bse,ev->bsv", x, lm_head.astype(dt),
                        preferred_element_type=jnp.float32)
    with jax.named_scope("loss"):
        # logsumexp formulation: nll = LSE(logits) - logit[target].
        # Unlike log_softmax this never materializes a second
        # [B, S, vocab] array — the LSE reduce fuses into the lm_head
        # matmul consumer, and the backward's softmax is recomputed
        # elementwise into the dW/dx matmuls.
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # promise_in_bounds: see chunked_nll_sum.
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1,
                                  mode="promise_in_bounds")[..., 0]
        return jnp.sum((lse - tgt) * mask) / denom


def remat(block: Callable, mode: Any) -> Callable:
    """``block`` under the remat mode of a model configuration: False saves
    everything (small models only); True/"full" recomputes the whole block
    in the backward pass; "dots" keeps every matmul output (the MXU work
    worth not repeating) and recomputes the cheap VPU elementwise ops
    (norms/rope/silu); "dots_nobatch" keeps only batch-free dots
    (weights-stationary projections)."""
    if mode is False:
        return block
    policies = {
        True: jax.checkpoint_policies.nothing_saveable,
        "full": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_nobatch":
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims}
    if mode not in policies:
        raise ValueError(f"unknown remat mode {mode!r}")
    return jax.checkpoint(block, policy=policies[mode])
