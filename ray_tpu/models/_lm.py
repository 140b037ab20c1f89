"""What every language model here shares beyond ``ops/``: the next-token
NLL of every position (fused, or in sequence chunks under remat), the masked
mean cross-entropy built on it, and the remat wrapper of a block.
``models/llama.py``, ``models/afmoe.py`` and ``models/ouro.py`` build on it;
none holds a copy."""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp


def is_shape(x) -> bool:
    """A leaf of a model's ``param_shapes``: (shape, fan-in[, start])."""
    return isinstance(x, tuple) and isinstance(x[1], int)


def init_from_shapes(shapes, key: jax.Array, param_dtype) -> Dict[str, Any]:
    """Parameters for a tree of (shape, fan-in[, start]): truncated normal /
    sqrt(fan-in); fan-in 0 marks a weight that starts at a constant:
    ``start`` (a number, or an array that broadcasts to the shape) where
    given, else a norm's weight at one and a scalar (a bias) at zero."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_shape)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, fan_in, *start) in zip(keys, leaves):
        if fan_in == 0:
            value = start[0] if start else (1.0 if shape else 0.0)
            out.append(jnp.broadcast_to(
                jnp.asarray(value, param_dtype), shape))
        else:
            out.append((jax.random.truncated_normal(
                k, -2, 2, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(param_dtype))
    return jax.tree.unflatten(treedef, out)


def count_params(shapes) -> int:
    return sum(math.prod(leaf[0]) for leaf in jax.tree.leaves(
        shapes, is_leaf=is_shape))


def project_heads(h, w, dt):
    """h [B, S, E] x w [E, H, D] -> [B, S, H, D]: the bytes of [B, S, H*D],
    the layout ``ops.rope.rotate_heads`` takes for q and k and the flash
    kernels for v (``ops.attention``'s ``rows``).  Said as one [B*S, E] x
    [E, H*D] product: as an einsum to ``bshd`` the TPU compiler emits the
    recomputed forward's result with the sequence minor and copies it round
    for the kernel (``copy`` of 67 MB a projection at Yi's shape; PERF.md,
    PR 35), and as one to ``bhsd`` it transposes what it wrote (PR 49)."""
    E, H, D = w.shape
    flat = jnp.einsum("bse,ef->bsf", h, w.astype(dt).reshape(E, H * D),
                      preferred_element_type=dt)
    return flat.reshape(*h.shape[:2], H, D)


def merge_heads(a, w, dt):
    """a [B, S, H, D] x w [H, D, E] -> [B, S, E]: ``project_heads``' way
    back, one [B*S, H*D] x [H*D, E] product over attention's result as the
    flash kernels write it (``rows``)."""
    H, D, E = w.shape
    return jnp.einsum("bsf,fe->bse", a.reshape(*a.shape[:2], H * D),
                      w.astype(dt).reshape(H * D, E),
                      preferred_element_type=dt)


def token_nll(x, lm_head, targets, num_chunks: int, dt, weights=None):
    """Next-token NLL of every position, float32 [B, S]: hidden states
    x [B, S, E] under ``lm_head`` [E, V] against ``targets`` [B, S].  With
    ``weights`` [B, S] it returns their weighted sum, a scalar, instead:
    summed inside each chunk, so that no per-token array leaves it (the
    per-token form under a plain masked mean cost the sparse model's step
    1.6 % on the chip: PERF.md, PR 34).

    ``num_chunks`` > 0 applies the head per sequence chunk under remat: peak
    logits memory is one chunk's [B, S/c, vocab] f32 slab (forward AND
    backward) instead of the full tensor.  Every loss here is built on this
    one function: a masked mean is its weighted sum (``next_token_loss``);
    a model that weighs several hidden states per token takes the
    per-token form (models/ouro.py).

    Several heads a position (models/evabyte.py): ``lm_head`` [E, J, V],
    ``targets`` and ``weights`` [B, S, J]; the per-token form is [B, S, J]
    and the weighted sums are one a head, [J].

    On an ambient mesh whose ``fsdp`` axis shards the head, a chunked loss
    of one head a position gathers it once for the call and not once a
    chunk and pass (``parallel/fsdp.on_rows``; where the mesh or the batch
    does not allow a manual region, the partitioner's program as before)."""
    spec = "bse,ev->bsv" if lm_head.ndim == 2 else "bse,ejv->bsjv"

    def run(lm_head, x, targets, weights=None):
        def nll(xc, tc, wc=None):
            # logsumexp formulation: nll = LSE(logits) - logit[target].
            # Unlike log_softmax this never materializes a second
            # [B, S, vocab] array — the LSE reduce fuses into the lm_head
            # matmul consumer, and the backward's softmax is recomputed
            # elementwise into the dW/dx matmuls.
            logits = jnp.einsum(spec, xc, lm_head.astype(dt),
                                preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            # promise_in_bounds: targets are token ids < vocab by
            # construction.  The default mode's NaN fill value poisons the
            # SPMD-partitioned gather when vocab is sharded (tp) — each
            # shard's locally-OOB rows fill NaN before the cross-shard
            # combine.
            tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1,
                                      mode="promise_in_bounds")[..., 0]
            return lse - tgt if wc is None else jnp.sum((lse - tgt) * wc,
                                                        axis=(0, 1))

        if not num_chunks:
            return nll(x, targets, weights)
        B, S, E = x.shape
        assert S % num_chunks == 0, (S, num_chunks)
        c = S // num_chunks
        chunks = lambda a: jnp.swapaxes(
            a.reshape((B, num_chunks, c) + a.shape[2:]), 0, 1)
        chunk_nll = jax.checkpoint(nll)
        if weights is None:
            _, out = jax.lax.scan(lambda _, xt: (None, chunk_nll(*xt)), None,
                                  (chunks(x), chunks(targets)))
            return jnp.swapaxes(out, 0, 1).reshape(targets.shape)
        total, _ = jax.lax.scan(
            lambda acc, xtw: (acc + chunk_nll(*xtw), None),
            jnp.zeros(weights.shape[2:], jnp.float32),
            (chunks(x), chunks(targets), chunks(weights)))
        return total

    from ..parallel import fsdp
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if num_chunks and lm_head.ndim == 2 and fsdp.manual_mesh(mesh,
                                                             x.shape[0]):
        # The head crosses the ICI once a step and its gradient once, not
        # once a chunk forward, recomputed and backward.
        rows = (x, targets) if weights is None else (x, targets, weights)
        return fsdp.on_rows(run, lm_head, rows, mesh=mesh,
                            logical=("embed", "vocab"), dtype=dt,
                            reduce=weights is not None)
    return run(lm_head, x, targets, weights)


def targets_and_mask(batch: Dict[str, jax.Array]):
    """(targets [B, S], float32 mask [B, S], the count the masked sum is
    divided by) of a batch: tokens [B, S], optional loss_mask [B, S] and
    loss_denom.  Position t predicts token t + 1; the last predicts
    nothing."""
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
    return targets, mask, denom


def next_token_loss(x, lm_head, batch: Dict[str, jax.Array],
                    loss_chunks: int, dt) -> jax.Array:
    """Masked mean next-token cross-entropy of the final hidden states
    x [B, S, E] under ``lm_head`` [E, V].  batch: tokens [B,S], optional
    loss_mask [B,S] and loss_denom.  ``loss_chunks`` > 0 computes the
    [B, S, vocab] logits in that many sequence chunks (scan + remat), so
    only ONE chunk's f32 logits are ever resident."""
    targets, mask, denom = targets_and_mask(batch)
    with jax.named_scope("loss"):
        return token_nll(x, lm_head, targets, loss_chunks, dt, mask) / denom


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
