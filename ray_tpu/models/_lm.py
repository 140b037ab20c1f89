"""What every language model here shares beyond ``ops/``: the next-token
NLL of every position (fused, or in sequence chunks under remat), the masked
mean cross-entropy built on it, and the remat wrapper of a block.
``models/llama.py``, ``models/afmoe.py`` and ``models/ouro.py`` build on it;
none holds a copy."""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import FLASH_LSE, FLASH_OUT
from ..util import telemetry


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


#: The share of a device's memory (``bytes_limit``) that the flash calls'
#: results of a whole step may take where they outlive the layers' remat
#: (``flash_keep``): all of them or none.  What the benchmark's training
#: steps would keep, a chip, against 16.91 GB (ISSUE 58; bf16 ``out``, and
#: a float32 ``lse`` 1/64 of it beside):
#:
#:   calls x [rows, S, H * Dv]                    GB     share
#:   lfm2      2 x [4, 8192, 32 * 64]            0.27    1.6 %   kept
#:   ling      1 x [4, 8192, 32 * 128]           0.27    1.6 %   kept
#:   nemotron  2 x [4, 8192, 32 * 128]           0.55    3.2 %   kept
#:   motif     4 x [1, 8192, 80 * 128]           0.68    4.0 %   kept
#:   xing4.0   6 x [2, 8192, 32 * 128]           0.82    4.9 %   kept
#:   mistral  28 x [1, 4096, 32 * 128]           0.96    5.7 %   kept
#:   yi       24 x [4, 4096, 16 * 128]           1.61    9.5 %
#:   trinity   9 x [4, 8192, 32 * 128]           2.42   14.3 %
#:   ouro     48 x [4, 4096, 16 * 128]           3.22   19.0 %
#:   kanana   12 x [4, 8192, 32 * 128]           3.22   19.0 %
FLASH_KEEP_SHARE = 1 / 16


def _bytes_limit() -> Optional[int]:
    """A local device's memory in bytes (the least of them), or None where
    the backend states none (the CPU)."""
    from ..profiler.capture import device_memory_stats
    return min((d["bytes_limit"] for d in device_memory_stats()
                if d.get("bytes_limit")), default=None)


def flash_keep(mode: Any, calls: int, shape, dtype, chips: int = 1) -> tuple:
    """What a stack's layers keep past their remat (``remat``'s ``keep``):
    the names of the flash kernels' two results (``ops.attention.FLASH_OUT``
    / ``FLASH_LSE``), so that the forward kernel runs once a layer and not
    again in the backward's recomputation, or nothing.  All or nothing,
    from what a stack sees when it is traced: ``calls`` attention calls a
    step under the remat (layers x loop passes, a prediction module's),
    each with a result of ``shape`` [B, S, H, Dv] and ``dtype`` whose rows
    lie over ``chips`` devices.  The names are kept iff ``mode`` is
    ``remat``'s True / "full" and ``out`` + ``lse`` (float32 [B, H, S]) of
    every call together are at most ``FLASH_KEEP_SHARE`` of the device's
    ``bytes_limit``; where the backend gives no limit (the CPU), nothing.
    No model is asked its name.  A step that stood within that share of
    the limit while it recomputed everything can stop fitting: say
    ``remat="dots"`` or fewer rows there.

    Counts ``ray_tpu_remat_kept_total``, one a stack traced."""
    B, S, H, Dv = shape
    rows = -(-B // chips)
    need = calls * rows * S * H * (Dv * jnp.dtype(dtype).itemsize + 4)
    limit = _bytes_limit()
    budget = int(limit * FLASH_KEEP_SHARE) if limit else None
    kept = mode in (True, "full") and bool(calls) \
        and budget is not None and need <= budget
    names = (FLASH_OUT, FLASH_LSE)
    telemetry.inc("ray_tpu_remat_kept_total", tags={
        "names": "+".join(names), "kept": "true" if kept else "false",
        "calls": str(calls), "bytes": str(need), "budget": str(budget)})
    return names if kept else ()


def remat(block: Callable, mode: Any, keep: tuple = ()) -> Callable:
    """``block`` under the remat mode of a model configuration: False saves
    everything (small models only); True/"full" recomputes the whole block
    in the backward pass but for the arrays named in ``keep``
    (``checkpoint_name``; ``flash_keep`` gives the flash kernels' results
    where a sixteenth of the device holds the whole step's, and nothing
    else: with an empty ``keep`` nothing outlives the forward pass but the
    block's inputs); "dots" keeps every matmul output (the MXU work worth
    not repeating) and recomputes the cheap VPU elementwise ops
    (norms/rope/silu); "dots_nobatch" keeps only batch-free dots
    (weights-stationary projections).  These two and False take no notice
    of ``keep``."""
    if mode is False:
        return block
    full = (jax.checkpoint_policies.save_only_these_names(*keep) if keep
            else jax.checkpoint_policies.nothing_saveable)
    policies = {
        True: full,
        "full": full,
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_nobatch":
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims}
    if mode not in policies:
        raise ValueError(f"unknown remat mode {mode!r}")
    return jax.checkpoint(block, policy=policies[mode])


def rows_at_a_time(one: Callable, x, layer_rows: Optional[int], top_k: int):
    """``one(rows) -> (y, report)`` over x [B, S, E], ``layer_rows`` rows at
    a time, one group after the other (``jax.lax.map``), so that a layer's
    temporaries are one group's; ``one`` is the layer under its remat.  The
    groups' reports are joined as an expert layer's loads are: ``counts``,
    ``dropped`` and ``sliced`` summed, ``top`` [tokens, k] laid end to end,
    and anything else a report holds averaged over the groups.  None, or a
    batch of no more rows: the whole batch at once."""
    B = x.shape[0]
    n = min(layer_rows or B, B)
    if B % n:
        raise ValueError(f"a batch of {B} rows does not split into groups "
                         f"of layer_rows={n}")
    if n == B:
        return one(x)
    y, report = jax.lax.map(one, x.reshape((B // n, n) + x.shape[1:]))
    if report is not None:
        summed = ("counts", "dropped", "sliced")
        report = {k: (v.reshape(-1, top_k) if k == "top" else
                      jnp.sum(v, axis=0) if k in summed else
                      jnp.mean(v, axis=0)) for k, v in report.items()}
    return y.reshape(x.shape), report
