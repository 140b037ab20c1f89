"""The ``evabyte`` decoder (EvaByte 6.5B): a byte-level model whose
attention is EVA, chunked linearized attention, and which predicts several
bytes a position.

What it has that ``models/llama.py`` has not:

- Attention over two kinds of key under one softmax (``ops/eva.py``): the
  tokens of the query's own window of ``window`` positions up to itself,
  and one summary of every ``chunk`` tokens of every earlier window.  A
  summary key and value are learned softmax poolings of the chunk's
  (rotated) keys and values under two vectors a head, ``eva_mu`` and
  ``eva_phi`` [H, D].  The projections, the rotary embedding
  (``ops.rope.rotate_heads``) and the SwiGLU branch (``llama.mlp_branch``)
  are Llama's, as are the configuration's fields: ``EvaByteConfig`` extends
  ``LlamaConfig``.  No grouped heads.
- A float32 residual stream (``fp32_skip_add``): a branch reads the normed
  stream in ``dtype`` and its result is added in float32.
- RMSNorm with a unit offset (``norm_add_unit_offset``): the weight held is
  ``g`` and the norm scales by ``1 + g``; ``g`` starts at 0, and weight
  decay pulls the scale to 1.
- ``pred_heads`` output heads a position over the byte vocabulary
  (``num_pred_heads`` = 8): head ``j`` (from 1) at position ``t`` predicts
  byte ``t + j``.  ``lm_head`` is [E, J, V]; the loss is the mean over the
  heads of each head's masked mean cross-entropy, and a head's mask leaves
  out the row's last ``j`` positions.  With one head it is Llama's loss.

The loss hands the step what it reports (``loss_and_report``):
``head_loss`` [J], each head's masked mean.

The per-token NLL, remat, norm and rotary code are ``models/_lm.py``'s and
``ops/``'s, shared with Llama, afmoe and ouro.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import _lm
from .llama import LlamaConfig, _kernel_mesh, mlp_branch
from .llama import param_logical_axes as llama_logical_axes
from .ouro import _scoped
from ..ops.eva import eva_attention, eva_summaries
from ..ops.norms import rms_norm
from ..ops.rope import rope_lane_tables, rotate_heads


@dataclass(frozen=True)
class EvaByteConfig(LlamaConfig):
    """Defaults are EvaByte 6.5B's published ``config.json``."""
    vocab_size: int = 320
    hidden: int = 4096
    layers: int = 32
    heads: int = 32
    kv_heads: int = 32
    head_dim: int = 128
    mlp_dim: int = 11008
    max_seq_len: int = 32768
    rope_theta: float = 1e5
    norm_eps: float = 1e-5
    window: int = 2048                  # ``window_size``
    chunk: int = 16                     # ``chunk_size``
    pred_heads: int = 8                 # ``num_pred_heads``


def evabyte_tiny(**kw) -> EvaByteConfig:
    """A CPU-test size: 2 layers, 4 windows of 64 in chunks of 8, 3 heads
    a position."""
    return EvaByteConfig(**{**dict(
        vocab_size=64, hidden=64, layers=2, heads=4, kv_heads=4,
        head_dim=16, mlp_dim=96, max_seq_len=256, window=64, chunk=8,
        pred_heads=3, dtype=jnp.float32, attention_impl="reference",
        remat=False), **kw})


def param_shapes(cfg: EvaByteConfig) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a norm's ``g``, which starts at 0).
    The pooling vectors play a query's part against a key, so they start
    at a query's size: unit normal, fan-in 1."""
    L, E, H, D, M, V, J = (cfg.layers, cfg.hidden, cfg.heads, cfg.head_dim,
                           cfg.mlp_dim, cfg.vocab_size, cfg.pred_heads)
    if cfg.kv_heads != H:
        raise ValueError("EVA has a key head a query head")
    return {
        "embed": ((V, E), E),
        "blocks": {
            "attn_norm": ((L, E), 0), "mlp_norm": ((L, E), 0),
            "wq": ((L, E, H, D), E), "wk": ((L, E, H, D), E),
            "wv": ((L, E, H, D), E), "wo": ((L, H, D, E), H * D),
            "eva_mu": ((L, H, D), 1), "eva_phi": ((L, H, D), 1),
            "w_gate": ((L, E, M), E), "w_up": ((L, E, M), E),
            "w_down": ((L, M, E), M)},
        "final_norm": ((E,), 0),
        "lm_head": ((E, J, V), E)}


def param_logical_axes(cfg: EvaByteConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples: Llama's, the
    two pooling vectors a layer, and the heads' axis of ``lm_head``."""
    axes = llama_logical_axes(cfg)
    pool = ("layers", "heads", "head_dim")
    return {**axes, "lm_head": ("embed", None, "vocab"),
            "blocks": {**axes["blocks"], "eva_mu": pool, "eva_phi": pool}}


def init_params(cfg: EvaByteConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    shapes = param_shapes(cfg)
    params = _lm.init_from_shapes(shapes, key, param_dtype)
    # ``init_from_shapes`` starts a norm's weight at 1; here it is an offset.
    return jax.tree.map(
        lambda p, s: jnp.zeros_like(p) if s[1] == 0 else p, params, shapes,
        is_leaf=_lm.is_shape)


def num_params(cfg: EvaByteConfig) -> int:
    return _lm.count_params(param_shapes(cfg))


def offset_norm(x, g, eps: float, dt):
    """``x / rms(x) * (1 + g)`` of the float32 stream, handed on in ``dt``.
    On a TPU ``rms_norm`` pins a float32 input row-major, which is what keeps
    this model's one-row stream from lying sequence-minor through the whole
    step (``ops/norms.py``; PERF.md, PR 39)."""
    return rms_norm(x, 1.0 + g.astype(jnp.float32), eps).astype(dt)


def eva_branch(cfg: EvaByteConfig, cos, sin, h, layer):
    """EVA(h) of a layer for normed h [B, S, E]: q, k, v, the rotary
    embedding, the chunks' summaries, the attention over a window's tokens
    and the earlier windows' summaries, the output projection."""
    dt = cfg.dtype
    impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
    if impl not in (None, "flash", "flash_interpret", "reference"):
        raise ValueError(f"EVA attention has no impl {impl!r}")
    mesh = _kernel_mesh(cfg)
    q = _lm.project_heads(h, layer["wq"], dt)
    k = _lm.project_heads(h, layer["wk"], dt)
    v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"].astype(dt),
                   preferred_element_type=dt)
    rope = partial(rotate_heads, cos2=cos, sin2=sin,
                   interpret=impl == "flash_interpret", mesh=mesh)
    q, k = rope(q), rope(k)
    k_sum, v_sum = _scoped("eva_pool", partial(
        eva_summaries, chunk=cfg.chunk, impl=impl))(
            k, v, layer["eva_mu"], layer["eva_phi"])
    attn = _scoped("eva", partial(
        eva_attention, window=cfg.window, chunk=cfg.chunk, impl=impl,
        mesh=mesh))(q, k, v, k_sum, v_sum)
    return jnp.einsum("bhsd,hde->bse", attn, layer["wo"].astype(dt),
                      preferred_element_type=dt)


def _layer(cfg: EvaByteConfig, cos, sin, x, layer):
    """One layer.  x: [B, S, E] float32."""
    dt, eps = cfg.dtype, cfg.norm_eps
    with jax.named_scope("block/attn"):
        x = x + eva_branch(cfg, cos, sin, offset_norm(
            x, layer["attn_norm"], eps, dt), layer).astype(jnp.float32)
    with jax.named_scope("block/mlp"):
        return x + mlp_branch(cfg, offset_norm(
            x, layer["mlp_norm"], eps, dt), layer).astype(jnp.float32)


def _forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                    cfg: EvaByteConfig):
    """tokens [B, S] -> the final normed hidden state [B, S, E] in
    ``cfg.dtype``."""
    if cfg.pp_microbatches:
        raise NotImplementedError("EVA layers under the pipeline (ROADMAP)")
    if cfg.remat == "mlp_only":
        raise ValueError("remat 'mlp_only' is Llama's; this stack takes the "
                         "modes of _lm.remat")
    with jax.named_scope("embed"):
        x = params["embed"].astype(jnp.float32)[tokens]
    cos, sin = rope_lane_tables(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    layer = _lm.remat(partial(_layer, cfg, cos, sin), cfg.remat)

    def stack(x, blocks, final_norm):
        x, _ = jax.lax.scan(lambda x, w: (layer(x, w), None), x, blocks)
        with jax.named_scope("final_norm"):
            return offset_norm(x, final_norm, cfg.norm_eps, cfg.dtype)

    # Traced on its own, so that the scopes inside keep their names under
    # ``jax.grad`` (``ouro._scoped`` says why).
    return _scoped("stack", stack)(x, params["blocks"], params["final_norm"])


def head_targets_and_masks(batch: Dict[str, jax.Array], heads: int):
    """(targets [B, S, J], float32 masks [B, S, J]) of a batch: tokens
    [B, S] and an optional loss_mask [B, S] over the positions that
    predict.  Head ``j`` (from 1) at position ``t`` predicts token
    ``t + j``: its mask is the position's own, without the row's last ``j``
    positions."""
    tokens = batch["tokens"]
    S = tokens.shape[1]
    base = batch.get("loss_mask")
    base = jnp.ones_like(tokens) if base is None else base
    targets = jnp.stack([jnp.roll(tokens, -j, axis=1)
                         for j in range(1, heads + 1)], axis=-1)
    ahead = jnp.arange(S)[:, None] + jnp.arange(1, heads + 1)[None, :] < S
    return targets, base.astype(jnp.float32)[..., None] * ahead


def loss_and_report(params: Dict[str, Any], batch: Dict[str, jax.Array],
                    cfg: EvaByteConfig, state=None,
                    positions: Optional[jax.Array] = None):
    """(the mean over the heads of each head's masked mean cross-entropy,
    what the step reports of it: ``head_loss`` [J]).  The model carries no
    state; ``state`` is the step's argument for one that does."""
    if positions is not None:
        raise NotImplementedError("EVA on a sharded sequence (ROADMAP)")
    x = _forward_hidden(params, batch["tokens"], cfg)
    targets, masks = head_targets_and_masks(batch, cfg.pred_heads)

    def heads_loss(x, lm_head):
        sums = _lm.token_nll(x, lm_head, targets, cfg.loss_chunks, cfg.dtype,
                             masks)
        head_loss = sums / jnp.maximum(jnp.sum(masks, axis=(0, 1)), 1.0)
        return jnp.mean(head_loss), {"head_loss": head_loss}

    loss, report = _scoped("loss", heads_loss)(x, params["lm_head"])
    return loss, jax.lax.stop_gradient(report)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            cfg: EvaByteConfig,
            positions: Optional[jax.Array] = None) -> jax.Array:
    return loss_and_report(params, batch, cfg, positions=positions)[0]


def forward(params: Dict[str, Any], tokens: jax.Array,
            cfg: EvaByteConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, J, V] float32 (``fp32_logits``):
    head ``j`` of position ``t`` is over byte ``t + j + 1``."""
    x = _forward_hidden(params, tokens, cfg)
    return jnp.einsum("bse,ejv->bsjv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)
