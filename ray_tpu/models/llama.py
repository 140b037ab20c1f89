"""Llama-family decoder-only transformer, TPU-first.

Design notes (vs the reference, which has no in-repo model — it wraps HF
torch models in Train workers, reference: release/train_tests/huggingface):

- Pure pytree params + functions — everything jit/pjit-able, no module
  framework in the hot path.
- Every param/activation dim carries a *logical* axis name; the
  parallel/sharding rule table maps those to mesh axes, so dp/fsdp/tp/sp/ep
  are layout choices, not model edits.
- Layers are stacked and iterated with ``lax.scan`` (one compiled block,
  layer-count-independent compile time) with optional ``jax.checkpoint``
  rematerialization to trade MXU FLOPs for HBM.
- bfloat16 activations/weights with fp32 master params handled by the
  optimizer; matmuls accumulate fp32 via preferred_element_type (MXU-native).
- Attention dispatches to the ops layer: pallas flash on-chip, ring/Ulysses
  over the ``sp`` axis for long context.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import _lm
from ..ops.attention import attention as _attention
from ..ops.attention import reference_attention
from ..ops.norms import rms_norm
from ..ops.ring_attention import ring_attention
from ..ops.rope import rope_lane_tables, rotate_heads
from ..ops.ulysses import ulysses_attention


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    layers: int = 32
    heads: int = 32
    kv_heads: int = 32
    head_dim: int = 128
    mlp_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # "auto" (flash on TPU / reference on CPU), "reference", "flash",
    # "flash_interpret", "ring", "ulysses"
    attention_impl: str = "auto"
    # Mesh axis used by ring/ulysses attention.
    seq_axis: str = "sp"
    # False | True/"full" | "mlp_only" (see _forward_hidden)
    remat: Any = True
    # Pipeline parallelism: number of microbatches (0 = off).  Needs a
    # mesh with pp > 1 and layers % pp == 0; the "layers" logical axis is
    # then sharded over pp (see parallel/pipeline.py).
    pp_microbatches: int = 0
    # Chunked cross-entropy: compute the [B, S, vocab] logits in this
    # many sequence chunks (scan + remat), so only ONE chunk's f32
    # logits are ever resident — the full tensor is ~2.6 GB at
    # bs10/seq2048/vocab32k and dominates peak HBM at the loss.  0 = the
    # single fused logits computation.
    loss_chunks: int = 0

    def replace(self, **kw) -> "LlamaConfig":
        return dataclasses.replace(self, **kw)


def llama_tiny() -> LlamaConfig:
    return LlamaConfig(vocab_size=512, hidden=128, layers=2, heads=4,
                       kv_heads=2, head_dim=32, mlp_dim=256, max_seq_len=256)


def llama_125m() -> LlamaConfig:
    return LlamaConfig(vocab_size=32000, hidden=768, layers=12, heads=12,
                       kv_heads=12, head_dim=64, mlp_dim=2048,
                       max_seq_len=2048)


def llama_1b() -> LlamaConfig:
    return LlamaConfig(vocab_size=32000, hidden=2048, layers=16, heads=16,
                       kv_heads=8, head_dim=128, mlp_dim=5504,
                       max_seq_len=2048)


def llama_7b() -> LlamaConfig:
    return LlamaConfig()  # defaults are 7B


def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    block: Dict[str, Any] = {
        "attn_norm": ("layers", None),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "mlp_norm": ("layers", None),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "blocks": block,
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def init_params(cfg: LlamaConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    # Ten keys, of which ks[5] is not used: a seed gives the weights it
    # always gave.
    ks = jax.random.split(key, 10)
    L, E, H, Hkv, D, M = (cfg.layers, cfg.hidden, cfg.heads, cfg.kv_heads,
                          cfg.head_dim, cfg.mlp_dim)

    def trunc(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(param_dtype)

    blocks: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, E), param_dtype),
        "wq": trunc(ks[1], (L, E, H, D), E),
        "wk": trunc(ks[2], (L, E, Hkv, D), E),
        "wv": trunc(ks[3], (L, E, Hkv, D), E),
        "wo": trunc(ks[4], (L, H, D, E), H * D),
        "mlp_norm": jnp.ones((L, E), param_dtype),
        "w_gate": trunc(ks[6], (L, E, M), E),
        "w_up": trunc(ks[7], (L, E, M), E),
        "w_down": trunc(ks[8], (L, M, E), M),
    }
    return {
        "embed": trunc(ks[0], (cfg.vocab_size, E), E),
        "blocks": blocks,
        "final_norm": jnp.ones((E,), param_dtype),
        "lm_head": trunc(ks[9], (E, cfg.vocab_size), E),
    }


def _attend(cfg: LlamaConfig, q, k, v, positions, rows=False):
    """q, k: [B, H, S, D]; v and the result head-major too, or [B, S, H, D]
    with ``rows`` (``ops.attention``'s).  Dispatch per configured impl.

    ring/ulysses run as shard_map islands inside the GSPMD forward: the
    logically-full q/k/v keep their (dp,fsdp)/tp/sp layout, the island
    rotates K/V (ring) or all-to-alls heads<->seq (ulysses) over the sp
    axis only.
    """
    if cfg.attention_impl in ("ring", "ulysses"):
        from jax.sharding import PartitionSpec as P
        from ..ops.ring_attention import ring_attention_sharded
        from ..ops.ulysses import ulysses_attention_sharded
        from ..parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_TENSOR
        assert not rows                     # these take head-major arrays
        spec = P((AXIS_DATA, AXIS_FSDP), AXIS_TENSOR, cfg.seq_axis, None)
        fn = (ring_attention_sharded if cfg.attention_impl == "ring"
              else ulysses_attention_sharded)
        return fn(q, k, v, axis_name=cfg.seq_axis, causal=True, in_spec=spec)
    if cfg.attention_impl in ("auto", "flash", "flash_interpret",
                              "reference"):
        impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
        return _attention(q, k, v, causal=True, impl=impl,
                          mesh=_kernel_mesh(cfg), rows=rows)
    raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")


def _kernel_mesh(cfg: LlamaConfig):
    """The mesh a Pallas kernel's shard_map island is laid on: none inside
    a pipeline stage, where the island is already manual over pp."""
    from ..parallel.mesh import get_global_mesh
    return None if cfg.pp_microbatches else get_global_mesh()


def _values_as_rows(cfg: LlamaConfig) -> bool:
    """Whether v and attention's result stay where the projections leave
    and take them, [B, S, H, D], the flash kernels addressing them there
    (``rows``): on one device.  Head-major they cost five q-sized copies a
    layer call there (v, the recomputed v, ``do``; ``out`` and ``dv`` for
    the weight gradients: 1.7 % of Yi's step, 2.1 % of Ouro's).  On a mesh
    the flat ``wv`` / ``wo`` products change the partitioner's rings (six
    more ``collective-permute``s a layer in Mistral's step on four chips,
    which then loses 1.4 %: 2,672.5 -> 2,635.6 tokens/s/chip; PERF.md,
    PR 49), so a mesh keeps the head-major arrangement, as ring / ulysses
    do, which need a mesh and take nothing else."""
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    return (cfg.attention_impl not in ("ring", "ulysses")
            and (mesh is None or mesh.size == 1))


def flash_keep(cfg: LlamaConfig, tokens, calls: int) -> tuple:
    """``_lm.flash_keep`` for a stack of ``calls`` calls of ``_attend`` a
    step on tokens [B, S] (models/ouro.py's too): a call's result as a
    device holds it, the rows over the mesh's (dp, fsdp) and the heads over
    tp, as ``ops.attention``'s island splits them.  Nothing is kept where
    the calls are not flash's (ring / ulysses) or run inside a pipeline
    stage, whose microbatches in flight are not counted here."""
    mesh = _kernel_mesh(cfg)
    over = lambda *axes: math.prod(mesh.shape.get(a, 1) for a in axes) \
        if mesh is not None else 1
    flash = (cfg.attention_impl in ("auto", "flash", "flash_interpret")
             and not cfg.pp_microbatches)
    return _lm.flash_keep(
        cfg.remat, calls if flash else 0,
        (*tokens.shape, cfg.heads // over("tp"), cfg.head_dim), cfg.dtype,
        chips=over("dp", "fsdp"))


def attention_branch(cfg: LlamaConfig, cos, sin, positions, h, layer):
    """Attn(h) of a layer for normed h [B, S, E]: q, k, v, rotary embedding,
    the configured attention, the output projection.  ``cos`` / ``sin`` are
    ``rope_lane_tables``'; q and k leave their projections as [B, S, H, D]
    and ``rotate_heads`` places them head-major on the way.  On one device
    (``_values_as_rows``) v and the attention's result lie as rows too: the
    flash kernels read and write them in place and nothing is transposed
    between a projection and a kernel but by the rotary pair.  The residual
    and the norms round it are the caller's (here ``_attn_half``;
    models/ouro.py puts a second norm behind it)."""
    dt = cfg.dtype
    q = _lm.project_heads(h, layer["wq"], dt)
    k = _lm.project_heads(h, layer["wk"], dt)
    rope = partial(rotate_heads, cos2=cos, sin2=sin, positions=positions,
                   interpret=cfg.attention_impl == "flash_interpret",
                   mesh=_kernel_mesh(cfg))
    q, k = rope(q), rope(k)
    if _values_as_rows(cfg):
        v = _lm.project_heads(h, layer["wv"], dt)
        return _lm.merge_heads(_attend(cfg, q, k, v, positions, rows=True),
                               layer["wo"], dt)
    v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"].astype(dt),
                   preferred_element_type=dt)
    return jnp.einsum("bhsd,hde->bse", _attend(cfg, q, k, v, positions),
                      layer["wo"].astype(dt), preferred_element_type=dt)


def mlp_branch(cfg: LlamaConfig, h, layer):
    """SwiGLU(h) of a layer for normed h [B, S, E]."""
    dt = cfg.dtype
    gate = jnp.einsum("bse,em->bsm", h, layer["w_gate"].astype(dt),
                      preferred_element_type=dt)
    up = jnp.einsum("bse,em->bsm", h, layer["w_up"].astype(dt),
                    preferred_element_type=dt)
    return jnp.einsum("bsm,me->bse", jax.nn.silu(gate) * up,
                      layer["w_down"].astype(dt),
                      preferred_element_type=dt)


@jax.named_scope("block/attn")
def _attn_half(cfg: LlamaConfig, cos, sin, positions, x, layer):
    """Attention residual branch. x: [B, S, E] -> [B, S, E]."""
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    return x + attention_branch(cfg, cos, sin, positions, h, layer)


@jax.named_scope("block/mlp")
def _mlp_half(cfg: LlamaConfig, x, layer):
    """MLP residual branch. x: [B, S, E] -> [B, S, E]."""
    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    return x + mlp_branch(cfg, h, layer)


def _block(cfg: LlamaConfig, cos, sin, positions, x, layer):
    """One transformer block. x: [B, S, E]."""
    x = _attn_half(cfg, cos, sin, positions, x, layer)
    return _mlp_half(cfg, x, layer)


def _forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                    cfg: LlamaConfig,
                    positions: Optional[jax.Array] = None):
    """tokens: [B, S] int32 -> final hidden [B, S, E]; forward applies the
    lm_head on top.

    ``positions``: absolute positions [S] (defaults to arange; sequence-
    sharded callers pass their shard's global positions).
    """
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
    cos, sin = rope_lane_tables(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)

    # remat modes (see _lm.remat); "mlp_only" = keep the attention half's
    # residuals (incl. the flash kernel's q/k/v/out/LSE — the quadratic part
    # is never recomputed) and recompute only the cheap MLP half: the
    # throughput sweet spot when HBM allows.
    if cfg.remat == "mlp_only":
        mlp = _lm.remat(partial(_mlp_half, cfg), "full")

        def block(x, layer):
            return mlp(_attn_half(cfg, cos, sin, positions, x, layer), layer)
    else:
        block = _lm.remat(partial(_block, cfg, cos, sin, positions),
                          cfg.remat, flash_keep(cfg, tokens, cfg.layers))

    def scan_body(x, layer):
        return block(x, layer), None

    if cfg.pp_microbatches:
        # Microbatched pipeline over the pp mesh axis: each stage scans its
        # resident layer shard; activations hop stage-to-stage over ICI.
        from ..parallel.mesh import get_global_mesh
        from ..parallel.pipeline import pipeline_blocks
        mesh = get_global_mesh()
        if mesh is None or mesh.shape.get("pp", 1) <= 1:
            raise ValueError(
                "cfg.pp_microbatches > 0 needs a global mesh with pp > 1")
        if cfg.attention_impl in ("ring", "ulysses"):
            raise NotImplementedError(
                "sequence-parallel attention inside a pipeline stage")

        def stage_body(stage_layers, h):
            h, _ = jax.lax.scan(scan_body, h, stage_layers)
            return h

        x = pipeline_blocks(params["blocks"], x, stage_body,
                            num_microbatches=cfg.pp_microbatches, mesh=mesh)
    else:
        x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            positions: Optional[jax.Array] = None) -> jax.Array:
    x = _forward_hidden(params, tokens, cfg, positions)
    return jnp.einsum("bse,ev->bsv", x,
                      params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            cfg: LlamaConfig,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross-entropy.  batch: tokens [B,S], loss_mask [B,S]."""
    x = _forward_hidden(params, batch["tokens"], cfg, positions)
    return _lm.next_token_loss(x, params["lm_head"], batch, cfg.loss_chunks,
                               cfg.dtype)


def num_params(cfg: LlamaConfig) -> int:
    L, E, H, Hkv, D, M, V = (cfg.layers, cfg.hidden, cfg.heads, cfg.kv_heads,
                             cfg.head_dim, cfg.mlp_dim, cfg.vocab_size)
    per_layer = E * H * D + 2 * E * Hkv * D + H * D * E + 2 * E + 3 * E * M
    return V * E + L * per_layer + E + E * V
