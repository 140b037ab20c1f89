"""The ``granitemoehybrid`` decoder without experts (IBM Granite-4.0-H-Micro):
a hybrid stack whose every layer is a mixer AND a feed-forward, trained on
packed rows.

What it has that no other model here has:

- **A mixer and a feed-forward in every layer**, the kinds read from the
  config's ``layer_types``: ``mamba`` (nine of ten) or ``attention``, then a
  SwiGLU of ``shared_intermediate_size`` under a norm of its own.
  ``models/nemotron_h.py``'s layers are one sublayer each.
- **One B and C for all the mixer's heads** (``mamba_n_groups`` 1): 64 heads
  of 64 channels on one state group, and the gated norm over all 4,096
  channels at once.  The mixer's body is ``nemotron_h._mixer``, as it is;
  ``ops/ssm.py``'s kernels take the wide group in slices of its heads.
- **Four multipliers** (muP): the embedding times ``embedding_multiplier``,
  every sublayer's output times ``residual_multiplier`` before it joins the
  stream, attention's scores times ``attention_multiplier`` (1 / head size,
  not its root), the logits over ``logits_scaling``.  The head is the
  embedding's own matrix.
- **Attention without positions** (``position_embedding_type`` nope): the
  mixers carry position.
- **Packed rows**: a batch may hold ``segment_ids`` [B, S] beside ``tokens``
  and ``loss_mask``, a run of equal ids a document.  The convolution reads
  zero before a document's first token, the state starts from zero there,
  and a query sees its own document's keys alone (``ops/ssm.py``,
  ``ops/attention.py``); the step reports the documents a row, the share of
  the causal triangle their pairs keep, and the chunks a boundary cuts.
  Without the key a row is one document.

The layers are unrolled (attention stands at layer 5 of a period of 10), each
under the remat ``layer_rows`` rows at a time, as ``nemotron_h.py``'s.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import _lm, llama, nemotron_h
from ..ops.attention import attention as _attention
from ..ops.norms import rms_norm
from ..ops.ssm import documents

MAMBA, ATTENTION = "mamba", "attention"

#: Granite-4.0-H-Micro's ``layer_types``: attention at 5, 15, 25, 35
PUBLISHED_LAYER_TYPES = tuple(
    ATTENTION if i % 10 == 5 else MAMBA for i in range(40))


@dataclass(frozen=True)
class GraniteHybridConfig:
    """Defaults are Granite-4.0-H-Micro's published ``config.json``."""
    vocab_size: int = 100352
    hidden: int = 2048
    layers: int = 40
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES  # its first ``layers``
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    mlp_dim: int = 8192                 # ``shared_intermediate_size``
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1                 # ``mamba_n_groups``: B, C, the norm
    conv_kernel: int = 4
    chunk_size: int = 256
    time_step_min: float = 0.001        # the range ``dt_bias`` starts in
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    # "auto" (flash on TPU / reference on CPU), "reference", "flash",
    # "flash_interpret"
    attention_impl: str = "auto"
    remat: Any = True                   # _lm.remat
    layer_rows: Optional[int] = None    # as NemotronHConfig's
    loss_chunks: int = 0
    pp_microbatches: int = 0            # refused: see _refuse_a_mesh

    def replace(self, **kw) -> "GraniteHybridConfig":
        return dataclasses.replace(self, **kw)

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = tuple(self.layer_types[:self.layers])
        if len(kinds) < self.layers or set(kinds) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types does not name {self.layers} "
                             f"layers of mamba / attention: {kinds!r}")
        return kinds

    @property
    def mamba_dim(self) -> int:
        """Channels of a mixer's X, z and y: heads * head size (the
        published ``mamba_expand`` * hidden)."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution sees: X, B and C."""
        return self.mamba_dim + 2 * self.ssm_groups * self.ssm_state


def granite_hybrid_tiny(**kw) -> GraniteHybridConfig:
    """A CPU-test size that keeps what the code must tell apart: several
    heads on ONE state group, a head size that is not the state size, four
    query heads a key head, both kinds of layer, multipliers that are not
    1."""
    return GraniteHybridConfig(**{**dict(
        vocab_size=256, hidden=64, layers=3,
        layer_types=(MAMBA, ATTENTION, MAMBA), heads=8, kv_heads=2,
        head_dim=16, mlp_dim=96, mamba_heads=4, mamba_head_dim=8,
        ssm_state=16, chunk_size=16, max_seq_len=128, dtype=jnp.float32,
        attention_impl="reference", remat=False), **kw})


# ------------------------------------------------------------- parameters

def _layer_shapes(cfg: GraniteHybridConfig, kind: str) -> Dict[str, Any]:
    E, M, d, H = cfg.hidden, cfg.mlp_dim, cfg.mamba_dim, cfg.mamba_heads
    if kind == MAMBA:
        mixer = {"w_in": ((E, d + cfg.conv_dim + H), E),
                 "conv_w": ((cfg.conv_kernel, cfg.conv_dim), cfg.conv_kernel),
                 "conv_b": ((cfg.conv_dim,), cfg.conv_kernel),
                 "A_log": ((H,), 0), "dt_bias": ((H,), 0), "D": ((H,), 0),
                 "gate_norm": ((d,), 0), "w_out": ((d, E), d)}
    else:
        Hq, K, D = cfg.heads, cfg.kv_heads, cfg.head_dim
        mixer = {"wq": ((E, Hq, D), E), "wk": ((E, K, D), E),
                 "wv": ((E, K, D), E), "wo": ((Hq, D, E), Hq * D)}
    # the published ``input_linear`` [2 M, E] as its two halves
    return {"norm": ((E,), 0), **mixer, "mlp_norm": ((E,), 0),
            "w_gate": ((E, M), E), "w_up": ((E, M), E),
            "w_down": ((M, E), M)}


_MIXER_AXES = {
    MAMBA: {"w_in": ("embed", "mlp"), "conv_w": (None, None),
            "conv_b": (None,), "A_log": (None,), "dt_bias": (None,),
            "D": (None,), "gate_norm": (None,), "w_out": ("mlp", "embed")},
    ATTENTION: {"wq": ("embed", "heads", "head_dim"),
                "wk": ("embed", "kv_heads", "head_dim"),
                "wv": ("embed", "kv_heads", "head_dim"),
                "wo": ("heads", "head_dim", "embed")}}


def param_shapes(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant).
    No ``lm_head``: the head is ``embed``'s own matrix.  ``A_log`` and
    ``dt_bias`` get Mamba-2's random start in ``init_params``."""
    return {"embed": ((cfg.vocab_size, cfg.hidden), cfg.hidden),
            "layers": [_layer_shapes(cfg, kind) for kind in cfg.kinds],
            "final_norm": ((cfg.hidden,), 0)}


def param_logical_axes(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    return {"embed": ("vocab", "embed"),
            "layers": [{"norm": (None,), **_MIXER_AXES[kind],
                        "mlp_norm": (None,), "w_gate": ("embed", "mlp"),
                        "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
                       for kind in cfg.kinds],
            "final_norm": (None,)}


def init_params(cfg: GraniteHybridConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    params = _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)
    for i, kind in enumerate(cfg.kinds):
        if kind == MAMBA:
            start = nemotron_h.ssm_start(cfg, jax.random.fold_in(key, i))
            params["layers"][i] |= {k: v.astype(param_dtype)
                                    for k, v in start.items()}
    return params


def num_params(cfg: GraniteHybridConfig) -> int:
    """The tied matrix counts once."""
    return _lm.count_params(param_shapes(cfg))


# ------------------------------------------------------------------ layers

@jax.named_scope("block/attn")
def _attn(cfg: GraniteHybridConfig, x, layer, segment_ids=None):
    """F of an attention layer: grouped-query, causal, no positions, the
    scores times ``attention_multiplier``; inside a document."""
    dt = cfg.dtype
    impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
    q = _lm.project_heads(x, layer["wq"], dt)
    k = _lm.project_heads(x, layer["wk"], dt)
    v = jnp.einsum("bse,ehd->bhsd", x, layer["wv"].astype(dt),
                   preferred_element_type=dt)
    o = _attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), v,
                   causal=True, scale=cfg.attention_multiplier, impl=impl,
                   segment_ids=segment_ids)
    return jnp.einsum("bhsd,hde->bse", o, layer["wo"].astype(dt),
                      preferred_element_type=dt)


def _layer(cfg: GraniteHybridConfig, kind: str, x, layer, segment_ids=None):
    """One layer, ``x + r F(N(x))`` then ``x + r SwiGLU(N(x))`` with r the
    residual multiplier: (x', a mixer's (chunk carry, chunks a boundary
    cuts), zeros of an attention layer)."""
    r = cfg.residual_multiplier
    h = rms_norm(x, layer["norm"], cfg.norm_eps)
    if kind == MAMBA:
        f, carry = nemotron_h._mixer(cfg, h, layer, segment_ids)
        carry = carry if segment_ids is not None else (
            carry, jnp.zeros((), jnp.float32))
    else:
        f = _attn(cfg, h, layer, segment_ids)
        carry = (jnp.ones((), jnp.float32), jnp.zeros((), jnp.float32))
    x = x + r * f
    with jax.named_scope("block/mlp"):
        h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        return x + r * llama.mlp_branch(cfg, h, layer), carry


def _run(cfg: GraniteHybridConfig, kind: str, x, layer, segment_ids, keep):
    """The layer under the remat, ``layer_rows`` rows at a time.  Traced on
    its own (``jax.jit``; the compiler inlines the call), so that the
    layer's scopes stay scopes in the first forward's operations too where
    a batch is one group of rows and no loop stands between the gradient
    and the layer (``models/ouro._scoped`` has the reason)."""
    one = jax.jit(_lm.remat(
        lambda x, ids, layer: _layer(cfg, kind, x, layer, ids), cfg.remat,
        keep))
    B = x.shape[0]
    n = min(cfg.layer_rows or B, B)
    if B % n:
        raise ValueError(f"a batch of {B} rows does not split into groups "
                         f"of layer_rows={n}")
    if n == B:
        return one(x, segment_ids, layer)
    groups = lambda a: a.reshape((B // n, n) + a.shape[1:])
    if segment_ids is None:
        y, (carry, cut) = jax.lax.map(lambda rows: one(rows, None, layer),
                                      groups(x))
    else:
        y, (carry, cut) = jax.lax.map(lambda a: one(*a, layer),
                                      (groups(x), groups(segment_ids)))
    return y.reshape(x.shape), (jnp.mean(carry), jnp.sum(cut))


def _refuse_a_mesh(cfg: GraniteHybridConfig) -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "granite_hybrid on a mesh: a scan split over heads or handing "
            "its state across a split row, and segment ids in attention's "
            "island, are not built (ROADMAP M6, M8)")
    if cfg.pp_microbatches:
        raise NotImplementedError(
            "granite_hybrid with pp_microbatches: its layers are not one "
            "stack of like layers that a pipeline stage could slice "
            "(ROADMAP M4)")


def packing(segment_ids):
    """What a step reports of its rows' packing: {``pack_documents_a_row``:
    the mean documents a row, ``pack_pairs_share``: the share of the S x S
    square that the pairs inside documents are, sum of len^2 / S^2 (a row of
    one document: 1)}.  float32 scalars."""
    B, S = segment_ids.shape
    doc = documents(segment_ids)
    # A token's position in its document is the tokens since the last start.
    at = jnp.arange(S)
    first = jax.lax.cummax(jnp.where(
        jnp.pad(doc[:, 1:] != doc[:, :-1], ((0, 0), (1, 0))), at, 0), axis=1)
    lengths_seen = (at - first + 1).astype(jnp.float32)   # 1 .. len
    # sum of len^2 = sum over tokens of (2 * position + 1)
    pairs = jnp.sum(2.0 * lengths_seen - 1.0) / (B * float(S) * S)
    return {"pack_documents_a_row": jnp.mean(doc[:, -1] + 1.0),
            "pack_pairs_share": pairs}


def _forward_hidden(params, tokens, cfg: GraniteHybridConfig,
                    segment_ids=None):
    """tokens [B, S] -> (final hidden [B, S, E] after the final norm, the
    mixers' mean chunk carry, the chunks of all mixers a boundary cuts)."""
    _refuse_a_mesh(cfg)
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens] \
            * cfg.embedding_multiplier
    keep = _lm.flash_keep(
        cfg.remat, sum(kind == ATTENTION for kind in cfg.kinds),
        (*tokens.shape, cfg.heads, cfg.head_dim), cfg.dtype)
    carries, cuts = [], []
    for kind, layer in zip(cfg.kinds, params["layers"]):
        x, (carry, cut) = _run(cfg, kind, x, layer, segment_ids, keep)
        if kind == MAMBA:
            carries.append(carry)
            cuts.append(cut)
    carry = jnp.mean(jnp.stack(carries)) if carries \
        else jnp.ones((), jnp.float32)
    cut = jnp.sum(jnp.stack(cuts)) if cuts else jnp.zeros((), jnp.float32)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, carry, cut


def forward(params, tokens, cfg: GraniteHybridConfig,
            segment_ids=None) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32, by the embedding's own
    matrix over ``logits_scaling``."""
    x, *_ = _forward_hidden(params, tokens, cfg, segment_ids)
    return jnp.einsum("bse,ve->bsv", x, params["embed"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32) / cfg.logits_scaling


def loss_and_report(params, batch, cfg: GraniteHybridConfig, state=None):
    """What the train step differentiates (parallel.spmd): the next-token
    cross-entropy under the tied, scaled head, and what joins the step's
    metrics: the mixers' chunk carry and, of a batch with ``segment_ids``,
    its packing."""
    ids = batch.get("segment_ids")
    x, carry, cut = _forward_hidden(params, batch["tokens"], cfg, ids)
    # Traced on its own, so that the scope ``loss`` stays a scope in the
    # backward's operations too (models/ouro._scoped has the reason).  The
    # head is the embedding read transposed inside the call; the logits'
    # scale goes onto the hidden states, [B, S, E] and not [B, S, V].
    loss = jax.jit(lambda x, embed, batch: _lm.next_token_loss(
        x * (1.0 / cfg.logits_scaling), embed.T, batch, cfg.loss_chunks,
        cfg.dtype))(x, params["embed"],
                    {k: v for k, v in batch.items() if k != "segment_ids"})
    report = {"ssm_chunk_carry": carry}
    if ids is not None:
        report |= {"ssm_chunks_with_boundary": cut, **packing(ids)}
    return loss, jax.lax.stop_gradient(report)


def loss_fn(params, batch, cfg: GraniteHybridConfig, state=None) -> jax.Array:
    return loss_and_report(params, batch, cfg, state)[0]
