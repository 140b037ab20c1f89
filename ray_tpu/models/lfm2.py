"""The ``lfm2_moe`` decoder (Liquid AI LFM2-24B-A2B): a stack whose layers pair
an operator chosen by a list with a feed-forward part.

What it has that no other model here has:

- **A double-gated short convolution as three mixers in four**
  (``layer_types``: ``conv`` or ``full_attention``).  A ``conv`` layer's
  operator is ``[B ; C ; u] = x W_in``, ``v = conv_K(B * u)`` (causal,
  depthwise, ``conv_L_cache`` = 3 taps, no bias, no activation), ``(C * v)
  W_out``: ``ops/ssm.gated_short_conv``.  It carries two tokens of state a
  layer where attention carries a row's keys and values.
- **Attention at head size 64**: grouped-query (32 query heads on 8 key
  heads), an RMSNorm on each query and key head *before* the rotary
  embedding, which turns the whole head (halves of 32).
- **Expert layers with no shared expert**: ``num_dense_layers`` leading
  layers whose F is a SwiGLU, the others ``sum_{e in top} w_e Expert_e(x)``
  alone (``models/afmoe._moe`` on ``ops/moe.py``: sigmoid router, dropless
  held experts, the selection bias as state, ``sum + 1e-6`` under the
  weights; a layer may hold a share of its experts).
- **A tied head**: the logits are by the embedding's own matrix.  ``params``
  has no ``lm_head``; the one ``embed`` leaf [V, E] gets the head's gradient
  and the lookup's scatter-add in one step, and one pair of moments.

A layer is ``h = x + Op(N(x; g_op))``, ``x' = h + F(N(h; g_ffn))``: two norms,
no post-norms, both sublayers in every layer.  The layers are unrolled (the
two kinds of operator have different weights, so one scanned body cannot
serve both), each under the remat ``layer_rows`` rows at a time; a layer's
weights are a dictionary of their own in ``params["layers"]``, as
``models/nemotron_h.py``'s.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import _lm, afmoe
from ..ops import ssm
from ..ops.attention import attention as _attention
from ..ops.norms import rms_norm
from ..ops.rope import rope_lane_tables, rotate_heads

CONV, FULL = "conv", "full_attention"


def _published_layer_types(layers: int) -> Tuple[str, ...]:
    """LFM2-24B-A2B's: attention at layers 2, 6, ..., a period of four."""
    return tuple(FULL if i % 4 == 2 else CONV for i in range(layers))


@dataclass(frozen=True)
class Lfm2Config:
    """Defaults are LFM2-24B-A2B's published ``config.json``."""
    vocab_size: int = 65536
    hidden: int = 2048
    layers: int = 40
    layer_types: Optional[Tuple[str, ...]] = None   # None = the published
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    conv_kernel: int = 3                # ``conv_L_cache``
    mlp_dim: int = 11776                # the dense layers' SwiGLU
    moe_mlp_dim: int = 1536             # an expert's
    num_experts: int = 64               # the router's width
    experts_held: Optional[int] = None  # None = all of them
    held_start: int = 0
    top_k: int = 4
    num_dense_layers: int = 2
    route_scale: float = 1.0            # ``routed_scaling_factor``
    route_norm: bool = True             # ``norm_topk_prob``
    route_eps: float = 1e-6             # added to the chosen scores' sum
    bias_update_rate: float = 1e-3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    # "auto" (flash on TPU / reference on CPU), "reference", "flash",
    # "flash_interpret"
    attention_impl: str = "auto"
    moe_impl: Optional[str] = None      # ops/moe.grouped_matmul
    remat: Any = True                   # _lm.remat
    layer_rows: Optional[int] = None    # as AfmoeConfig's
    loss_chunks: int = 0
    pp_microbatches: int = 0            # refused: see _refuse_a_mesh

    def replace(self, **kw) -> "Lfm2Config":
        return dataclasses.replace(self, **kw)

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = self.layer_types or _published_layer_types(self.layers)
        if len(kinds) < self.layers or set(kinds) - {CONV, FULL}:
            raise ValueError(f"layer_types does not name {self.layers} "
                             f"layers of {CONV} / {FULL}: {kinds}")
        return tuple(kinds[:self.layers])

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_layers(self) -> int:
        return self.layers - self.num_dense_layers


def lfm2_tiny(**kw) -> Lfm2Config:
    """A CPU-test size that keeps what the code must tell apart: a head size
    that is not 128, four query heads a key head, 8 experts with 4 a token
    and no shared one, both kinds of operator, one dense layer."""
    return Lfm2Config(**{**dict(
        vocab_size=256, hidden=64, layers=5,
        layer_types=(CONV, FULL, CONV, CONV, FULL), heads=8, kv_heads=2,
        head_dim=8, mlp_dim=96, moe_mlp_dim=32, num_experts=8, top_k=4,
        num_dense_layers=1, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference", remat=False), **kw})


# ------------------------------------------------------------- parameters

def _layer_shapes(cfg: Lfm2Config, kind: str, dense: bool) -> Dict[str, Any]:
    E, K = cfg.hidden, cfg.conv_kernel
    if kind == CONV:
        op = {"w_in": ((E, 3 * E), E), "conv_w": ((K, E), K),
              "w_out": ((E, E), E)}
    else:
        H, Hk, D = cfg.heads, cfg.kv_heads, cfg.head_dim
        op = {"wq": ((E, H, D), E), "wk": ((E, Hk, D), E),
              "wv": ((E, Hk, D), E), "q_norm": ((D,), 0),
              "k_norm": ((D,), 0), "wo": ((H, D, E), H * D)}
    if dense:
        M = cfg.mlp_dim
        f = {"w_gate": ((E, M), E), "w_up": ((E, M), E),
             "w_down": ((M, E), M)}
    else:
        Me, X, Xh = cfg.moe_mlp_dim, cfg.num_experts, cfg.held
        f = {"router": ((E, X), E), "w_gate": ((Xh, E, Me), E),
             "w_up": ((Xh, E, Me), E), "w_down": ((Xh, Me, E), Me)}
    return {"op_norm": ((E,), 0), **op, "ffn_norm": ((E,), 0), **f}


_OP_AXES = {
    CONV: {"w_in": ("embed", "mlp"), "conv_w": (None, None),
           "w_out": ("mlp", "embed")},
    FULL: {"wq": ("embed", "heads", "head_dim"),
           "wk": ("embed", "kv_heads", "head_dim"),
           "wv": ("embed", "kv_heads", "head_dim"),
           "q_norm": (None,), "k_norm": (None,),
           "wo": ("heads", "head_dim", "embed")}}
_F_AXES = {
    True: {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
           "w_down": ("mlp", "embed")},
    False: {"router": ("embed", None), "w_gate": ("expert", "embed", "mlp"),
            "w_up": ("expert", "embed", "mlp"),
            "w_down": ("expert", "mlp", "embed")}}


def _stack(cfg: Lfm2Config):
    """(kind, whether its F is dense) down the stack."""
    return [(kind, i < cfg.num_dense_layers)
            for i, kind in enumerate(cfg.kinds)]


def param_shapes(cfg: Lfm2Config) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a norm weight, which starts at one).
    No ``lm_head``: the head is ``embed``'s own matrix."""
    V, E = cfg.vocab_size, cfg.hidden
    return {"embed": ((V, E), E),
            "layers": [_layer_shapes(cfg, kind, dense)
                       for kind, dense in _stack(cfg)],
            "final_norm": ((E,), 0)}


def param_logical_axes(cfg: Lfm2Config) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    return {"embed": ("vocab", "embed"),
            "layers": [{"op_norm": (None,), **_OP_AXES[kind],
                        "ffn_norm": (None,), **_F_AXES[dense]}
                       for kind, dense in _stack(cfg)],
            "final_norm": (None,)}


def init_params(cfg: Lfm2Config, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    return _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)


def num_params(cfg: Lfm2Config) -> int:
    """The tied matrix counts once."""
    return _lm.count_params(param_shapes(cfg))


def init_state(cfg: Lfm2Config) -> Dict[str, jax.Array]:
    """The routers' selection bias (``expert_bias``), float32 [expert layers,
    experts]: state that no optimizer touches (``models/afmoe.py``)."""
    return {"bias": jnp.zeros((cfg.expert_layers, cfg.num_experts),
                              jnp.float32)}


# ------------------------------------------------------------------ layers

def _conv(cfg: Lfm2Config, x, layer):
    """Op of a ``conv`` layer on the normed stream x [B, S, E]."""
    dt = cfg.dtype
    with jax.named_scope("block/conv/proj"):
        bcu = jnp.einsum("bse,ef->bsf", x, layer["w_in"].astype(dt),
                         preferred_element_type=dt)
    B, C, u = jnp.split(bcu, 3, axis=-1)
    v = ssm.gated_short_conv(B, C, u, layer["conv_w"])
    with jax.named_scope("block/conv/proj"):
        return jnp.einsum("bsf,fe->bse", v, layer["w_out"].astype(dt),
                          preferred_element_type=dt)


@jax.named_scope("block/attn")
def _attn(cfg: Lfm2Config, cos, sin, x, layer):
    """Op of a ``full_attention`` layer: grouped-query, causal, each query
    and key head normed and then rotated."""
    dt, eps = cfg.dtype, cfg.norm_eps
    impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
    q = _lm.project_heads(x, layer["wq"], dt)
    k = _lm.project_heads(x, layer["wk"], dt)
    v = jnp.einsum("bse,ehd->bhsd", x, layer["wv"].astype(dt),
                   preferred_element_type=dt)
    with jax.named_scope("qk_norm"):
        q = rms_norm(q, layer["q_norm"], eps)
        k = rms_norm(k, layer["k_norm"], eps)
    with jax.named_scope("rope"):
        interpret = impl == "flash_interpret"
        q = rotate_heads(q, cos, sin, interpret=interpret)
        k = rotate_heads(k, cos, sin, interpret=interpret)
    o = _attention(q, k, v, causal=True, impl=impl)
    return jnp.einsum("bhsd,hde->bse", o, layer["wo"].astype(dt),
                      preferred_element_type=dt)


def _layer(cfg: Lfm2Config, kind: str, cos, sin, x, layer, bias=None):
    """One layer: (x', an expert layer's loads as ``afmoe._moe``'s; None
    for a dense layer, which ``bias is None`` marks)."""
    h = rms_norm(x, layer["op_norm"], cfg.norm_eps)
    h = x + (_conv(cfg, h, layer) if kind == CONV
             else _attn(cfg, cos, sin, h, layer))
    f = rms_norm(h, layer["ffn_norm"], cfg.norm_eps)
    if bias is None:
        with jax.named_scope("block/mlp"):
            f, loads = afmoe._swiglu(f, layer["w_gate"], layer["w_up"],
                                     layer["w_down"], cfg.dtype), None
    else:
        f, loads = afmoe._moe(cfg, f, layer, bias, route_eps=cfg.route_eps)
    return h + f, loads


def _run(cfg: Lfm2Config, kind: str, cos, sin, x, layer, bias=None,
         keep=()):
    """The layer under the remat, ``layer_rows`` rows at a time (as
    ``afmoe``'s); ``keep``: ``_lm.remat``'s, the stack's."""
    one = _lm.remat(lambda x, layer, bias: _layer(cfg, kind, cos, sin, x,
                                                  layer, bias), cfg.remat,
                    keep)
    B = x.shape[0]
    n = min(cfg.layer_rows or B, B)
    if B % n:
        raise ValueError(f"a batch of {B} rows does not split into groups "
                         f"of layer_rows={n}")
    if n == B:
        return one(x, layer, bias)
    y, loads = jax.lax.map(lambda rows: one(rows, layer, bias),
                           x.reshape((B // n, n) + x.shape[1:]))
    if loads is not None:
        loads = {"counts": jnp.sum(loads["counts"], axis=0),
                 "dropped": jnp.sum(loads["dropped"]),
                 "sliced": jnp.sum(loads["sliced"]),
                 "top": loads["top"].reshape(-1, cfg.top_k)}
    return y.reshape(x.shape), loads


def _refuse_a_mesh(cfg: Lfm2Config) -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "lfm2 on a mesh: the exchange of an expert-parallel group is "
            "not built (ROADMAP M8)")
    if cfg.pp_microbatches:
        raise NotImplementedError(
            "lfm2 with pp_microbatches: its layers are not one stack of "
            "like layers that a pipeline stage could slice (ROADMAP M4)")


def _forward_hidden(params, state, tokens, cfg: Lfm2Config):
    """tokens [B, S] -> (final hidden [B, S, E] after the final norm, the
    expert layers' loads {"counts" [Le, X], "dropped" [Le], "sliced" [Le],
    "top" [Le, B*S, k]})."""
    _refuse_a_mesh(cfg)
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    cos, sin = rope_lane_tables(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    keep = _lm.flash_keep(
        cfg.remat, sum(kind == FULL for kind, _ in _stack(cfg)),
        (*tokens.shape, cfg.heads, cfg.head_dim), cfg.dtype)
    loads = []
    for (kind, dense), layer in zip(_stack(cfg), params["layers"]):
        bias = None if dense else state["bias"][len(loads)]
        x, report = _run(cfg, kind, cos, sin, x, layer, bias, keep)
        if not dense:
            loads.append(report)
    if loads:
        loads = jax.tree.map(lambda *a: jnp.stack(a), *loads)
    else:
        loads = {"counts": jnp.zeros((0, cfg.num_experts), jnp.int32),
                 "dropped": jnp.zeros((0,), jnp.int32),
                 "sliced": jnp.zeros((0,), jnp.int32),
                 "top": jnp.zeros((0, tokens.size, cfg.top_k), jnp.int32)}
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, loads


def forward(params, tokens, cfg: Lfm2Config, state=None) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32, by the embedding's own
    matrix."""
    x, _ = _forward_hidden(params, state or init_state(cfg), tokens, cfg)
    return jnp.einsum("bse,ve->bsv", x, params["embed"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def loss_and_report(params, batch, cfg: Lfm2Config, state=None):
    """What the train step differentiates (parallel.spmd): the next-token
    cross-entropy under the tied head (no auxiliary term), and the expert
    layers' loads, which ``update_state`` turns into the step's metrics."""
    x, loads = _forward_hidden(params, state or init_state(cfg),
                               batch["tokens"], cfg)
    # Traced on its own, so that the scope ``loss`` stays a scope in the
    # backward's operations too (models/ouro._scoped has the reason).  The
    # head is the embedding read transposed inside the call: nothing of it
    # lives past the call, and the leaf's gradient is the sum of both uses.
    loss = jax.jit(lambda x, embed, batch: _lm.next_token_loss(
        x, embed.T, batch, cfg.loss_chunks, cfg.dtype))(x, params["embed"],
                                                         batch)
    return loss, jax.lax.stop_gradient(loads)


def loss_fn(params, batch, cfg: Lfm2Config, state=None) -> jax.Array:
    return loss_and_report(params, batch, cfg, state)[0]


def update_state(state, loads, cfg: Lfm2Config):
    """(the state after a step with these loads, the step's metrics):
    ``afmoe``'s."""
    return afmoe.update_state(state, loads, cfg)
