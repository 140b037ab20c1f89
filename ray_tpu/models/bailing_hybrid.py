"""The ``bailing_hybrid`` decoder (inclusionAI Ling-3.0-flash): a stack of
two kinds of mixer on one pre-norm residual stream, each layer either dense
or sparse.

What it has that no other model here has:

- **Kimi Delta Attention** (KDA; Kimi Linear, arXiv 2510.26692) in five
  layers of six (``layer_group_size``: layer ``i`` is latent attention
  where ``(i + 1) % 6 == 0``): q, k and v through a causal depthwise
  convolution of 4 taps and a SiLU (``ops/ssm.causal_conv``, Nemotron's, one
  call over the three side by side), q and k normalised to unit length a
  head, a write strength ``beta = sigmoid(x W_beta)`` a head, a decay a
  CHANNEL ``g = kda_lower_bound * sigmoid(exp(A_log) (x W_a + dt_bias))``
  in (-5, 0) (``kda_safe_gate``; ONE full projection, ``no_kda_lora``), the
  gated delta rule over a matrix state a head (``ops/kda.py``), an RMSNorm
  over each head's 128 channels with one learned weight, an output gate
  ``sigmoid(x W_g)`` and the out-projection.  No positional term: the
  recurrence carries position.  Every row starts from a zero state.  What
  stands between the projections and the scan, and between the scan and
  the out-projection, is ``ops/kda.kda_mixer`` / ``gated_head_norm``: on a
  TPU one Pallas pass each way on the flat arrays the scan's kernels read
  and write, ``jnp`` elsewhere.
- **Latent attention with a head-wise output gate**: DeepSeek-V3's as
  ``models/xing4._mla`` runs it for Kanana (no query bottleneck, 128 + 64 /
  128), the result of head ``h`` multiplied by ``sigmoid(x W_theta)_h``
  before the out-projection (``_mla``'s ``head_gate``).
- **Group-limited routing**: the router's 512 sigmoid scores are 8 groups of
  64, a group scores the sum of its two largest ``score + bias``, the 4 best
  groups are kept and the 8 largest inside them chosen
  (``ops/moe.sigmoid_routing``'s ``n_group`` / ``topk_group``).

The expert layer is ``models/afmoe._moe`` (dropless held experts beside one
shared SwiGLU, the selection bias as state), as it is; a layer may hold a
share of its experts (``experts_held`` from ``held_start``).  The layers are
unrolled (two kinds of mixer times two kinds of feed-forward), each under
the remat ``layer_rows`` rows at a time; a layer's weights are a dictionary
of their own in ``params["layers"]``.  ``first_layer`` is the published
index of the stack's first layer, so that a pipeline stage's kinds are the
published ones.  The prediction module is not built: its published loss
weight is 0.  ``A_log`` and ``dt_bias`` are float32 whatever the other
parameters are.  A mesh of more than one device is refused.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import _lm, afmoe
from ..ops import kda as kda_ops
from ..ops.norms import rms_norm
from ..ops.rope import rope_lane_tables
from ..util import telemetry
from .afmoe import _moe, _swiglu
from .xing4 import _mla

KDA, MLA = "kda", "mla"
F32 = jnp.float32


@dataclass(frozen=True)
class BailingHybridConfig:
    """Defaults are Ling-3.0-flash's published ``config.json``."""
    vocab_size: int = 157184
    hidden: int = 2560
    layers: int = 42
    first_layer: int = 0                # published index of layer 0 here
    layer_group_size: int = 6           # 5 KDA layers to 1 of latent attention
    heads: int = 32
    head_dim: int = 128                 # KDA's d_k = d_v
    conv_kernel: int = 4                # ``short_conv_kernel_size``
    kda_lower_bound: float = -5.0       # ``kda_safe_gate``
    kda_chunk: int = 64
    time_step_min: float = 0.001        # the range ``dt_bias`` starts in
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    q_lora_rank: Optional[int] = None   # latent attention: one ``wq``
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 6144                 # the dense layers' SwiGLU
    moe_mlp_dim: int = 768              # every routed expert's, the shared one's
    num_experts: int = 512              # the router's width
    experts_held: Optional[int] = None  # None = all of them
    held_start: int = 0
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    num_shared_experts: int = 1
    num_dense_layers: int = 2           # ``first_k_dense_replace``
    route_scale: float = 2.5            # ``routed_scaling_factor``
    route_norm: bool = True             # ``norm_topk_prob``
    bias_update_rate: float = 1e-3
    rope_theta: float = 6000000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 8192             # the rotary tables' rows
    dtype: Any = jnp.bfloat16
    # "auto" (kernels on TPU / reference on CPU), "reference", "flash",
    # "flash_interpret" (latent attention's AND the delta rule's kernels)
    attention_impl: str = "auto"
    moe_impl: Optional[str] = None      # ops/moe.grouped_matmul
    remat: Any = True                   # _lm.remat
    layer_rows: Optional[int] = None    # as AfmoeConfig's
    loss_chunks: int = 0
    pp_microbatches: int = 0            # refused: see _refuse_a_mesh

    def replace(self, **kw) -> "BailingHybridConfig":
        return dataclasses.replace(self, **kw)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The mixer of every layer here, by its published index."""
        return tuple(
            MLA if (self.first_layer + i + 1) % self.layer_group_size == 0
            else KDA for i in range(self.layers))

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_layers(self) -> int:
        return self.layers - self.num_dense_layers

    @property
    def kda_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


def bailing_hybrid_tiny(**kw) -> BailingHybridConfig:
    """A CPU-test size that keeps what the code must tell apart: both mixers
    dense and sparse (``K/d K/s M/s K/s`` with a period of 3), head sizes
    192 / 128 for latent attention, 16 experts in 4 groups of which 2 are
    kept, 4 a token."""
    return BailingHybridConfig(**{**dict(
        vocab_size=256, hidden=64, layers=4, layer_group_size=3, heads=2,
        head_dim=16, kda_chunk=16, kv_lora_rank=32, mlp_dim=96,
        moe_mlp_dim=32, num_experts=16, top_k=4, n_group=4, topk_group=2,
        num_dense_layers=1, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference", remat=False), **kw})


# ------------------------------------------------------------- parameters

def _layer_shapes(cfg: BailingHybridConfig, kind: str, sparse: bool):
    E, H, F = cfg.hidden, cfg.heads, cfg.kda_dim
    shapes = {"attn_norm": ((E,), 0), "mlp_norm": ((E,), 0)}
    if kind == KDA:
        K = cfg.conv_kernel
        shapes |= {
            "w_qkv": ((E, 3 * F), E), "conv_w": ((K, 3 * F), K),
            "w_a": ((E, F), E), "w_beta": ((E, H), E), "w_g": ((E, F), E),
            # finished by ``init_params``: Kimi Linear's published start
            "A_log": ((H,), 0), "dt_bias": ((F,), 0),
            "o_norm": ((cfg.head_dim,), 0), "wo": ((F, E), F)}
    else:
        rkv, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim)
        shapes |= {
            "kv_norm": ((rkv,), 0), "wq": ((E, H, dn + dr), E),
            "wkv_a": ((E, rkv + dr), E), "wkv_b": ((rkv, H, dn + dv), rkv),
            "w_head_gate": ((E, H), E), "wo": ((H, dv, E), H * dv)}
    if sparse:
        Me, X, Xh = cfg.moe_mlp_dim, cfg.num_experts, cfg.held
        Ms = Me * cfg.num_shared_experts
        shapes |= {"router": ((E, X), E), "shared_gate": ((E, Ms), E),
                   "shared_up": ((E, Ms), E), "shared_down": ((Ms, E), Ms),
                   "w_gate": ((Xh, E, Me), E), "w_up": ((Xh, E, Me), E),
                   "w_down": ((Xh, Me, E), Me)}
    else:
        M = cfg.mlp_dim
        shapes |= {"w_gate": ((E, M), E), "w_up": ((E, M), E),
                   "w_down": ((M, E), M)}
    return shapes


_AXES = {
    "attn_norm": (None,), "mlp_norm": (None,), "kv_norm": (None,),
    "o_norm": (None,), "A_log": (None,), "dt_bias": (None,),
    "w_qkv": ("embed", "mlp"), "conv_w": (None, None), "w_a": ("embed", "mlp"),
    "w_beta": ("embed", None), "w_g": ("embed", "mlp"),
    "wq": ("embed", "heads", "head_dim"), "wkv_a": ("embed", None),
    "wkv_b": (None, "heads", "head_dim"), "w_head_gate": ("embed", None),
    "router": ("embed", None), "shared_gate": ("embed", "mlp"),
    "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed")}


def _layer_axes(shapes) -> Dict[str, Any]:
    def axes(name, shape):
        if name in _AXES:
            return _AXES[name]
        if name == "wo":
            return ("mlp", "embed") if len(shape) == 2 \
                else ("heads", "head_dim", "embed")
        lead = ("expert",) if len(shape) == 3 else ()
        return lead + (("mlp", "embed") if name == "w_down"
                       else ("embed", "mlp"))
    return {name: axes(name, leaf[0]) for name, leaf in shapes.items()}


def _sparse(cfg: BailingHybridConfig, i: int) -> bool:
    return i >= cfg.num_dense_layers


def param_shapes(cfg: BailingHybridConfig) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant[,
    the constant, 1 if not given]).  ``A_log`` and ``dt_bias`` get their
    published random start in ``init_params``."""
    V, E = cfg.vocab_size, cfg.hidden
    return {"embed": ((V, E), E),
            "layers": [_layer_shapes(cfg, kind, _sparse(cfg, i))
                       for i, kind in enumerate(cfg.kinds)],
            "final_norm": ((E,), 0),
            "lm_head": ((E, V), E)}


def param_logical_axes(cfg: BailingHybridConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    return {"embed": ("vocab", "embed"),
            "layers": [_layer_axes(layer)
                       for layer in param_shapes(cfg)["layers"]],
            "final_norm": (None,),
            "lm_head": ("embed", "vocab")}


def kda_start(cfg: BailingHybridConfig, key: jax.Array):
    """Kimi Linear's published start of a KDA layer's ``A_log`` (float32
    [heads]: the log of a uniform draw from [1, 16]) and ``dt_bias``
    (float32 [heads * head_dim]: the inverse softplus of a log-uniform draw
    from [``time_step_min``, ``time_step_max``] floored at
    ``time_step_floor``), Mamba-2's start as that code takes it over."""
    ka, kd = jax.random.split(key)
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
    step = jnp.maximum(jnp.exp(jax.random.uniform(kd, (cfg.kda_dim,))
                               * (hi - lo) + lo), cfg.time_step_floor)
    return {"A_log": jnp.log(jax.random.uniform(ka, (cfg.heads,), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step))}


def init_params(cfg: BailingHybridConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    params = _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)
    for i, kind in enumerate(cfg.kinds):
        if kind == KDA:
            params["layers"][i] |= kda_start(cfg, jax.random.fold_in(key, i))
    return params


def num_params(cfg: BailingHybridConfig) -> int:
    return _lm.count_params(param_shapes(cfg))


def init_state(cfg: BailingHybridConfig) -> Dict[str, jax.Array]:
    """The routers' selection bias, float32 [expert layers, experts]: state
    that no optimizer touches (``models/afmoe.py``)."""
    return {"bias": jnp.zeros((cfg.expert_layers, cfg.num_experts), F32)}


# ------------------------------------------------------------------ layers

@jax.named_scope("block/attn")
def _kda(cfg: BailingHybridConfig, x, layer):
    """F of a KDA layer on the normed stream x [B, S, E] -> (out [B, S, E],
    the mean share of a state's row that a chunk hands on)."""
    dt = cfg.dtype
    proj = lambda w: jnp.einsum("bse,ef->bsf", x, w.astype(dt),
                                preferred_element_type=dt)
    with jax.named_scope("kda/proj"):
        qkv, a, gate = proj(layer["w_qkv"]), proj(layer["w_a"]), \
            proj(layer["w_g"])
    with jax.named_scope("kda/gate"):
        beta = jax.nn.sigmoid(jnp.einsum(
            "bse,eh->bsh", x.astype(F32), layer["w_beta"].astype(F32)))
    # under ``kda/conv``, ``kda/gate`` and ``kda/scan``, the op's own scopes
    o, g = kda_ops.kda_mixer(
        qkv, a, beta, layer["conv_w"], layer["A_log"], layer["dt_bias"],
        bound=cfg.kda_lower_bound, chunk=cfg.kda_chunk,
        interpret=cfg.attention_impl == "flash_interpret")
    with jax.named_scope("kda/gate"):
        carry = kda_ops.chunk_carry(g, cfg.kda_chunk)
    # under ``kda/norm``, the op's own scope
    o = kda_ops.gated_head_norm(
        o, gate, layer["o_norm"], cfg.norm_eps,
        interpret=cfg.attention_impl == "flash_interpret")
    with jax.named_scope("kda/out"):
        return jnp.einsum("bsf,fe->bse", o, layer["wo"].astype(dt),
                          preferred_element_type=dt), carry


def _layer(cfg: BailingHybridConfig, kind: str, tables, x, layer, bias=None):
    """One layer, ``a = x + Mixer(N(x))``, ``a + F(N(a))``: (x', what it
    reports: an expert layer's loads as ``afmoe._moe``'s, a KDA layer's
    ``carry``).  ``bias`` is None for a dense layer."""
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    report = {}
    if kind == KDA:
        f, report["carry"] = _kda(cfg, h, layer)
    else:
        f = _mla(cfg, *tables, h, layer, head_gate=layer["w_head_gate"])
    a = x + f
    h = rms_norm(a, layer["mlp_norm"], cfg.norm_eps)
    if bias is None:
        with jax.named_scope("block/mlp"):
            f = _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                        cfg.dtype)
    else:
        f, loads = _moe(cfg, h, layer, bias)
        report |= loads
    return a + f, report


def _merge(report, cfg: BailingHybridConfig):
    """A layer's reports over its groups of rows (leading axis) as one."""
    merged = {}
    if "carry" in report:
        merged["carry"] = jnp.mean(report["carry"])
    if "counts" in report:
        merged |= {"counts": jnp.sum(report["counts"], axis=0),
                   "dropped": jnp.sum(report["dropped"]),
                   "sliced": jnp.sum(report["sliced"]),
                   "top": report["top"].reshape(-1, cfg.top_k)}
    return merged


def _run(cfg: BailingHybridConfig, kind: str, tables, x, layer, bias=None,
         keep=()):
    """The layer under the remat, ``layer_rows`` rows at a time (as
    ``afmoe``'s); ``keep``: ``_lm.remat``'s, the stack's."""
    one = _lm.remat(lambda x, layer, bias: _layer(cfg, kind, tables, x,
                                                  layer, bias), cfg.remat,
                    keep)
    B = x.shape[0]
    n = min(cfg.layer_rows or B, B)
    if B % n:
        raise ValueError(f"a batch of {B} rows does not split into groups "
                         f"of layer_rows={n}")
    if n == B:
        return one(x, layer, bias)
    y, report = jax.lax.map(lambda rows: one(rows, layer, bias),
                            x.reshape((B // n, n) + x.shape[1:]))
    return y.reshape(x.shape), _merge(report, cfg)


def _refuse_a_mesh(cfg: BailingHybridConfig) -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "bailing_hybrid on a mesh: the exchange of an expert-parallel "
            "group and a delta rule split over heads are not built "
            "(ROADMAP M3, M8)")
    if cfg.pp_microbatches:
        raise NotImplementedError(
            "bailing_hybrid with pp_microbatches: its layers are not one "
            "stack of like layers that a pipeline stage could slice "
            "(ROADMAP M4)")


def _forward_hidden(params, state, tokens, cfg: BailingHybridConfig):
    """tokens [B, S] -> (final hidden [B, S, E] after the final norm; the
    expert layers' loads {"counts" [Le, X], "dropped" [Le], "sliced" [Le],
    "top" [Le, B*S, k]}; the KDA layers' mean chunk carry)."""
    _refuse_a_mesh(cfg)
    telemetry.inc("ray_tpu_moe_groups_kept_total", tags={
        "n_group": str(cfg.n_group), "topk_group": str(cfg.topk_group)})
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    tables = rope_lane_tables(cfg.qk_rope_head_dim, cfg.max_seq_len,
                              cfg.rope_theta)
    keep = _lm.flash_keep(
        cfg.remat, sum(kind == MLA for kind in cfg.kinds),
        (*tokens.shape, cfg.heads, cfg.v_head_dim), cfg.dtype)
    loads, carries = [], []
    for i, (kind, layer) in enumerate(zip(cfg.kinds, params["layers"])):
        bias = state["bias"][len(loads)] if _sparse(cfg, i) else None
        x, report = _run(cfg, kind, tables, x, layer, bias, keep)
        if "carry" in report:
            carries.append(report.pop("carry"))
        if bias is not None:
            loads.append(report)
    if loads:
        loads = jax.tree.map(lambda *a: jnp.stack(a), *loads)
    else:
        loads = {"counts": jnp.zeros((0, cfg.num_experts), jnp.int32),
                 "dropped": jnp.zeros((0,), jnp.int32),
                 "sliced": jnp.zeros((0,), jnp.int32),
                 "top": jnp.zeros((0, tokens.size, cfg.top_k), jnp.int32)}
    carry = jnp.mean(jnp.stack(carries)) if carries else jnp.ones((), F32)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, loads, carry


def forward(params, tokens, cfg: BailingHybridConfig, state=None):
    """tokens [B, S] -> logits [B, S, V] float32."""
    x, *_ = _forward_hidden(params, state or init_state(cfg), tokens, cfg)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=F32)


def loss_and_report(params, batch, cfg: BailingHybridConfig, state=None):
    """What the train step differentiates (parallel.spmd): the next-token
    cross-entropy (no auxiliary term, no prediction module), and what
    ``update_state`` turns into the step's metrics: the expert layers' loads
    and the KDA layers' chunk carry."""
    x, loads, carry = _forward_hidden(params, state or init_state(cfg),
                                      batch["tokens"], cfg)
    # Traced on its own, so that the scope ``loss`` stays a scope in the
    # backward's operations too (models/ouro._scoped has the reason).
    loss = jax.jit(lambda x, head, batch: _lm.next_token_loss(
        x, head, batch, cfg.loss_chunks, cfg.dtype))(x, params["lm_head"],
                                                     batch)
    return loss, jax.lax.stop_gradient({**loads, "kda_chunk_carry": carry})


def loss_fn(params, batch, cfg: BailingHybridConfig, state=None):
    return loss_and_report(params, batch, cfg, state)[0]


def update_state(state, report, cfg: BailingHybridConfig):
    """(the state after a step with this report, the step's metrics):
    ``afmoe``'s metrics of the loads, and ``kda_chunk_carry``."""
    state, metrics = afmoe.update_state(state, report, cfg)
    return state, {**metrics, "kda_chunk_carry": report["kda_chunk_carry"]}
