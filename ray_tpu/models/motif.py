"""The ``Motif`` decoder (Motif-Technologies Motif-3-Beta): grouped
differential attention on latent keys and values, window and full layers on
the one latent stack, a residual stream of four lanes, PolyNorm feed-forwards
and sigmoid-routed dropless experts beside a shared one.

What it has that no other model here has:

- **Grouped differential attention on latent keys** (``attention_cls``
  ``gdla``).  Latent attention as ``models/xing4._mla`` runs it (queries
  through a ``q_lora_rank`` bottleneck, keys and values through a
  ``kv_lora_rank`` one, 128 lanes without position + 64 rotary, values of
  128, one rotary key for all heads), but the latent is decompressed into
  ``kv_heads`` key heads (16) that ``heads`` query heads (80) read five to
  one, query head ``i`` key head ``i // 5`` (``ops/attention.py``: a call in
  parts with a group).  Of a key head's five query heads the first four are
  **signal** heads and the last is its **noise** head (Grouped Differential
  Attention, arXiv:2510.06949: 64 signal to 16 noise, a noise head shared
  by a group as a key head is).  ``diff_v2`` (Differential Transformer V2):
  the pair shares keys and values, and signal head ``m`` of key head ``j``
  leaves as ``o_m - sigmoid(h w_lambda,m) o_noise(j)``, the weight a number
  a token and a signal head (``mla/diff``).  The 64 differences pass an
  elementwise gate ``sigmoid(h W_g)`` over their 8,192 channels
  (arXiv:2505.06708; ``mla/gate``) and the out-projection.
- **A window on three layers of four** (``sliding_window`` 128,
  ``sliding_window_pattern`` interleave, ``sliding_window_period`` 4): layer
  ``i`` is full-causal where ``(first_layer + i + 1) % 4 == 0`` and sees
  ``0 <= t - s < 128`` elsewhere, through the same kernels under
  ``block/attn_window`` / ``block/attn_full``.  Rotary frequencies are plain
  (``apply_yarn_scaling`` false, ``mscale`` 1): the softmax scale is
  ``192 ** -0.5``.
- **PolyNorm** (``hidden_act`` ``poly_norm``; ``ops/norms.poly_norm``) in
  the place of SiLU in all three kinds of feed-forward: the dense layers'
  (``mlp_poly``), the shared expert's (``shared_poly``) and the routed
  experts' together (``expert_poly``: a grouped expert module has one
  activation), three weights and a bias each, under ``block/mlp/polynorm``
  where the feed-forward is dense.

The stream is ``models/xing4.py``'s (``_sublayer``: ``ops/hyper.py``'s maps,
20 Sinkhorn passes, no clamp on ``H_res``' logits; ``hidden_clamp`` clips a
sublayer's collected input), the expert layer ``models/afmoe._moe``'s with
``act="poly_norm"`` (a layer may hold a share of its experts), the
prediction module ``xing4._mtp_loss``'s, the stack xing4's: dense layers
unrolled, the expert layers one ``lax.scan`` whose body serves both kinds of
layer (the kind rides with the layer's weights as a flag, as in
``models/afmoe.py``), each layer under the remat ``layer_rows`` rows at a
time.  A mesh of more than one device is refused.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import _lm, afmoe, xing4
from ..ops.norms import poly_norm, rms_norm
from ..ops.rope import rope_lane_tables
from .afmoe import _moe
from .xing4 import SUBLAYERS, _hc_start, _kernels, _mla, _sublayer

F32 = jnp.float32


@dataclass(frozen=True)
class MotifConfig:
    """Defaults are Motif-3-Beta's published ``config.json``."""
    vocab_size: int = 220160
    hidden: int = 4096
    layers: int = 53
    first_layer: int = 0                # published index of layer 0 here
    heads: int = 80                     # 64 signal + 16 noise
    kv_heads: int = 16
    num_noise_heads: int = 16           # one a key head, its group's last
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    sliding_window: int = 128
    sliding_window_period: int = 4      # every fourth layer is full
    mlp_dim: int = 12288                # the dense layers' feed-forward
    moe_mlp_dim: int = 1280             # every expert's, and the shared one's
    num_experts: int = 384              # the router's width
    experts_held: Optional[int] = None  # None = all of them
    held_start: int = 0
    top_k: int = 8
    num_shared_experts: int = 1
    num_dense_layers: int = 2           # ``n_dense_first_layers``
    route_scale: float = 2.0
    route_norm: bool = True
    bias_update_rate: float = 1e-4      # ``load_balance_coeff``
    polynorm_output_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    hidden_clamp: float = 1e6
    hc_mult: int = 4                    # ``mhc_expansion_rate``
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-np.inf, np.inf)   # none is published
    mtp_layers: int = 1                 # ``num_nextn_predict_layers``
    mtp_loss_weight: float = 0.3
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192             # the rotary tables' rows
    dtype: Any = jnp.bfloat16
    # "auto" (flash on TPU / reference on CPU), "reference", "flash",
    # "flash_interpret"
    attention_impl: str = "auto"
    moe_impl: Optional[str] = None      # ops/moe.grouped_matmul
    remat: Any = True                   # _lm.remat
    layer_rows: Optional[int] = None    # as AfmoeConfig's
    loss_chunks: int = 0
    pp_microbatches: int = 0            # refused: see _refuse_a_mesh

    def replace(self, **kw) -> "MotifConfig":
        return dataclasses.replace(self, **kw)

    def __post_init__(self):
        if self.heads % self.kv_heads or self.num_noise_heads not in (
                0, self.kv_heads):
            raise ValueError(
                f"{self.heads} query heads over {self.kv_heads} key heads "
                f"with {self.num_noise_heads} noise heads: a key head has "
                "one noise head (its group's last) or none")

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_layers(self) -> int:
        return self.layers - self.num_dense_layers

    @property
    def signal_heads(self) -> int:
        return self.heads - self.num_noise_heads

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def full(self, i: int) -> bool:
        """Whether layer ``i`` here (the module's is ``layers``) is
        full-causal; the others see a window."""
        return (self.first_layer + i + 1) % self.sliding_window_period == 0


def motif_tiny(**kw) -> MotifConfig:
    """A CPU-test size that keeps what the code must tell apart: 10 query
    heads of 192 / 128 over 2 key heads (4 signal and 1 noise a group), a
    window of 16 on three layers of four (``W/d W/s W/s F/s W/s``), four
    lanes, 8 experts with 4 a token, and the prediction module."""
    return MotifConfig(**{**dict(
        vocab_size=256, hidden=64, layers=5, heads=10, kv_heads=2,
        num_noise_heads=2, q_lora_rank=48, kv_lora_rank=32,
        sliding_window=16, mlp_dim=96, moe_mlp_dim=32, num_experts=8,
        top_k=4, num_dense_layers=1, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference", remat=False), **kw})


# ------------------------------------------------------------- parameters

#: what PolyNorm's three weights and its bias start at (Motif-2.6B's report)
POLY_START = np.array([1 / 3, 1 / 3, 1 / 3, 0.0], np.float32)


def _layer_axes(cfg: MotifConfig) -> Dict[str, Any]:
    axes = {
        "attn_norm": ("layers", None), "mlp_norm": ("layers", None),
        "q_norm": ("layers", None), "kv_norm": ("layers", None),
        "wq_a": ("layers", "embed", None),
        "wq_b": ("layers", None, "heads", "head_dim"),
        "wkv_a": ("layers", "embed", None),
        "wkv_b": ("layers", None, "heads", "head_dim"),
        "w_lambda": ("layers", "embed", None),
        "w_attn_gate": ("layers", "embed", "mlp"),
        "wo": ("layers", "heads", "head_dim", "embed")}
    for s in SUBLAYERS:
        axes |= {f"hc_{s}_phi": ("layers", None, None),
                 f"hc_{s}_b": ("layers", None),
                 f"hc_{s}_alpha": ("layers", None)}
    return axes


def _moe_axes(cfg: MotifConfig) -> Dict[str, Any]:
    return {**_layer_axes(cfg),
            "router": ("layers", "embed", None),
            "shared_gate": ("layers", "embed", "mlp"),
            "shared_up": ("layers", "embed", "mlp"),
            "shared_down": ("layers", "mlp", "embed"),
            "shared_poly": ("layers", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
            "expert_poly": ("layers", None)}


def param_logical_axes(cfg: MotifConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    axes = {
        "embed": ("vocab", "embed"),
        "dense": {**_layer_axes(cfg),
                  "w_gate": ("layers", "embed", "mlp"),
                  "w_up": ("layers", "embed", "mlp"),
                  "w_down": ("layers", "mlp", "embed"),
                  "mlp_poly": ("layers", None)},
        "moe": _moe_axes(cfg),
        "final_norm": (None,),
        "lm_head": ("embed", "vocab")}
    if cfg.mtp_layers:
        axes["mtp"] = {"h_norm": (None,), "e_norm": (None,),
                       "proj": (None, "embed"), "final_norm": (None,),
                       "layer": _moe_axes(cfg)}
    return axes


def param_shapes(cfg: MotifConfig) -> Dict[str, Any]:
    """leaf -> (shape, fan-in[, start]; fan-in 0 marks a weight that starts
    at a constant: a norm's at one, a hyper-connection's as
    ``models/xing4.py``'s, PolyNorm's numbers at ``POLY_START``)."""
    E, H, Hkv, Hs, V, n = (cfg.hidden, cfg.heads, cfg.kv_heads,
                           cfg.signal_heads, cfg.vocab_size, cfg.hc_mult)
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    width = 2 * n + n * n
    poly = lambda L: ((L, 4), 0, POLY_START)

    def layer(L):
        shapes = {
            "attn_norm": ((L, E), 0), "mlp_norm": ((L, E), 0),
            "q_norm": ((L, rq), 0), "kv_norm": ((L, rkv), 0),
            "wq_a": ((L, E, rq), E), "wq_b": ((L, rq, H, dn + dr), rq),
            "wkv_a": ((L, E, rkv + dr), E),
            "wkv_b": ((L, rkv, Hkv, dn + dv), rkv),
            "w_lambda": ((L, E, Hs), E),
            "w_attn_gate": ((L, E, Hs * dv), E),
            "wo": ((L, Hs, dv, E), Hs * dv)}
        for s in SUBLAYERS:
            shapes |= {f"hc_{s}_phi": ((L, n * E, width), n * E),
                       f"hc_{s}_b": ((L, width), 0, _hc_start(cfg)),
                       f"hc_{s}_alpha": ((L, 3), 0, 0.01)}
        return shapes

    M, Me, X, Xh = cfg.mlp_dim, cfg.moe_mlp_dim, cfg.num_experts, cfg.held
    Ms = Me * cfg.num_shared_experts

    def moe_layer(L):
        return {**layer(L), "router": ((L, E, X), E),
                "shared_gate": ((L, E, Ms), E), "shared_up": ((L, E, Ms), E),
                "shared_down": ((L, Ms, E), Ms), "shared_poly": poly(L),
                "w_gate": ((L, Xh, E, Me), E), "w_up": ((L, Xh, E, Me), E),
                "w_down": ((L, Xh, Me, E), Me), "expert_poly": poly(L)}

    Ld = cfg.num_dense_layers
    shapes = {
        "embed": ((V, E), E),
        "dense": {**layer(Ld), "w_gate": ((Ld, E, M), E),
                  "w_up": ((Ld, E, M), E), "w_down": ((Ld, M, E), M),
                  "mlp_poly": poly(Ld)},
        "moe": moe_layer(cfg.expert_layers),
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E)}
    if cfg.mtp_layers:
        shapes["mtp"] = {"h_norm": ((E,), 0), "e_norm": ((E,), 0),
                         "proj": ((2 * E, E), 2 * E), "final_norm": ((E,), 0),
                         "layer": moe_layer(cfg.mtp_layers)}
    return shapes


def init_params(cfg: MotifConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    return _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)


def num_params(cfg: MotifConfig) -> int:
    return _lm.count_params(param_shapes(cfg))


def init_state(cfg: MotifConfig) -> Dict[str, jax.Array]:
    """The routers' selection bias, float32 [expert layers + the prediction
    module's, experts]: state, which no optimizer touches."""
    return xing4.init_state(cfg)


# ------------------------------------------------------------------ layers

def _poly_weights(cfg: MotifConfig):
    """``afmoe._moe``'s ``act_weights``: PolyNorm's keyword arguments from a
    module's four numbers."""
    return lambda p: {"p": p, "scale": cfg.polynorm_output_scale,
                      "clamp": cfg.polynorm_bias_clamp, "eps": cfg.norm_eps}


def _attend(cfg: MotifConfig, full):
    """``xing4._mla``'s ``attend`` for a layer of this kind: ``full`` is a
    Python bool where the kind is known when tracing, or a traced scalar
    where one scanned body serves both kinds (``lax.cond``)."""
    in_window = jax.named_scope("block/attn_window")(
        _kernels(cfg, cfg.sliding_window))
    over_the_row = jax.named_scope("block/attn_full")(_kernels(cfg))
    if isinstance(full, bool):
        return over_the_row if full else in_window
    return lambda q, k: jax.lax.cond(full, over_the_row, in_window, q, k)


def _gdla(cfg: MotifConfig, cos, sin, h, layer, full):
    """F of an attention sublayer on the normed input h [B, S, E] -> (out
    [B, S, E], the mean of sigmoid(lambda) over tokens and signal heads)."""
    dt, dv = cfg.dtype, cfg.v_head_dim
    Hkv, per_key = cfg.kv_heads, cfg.signal_heads // cfg.kv_heads
    seen = {}

    def after(h, o):
        """o [B, S, H, dv] of the kernels -> [B, S, signal heads, dv]."""
        B, S = o.shape[:2]
        if cfg.num_noise_heads:
            with jax.named_scope("mla/diff"):
                lam = jax.nn.sigmoid(jnp.einsum(
                    "bse,em->bsm", h, layer["w_lambda"].astype(dt),
                    preferred_element_type=F32))            # [B, S, 64]
                seen["lambda"] = jnp.mean(lam)
                o = o.reshape(B, S, Hkv, per_key + 1, dv).astype(F32)
                o = (o[..., :per_key, :]
                     - lam.reshape(B, S, Hkv, per_key, 1) * o[..., per_key:, :]
                     ).astype(dt).reshape(B, S, cfg.signal_heads, dv)
        with jax.named_scope("mla/gate"):
            gate = jax.nn.sigmoid(jnp.einsum(
                "bse,ef->bsf", h, layer["w_attn_gate"].astype(dt),
                preferred_element_type=dt).astype(F32))
            return o * gate.astype(dt).reshape(o.shape)

    out = _mla(cfg, cos, sin, h, layer, attend=_attend(cfg, full),
               after=after)
    return out, seen.get("lambda", jnp.zeros((), F32))


def _poly_mlp(cfg: MotifConfig, h, layer):
    """A dense layer's feed-forward, ``W_down(P(h W_gate) * (h W_up))``."""
    with jax.named_scope("block/mlp"):
        return afmoe._feed_forward(
            h, layer["w_gate"], layer["w_up"], layer["w_down"], cfg.dtype,
            "poly_norm", _poly_weights(cfg)(layer["mlp_poly"]))


def _layer_of(full=None):
    """``xing4._run``'s ``layer_fn`` for a layer whose kind is ``full`` (a
    Python bool), or rides with its weights as ``layer["full"]`` (None: a
    traced flag, an argument of the rematted function)."""

    def layer_fn(cfg, cos, sin, X, layer, bias=None):
        """One layer on the stream; ``bias`` is None for a dense layer.  ->
        (X, {"hc_residual", "gdla_lambda", and an expert layer's loads})."""
        kind = layer["full"] if full is None else full
        X, lam, r_attn = _sublayer(
            cfg, X, layer, "attn",
            lambda h: _gdla(cfg, cos, sin, h, layer, kind))

        def feed_forward(h):
            if bias is None:
                return _poly_mlp(cfg, h, layer), {}
            return _moe(cfg, h, layer, bias, "poly_norm",
                        act_weights=_poly_weights(cfg))

        X, loads, r_mlp = _sublayer(cfg, X, layer, "mlp", feed_forward)
        return X, {**loads, "hc_residual": jnp.maximum(r_attn, r_mlp),
                   "gdla_lambda": lam}

    return layer_fn


def _refuse_a_mesh(cfg: MotifConfig) -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "motif on a mesh: the exchange of an expert-parallel group is "
            "not built (ROADMAP M3)")
    if cfg.pp_microbatches:
        raise NotImplementedError(
            "motif with pp_microbatches: a pipeline stage hands on one "
            "lane, not hc_mult (ROADMAP M4)")


def _forward_hidden(params, state, tokens, cfg: MotifConfig, keep=()):
    """``xing4._forward_hidden``'s results for this stack: (the lanes' sum
    after the last layer [B, S, C], before the final norm; the expert
    layers' loads {"counts" [Lm, X], "dropped" [Lm], "sliced" [Lm], "top"
    [Lm, B*S, k]} and ``gdla_lambda`` [L], every layer's; the largest
    Sinkhorn residual of the stack; the rotary tables)."""
    _refuse_a_mesh(cfg)
    with jax.named_scope("embed"):
        X = xing4._lanes(params["embed"].astype(cfg.dtype)[tokens],
                         cfg.hc_mult)
    cos, sin = rope_lane_tables(cfg.qk_rope_head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    Ld, Lm = cfg.num_dense_layers, cfg.expert_layers
    residual, lambdas = jnp.zeros((), F32), []
    for i in range(Ld):
        X, report = xing4._run(
            cfg, cos, sin, X, jax.tree.map(lambda a: a[i], params["dense"]),
            layer_fn=_layer_of(cfg.full(i)), keep=keep)
        residual = jnp.maximum(residual, report["hc_residual"])
        lambdas.append(report["gdla_lambda"][None])

    kinds = [cfg.full(Ld + j) for j in range(Lm)]
    mixed = len(set(kinds)) > 1
    stacked = {"layer": dict(params["moe"]), "bias": state["bias"][:Lm]}
    if mixed:
        stacked["layer"]["full"] = jnp.asarray(kinds)
    layer_fn = _layer_of(None if mixed else kinds[0]) if Lm else None

    def body(X, group):
        return xing4._run(cfg, cos, sin, X, group["layer"], group["bias"],
                          layer_fn, keep)

    if Lm:
        X, loads = jax.lax.scan(body, X, stacked)
        residual = jnp.maximum(residual, jnp.max(loads.pop("hc_residual")))
        lambdas.append(loads.pop("gdla_lambda"))
    else:
        loads = {"counts": jnp.zeros((0, cfg.num_experts), jnp.int32),
                 "dropped": jnp.zeros((0,), jnp.int32),
                 "sliced": jnp.zeros((0,), jnp.int32),
                 "top": jnp.zeros((0, tokens.size, cfg.top_k), jnp.int32)}
    loads["gdla_lambda"] = jnp.concatenate(lambdas)
    return xing4._collapse(X), loads, residual, (cos, sin)


def forward(params, tokens, cfg: MotifConfig, state=None) -> jax.Array:
    """tokens [B, S] -> next-token logits [B, S, V] float32 (the prediction
    module is a training part)."""
    x, *_ = _forward_hidden(params, state or init_state(cfg), tokens, cfg)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def loss_and_report(params, batch, cfg: MotifConfig, state=None):
    """What the train step differentiates (parallel.spmd): xing4's loss
    (``main + mtp_loss_weight * module's``) over this stack, and its report
    with ``gdla_lambda`` [layers, the module's last]."""
    return xing4.loss_and_report(
        params, batch, cfg, state or init_state(cfg), _forward_hidden,
        _layer_of(cfg.full(cfg.layers)))


def loss_fn(params, batch, cfg: MotifConfig, state=None) -> jax.Array:
    return loss_and_report(params, batch, cfg, state)[0]


def update_state(state, report, cfg: MotifConfig):
    """(the state after a step with this report, the step's metrics):
    xing4's, and ``gdla_lambda_mean``: the mean of sigmoid(lambda) over
    tokens, signal heads and layers (0 or 1 says the pair is dead)."""
    state, metrics = xing4.update_state(state, report, cfg)
    return state, {**metrics,
                   "gdla_lambda_mean": jnp.mean(report["gdla_lambda"])}
