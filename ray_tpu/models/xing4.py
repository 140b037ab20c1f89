"""The ``xing4_0`` decoder (Xing4.0-29B-A4B): latent attention, a residual
stream of several lanes mixed by hyper-connections, sigmoid-routed dropless
experts beside a shared one, and a module that predicts a second token.

A configuration may switch three of its mechanisms off, and what is left is
DeepSeek-V3's block, which ``models/deepseek_v3.py`` (kanana-2-30b-a3b) runs
through this stack: ``hc_mult`` 1 (a plain pre-norm stream, no map),
``mtp_layers`` 0 (no prediction module) and ``q_lora_rank`` None (queries
straight from the hidden state).  ``yarn`` None leaves the rotary
frequencies plain and the softmax scale without ``mscale``.  Latent
attention, the expert layer and the layer-rows loop are always there, and
are the one copy both models run.

What it has that no other model here has:

- **Latent attention** (DeepSeek-V3's): queries through a ``q_lora_rank``
  bottleneck with an RMSNorm (or, with no rank, one ``wq`` [E, H, 192]),
  keys and values through a ``kv_lora_rank`` one; a head's query and key
  are ``qk_nope_head_dim`` channels without position and
  ``qk_rope_head_dim`` rotary ones, the rotary key one head
  for all query heads; values are ``v_head_dim`` wide.  The flash kernels
  take q and k in those two parts, 128 + 64, the rotary key head once, and
  a head's key and value where the one projection wrote them side by side
  (``ops/attention.py``, a call in parts).  Rotary
  frequencies are yarn's (``ops/rope.Yarn``), and the softmax scale carries
  its ``mscale ** 2``.
- **A stream of ``hc_mult`` lanes** [B, n, S, C]: every sublayer reads a
  weighted sum of the lanes and writes back through a doubly stochastic
  lane-to-lane map (``ops/hyper.py``).  With ``hc_mult`` 1 no map is made
  and a layer is the pre-norm ``x + F(N(x))``.
- **A prediction module** (``num_nextn_predict_layers`` 1, DeepSeek-V3's
  form): the final hidden state of position t and the embedding of token
  t + 1, each normed, through one projection, one expert layer of its own
  and the model's head, against token t + 2; its loss joins the main one
  with weight ``mtp_loss_weight``.

The expert layer is ``models/afmoe.py``'s (``_moe``: ``ops/moe.py``'s sigmoid
router, dropless held experts, the selection bias as state), as it is; a
layer may hold a share of its experts.  The stack: dense layers unrolled,
the expert layers one ``lax.scan``, each layer under the remat
``layer_rows`` rows at a time.  The selection bias of the module's layer is
the last row of the state's ``bias``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import _lm, afmoe
from ..ops import hyper
from ..ops.attention import attention as _attention
from ..ops.norms import rms_norm
from ..ops.rope import Yarn, rope_lane_tables, rotate_heads
from ..util import telemetry
from .afmoe import _moe, _swiglu

#: the two sublayers of a layer, as their weights' names say them
SUBLAYERS = ("attn", "mlp")


@dataclass(frozen=True)
class Xing4Config:
    """Defaults are Xing4.0-29B-A4B's published ``config.json``."""
    vocab_size: int = 131072
    hidden: int = 3584
    layers: int = 40
    heads: int = 32
    q_lora_rank: Optional[int] = 768    # None: no bottleneck, one ``wq``
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 9216                 # the dense layers' SwiGLU
    moe_mlp_dim: int = 1024             # every expert's, and the shared one's
    num_experts: int = 64               # the router's width
    experts_held: Optional[int] = None  # None = all of them
    held_start: int = 0
    top_k: int = 4
    num_shared_experts: int = 1
    num_dense_layers: int = 2           # ``first_k_dense_replace``
    route_scale: float = 2.0            # ``routed_scaling_factor``
    route_norm: bool = True             # ``norm_topk_prob``
    bias_update_rate: float = 1e-3
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    mtp_layers: int = 1                 # ``num_nextn_predict_layers``
    mtp_loss_weight: float = 0.3
    rope_theta: float = 10000.0
    yarn: Optional[Yarn] = Yarn(factor=64.0,
                                original_max_position_embeddings=4096,
                                beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                                mscale_all_dim=1.0)
    norm_eps: float = 1e-6
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

    def replace(self, **kw) -> "Xing4Config":
        return dataclasses.replace(self, **kw)

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_layers(self) -> int:
        return self.layers - self.num_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5 * (
            self.yarn.softmax_scale if self.yarn else 1.0)

    @property
    def hc_width(self) -> int:
        """Numbers a sublayer's maps take a token: 2 n + n * n."""
        return 2 * self.hc_mult + self.hc_mult ** 2


def xing4_tiny(**kw) -> Xing4Config:
    """A CPU-test size that keeps what the kernels must tell apart: head
    sizes 192 / 128 (128 + 64 rotary), four lanes, 8 experts with 4 a
    token, 1 dense + 2 expert layers and the prediction module."""
    return Xing4Config(**{**dict(
        vocab_size=256, hidden=64, layers=3, heads=2, q_lora_rank=48,
        kv_lora_rank=32, mlp_dim=96, moe_mlp_dim=32, num_experts=8, top_k=4,
        num_dense_layers=1, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference", remat=False), **kw})


# ------------------------------------------------------------- parameters

def _layer_axes(cfg: Xing4Config) -> Dict[str, Any]:
    axes = {
        "attn_norm": ("layers", None), "mlp_norm": ("layers", None),
        "kv_norm": ("layers", None),
        "wkv_a": ("layers", "embed", None),
        "wkv_b": ("layers", None, "heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed")}
    if cfg.q_lora_rank is None:
        axes["wq"] = ("layers", "embed", "heads", "head_dim")
    else:
        axes |= {"q_norm": ("layers", None),
                 "wq_a": ("layers", "embed", None),
                 "wq_b": ("layers", None, "heads", "head_dim")}
    if cfg.hc_mult > 1:
        for s in SUBLAYERS:
            axes |= {f"hc_{s}_phi": ("layers", None, None),
                     f"hc_{s}_b": ("layers", None),
                     f"hc_{s}_alpha": ("layers", None)}
    return axes


def _moe_axes(cfg: Xing4Config) -> Dict[str, Any]:
    return {**_layer_axes(cfg),
            "router": ("layers", "embed", None),
            "shared_gate": ("layers", "embed", "mlp"),
            "shared_up": ("layers", "embed", "mlp"),
            "shared_down": ("layers", "mlp", "embed"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed")}


def param_logical_axes(cfg: Xing4Config) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    axes = {
        "embed": ("vocab", "embed"),
        "dense": {**_layer_axes(cfg),
                  "w_gate": ("layers", "embed", "mlp"),
                  "w_up": ("layers", "embed", "mlp"),
                  "w_down": ("layers", "mlp", "embed")},
        "moe": _moe_axes(cfg),
        "final_norm": (None,),
        "lm_head": ("embed", "vocab")}
    if cfg.mtp_layers:
        axes["mtp"] = {"h_norm": (None,), "e_norm": (None,),
                       "proj": (None, "embed"), "final_norm": (None,),
                       "layer": _moe_axes(cfg)}
    return axes


def _hc_start(cfg: Xing4Config):
    """What a sublayer's ``b`` starts at: H_pre = 1/2 and H_post = 1 in every
    lane, H_res near the identity (4 on the diagonal of its logits)."""
    n = cfg.hc_mult
    return np.concatenate([np.zeros(2 * n), 4.0 * np.eye(n).reshape(-1)]
                          ).astype(np.float32)


def param_shapes(cfg: Xing4Config) -> Dict[str, Any]:
    """leaf -> (shape, fan-in[, start]; fan-in 0 marks a weight that starts
    at a constant: a norm's at one, a hyper-connection's gains at 0.01 and
    its ``b`` at ``_hc_start``)."""
    E, H, V, n = cfg.hidden, cfg.heads, cfg.vocab_size, cfg.hc_mult
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def layer(L):
        shapes = {
            "attn_norm": ((L, E), 0), "mlp_norm": ((L, E), 0),
            "kv_norm": ((L, rkv), 0),
            "wkv_a": ((L, E, rkv + dr), E),
            "wkv_b": ((L, rkv, H, dn + dv), rkv),
            "wo": ((L, H, dv, E), H * dv)}
        if rq is None:
            shapes["wq"] = ((L, E, H, dn + dr), E)
        else:
            shapes |= {"q_norm": ((L, rq), 0), "wq_a": ((L, E, rq), E),
                       "wq_b": ((L, rq, H, dn + dr), rq)}
        if n > 1:
            for s in SUBLAYERS:
                shapes |= {
                    f"hc_{s}_phi": ((L, n * E, cfg.hc_width), n * E),
                    f"hc_{s}_b": ((L, cfg.hc_width), 0, _hc_start(cfg)),
                    f"hc_{s}_alpha": ((L, 3), 0, 0.01)}
        return shapes

    M, Me, X, Xh = cfg.mlp_dim, cfg.moe_mlp_dim, cfg.num_experts, cfg.held
    Ms = Me * cfg.num_shared_experts

    def moe_layer(L):
        return {**layer(L), "router": ((L, E, X), E),
                "shared_gate": ((L, E, Ms), E), "shared_up": ((L, E, Ms), E),
                "shared_down": ((L, Ms, E), Ms),
                "w_gate": ((L, Xh, E, Me), E), "w_up": ((L, Xh, E, Me), E),
                "w_down": ((L, Xh, Me, E), Me)}

    Ld = cfg.num_dense_layers
    shapes = {
        "embed": ((V, E), E),
        "dense": {**layer(Ld), "w_gate": ((Ld, E, M), E),
                  "w_up": ((Ld, E, M), E), "w_down": ((Ld, M, E), M)},
        "moe": moe_layer(cfg.expert_layers),
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E)}
    if cfg.mtp_layers:
        shapes["mtp"] = {"h_norm": ((E,), 0), "e_norm": ((E,), 0),
                         "proj": ((2 * E, E), 2 * E), "final_norm": ((E,), 0),
                         "layer": moe_layer(cfg.mtp_layers)}
    return shapes


def init_params(cfg: Xing4Config, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    return _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)


def num_params(cfg: Xing4Config) -> int:
    return _lm.count_params(param_shapes(cfg))


def init_state(cfg: Xing4Config) -> Dict[str, jax.Array]:
    """The routers' selection bias, float32 [expert layers + the prediction
    module's, experts]: state, which no optimizer touches."""
    return {"bias": jnp.zeros((cfg.expert_layers + cfg.mtp_layers,
                               cfg.num_experts), jnp.float32)}


# ------------------------------------------------------------------ layers

def _flat(w, shape, dt):
    """A head weight as the flat matrix its product takes, behind a
    barrier: without one the TPU compiler folds the reshape of a weight's
    gradient into the product that forms it ([H, D, E] for [H * D, E]) and
    then wants the activation it contracts with the sequence minor, a
    transposing copy of q's size before every one of them at a row a call
    (PERF.md, PR 50)."""
    return jax.lax.optimization_barrier(w.astype(dt).reshape(shape))


def _project(h, w, dt):
    """``_lm.project_heads``: h [B, S, E] x w [E, H, D] -> [B, S, H, D]."""
    E, H, D = w.shape
    flat = jnp.einsum("bse,ef->bsf", h, _flat(w, (E, H * D), dt),
                      preferred_element_type=dt)
    return flat.reshape(*h.shape[:2], H, D)


def _count_geometry(cfg, h) -> None:
    """What a traced call of latent attention is, for ``counters.json``; a
    configuration with fewer key heads than query heads, noise heads or a
    window (``models/motif.py``) says so in tags of its own."""
    more = {tag: str(getattr(cfg, field)) for tag, field in (
        ("kv_heads", "kv_heads"), ("noise_heads", "num_noise_heads"),
        ("window", "sliding_window")) if hasattr(cfg, field)}
    telemetry.inc("ray_tpu_mla_call_geometry_total", tags={
        "heads": str(cfg.heads), "dn": str(cfg.qk_nope_head_dim),
        "dr": str(cfg.qk_rope_head_dim), "dv": str(cfg.v_head_dim),
        "q_lora": str(cfg.q_lora_rank or "none"),
        "rows": str(h.shape[0]), "seq": str(h.shape[1]), **more})


def _kernels(cfg, window=None):
    """The call in parts of a layer's attention kernels: (q's parts, k's
    parts) -> o [B, S, H, Dv], full-causal or inside ``window``."""
    impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
    return lambda q, k: _attention(q, k, None, causal=True, impl=impl,
                                   scale=cfg.softmax_scale, window=window)


@jax.named_scope("block/attn")
def _mla(cfg: Xing4Config, cos, sin, h, layer, head_gate=None, attend=None,
         after=None):
    """Latent attention of h [B, S, E] -> [B, S, E].  q and k reach the
    kernels in the parts the projections write (``ops.attention``, a call
    in parts): q's weight (``wq_b`` behind the bottleneck, ``wq`` without
    one) is sliced, a weight, so that q's 128 lanes without position and
    its 64 rotary ones are two products' results, and ``kv`` goes as the one
    product leaves it, a head's key and value side by side.  Nothing of q's
    size is concatenated, broadcast, turned or sliced.  The four products
    have scopes of their own under ``mla`` (``q``, ``kv_a``, ``kv_b``,
    ``out``), the rotary passes lie under ``rope``.  ``head_gate`` [E, H]
    (``models/bailing_hybrid.py``; absent for Xing4.0 and Kanana): head h's
    result is multiplied by ``sigmoid(h W)_h`` before the out-projection,
    under ``mla/gate``.  The key heads are ``wkv_b``'s, as many as the query
    heads or a divisor of them (a key head then serves a group of query
    heads).  ``attend``: what stands for the kernels' call (``_kernels``:
    full-causal; ``models/motif.py`` hands a window's, or both under a
    ``lax.cond``); ``after(h, o)``: what follows them before the
    out-projection, whose ``wo`` takes as many heads as it leaves."""
    dt, eps = cfg.dtype, cfg.norm_eps
    dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
    _count_geometry(cfg, h)
    with jax.named_scope("mla"):
        with jax.named_scope("q"):
            if cfg.q_lora_rank is None:
                c_q, wq = h, layer["wq"]
            else:
                c_q, wq = rms_norm(
                    jnp.einsum("bse,er->bsr", h, layer["wq_a"].astype(dt),
                               preferred_element_type=dt),
                    layer["q_norm"], eps), layer["wq_b"]
            q_n = _project(c_q, wq[..., :dn], dt)           # [B, S, H, 128]
            q_r = _project(c_q, wq[..., dn:], dt)           # [B, S, H, 64]
        with jax.named_scope("kv_a"):
            kv_a = jnp.einsum("bse,er->bsr", h, layer["wkv_a"].astype(dt),
                              preferred_element_type=dt)    # [B, S, 512 + 64]
        with jax.named_scope("kv_b"):
            c_kv = rms_norm(kv_a[..., :rkv], layer["kv_norm"], eps)
            kv = _project(c_kv, layer["wkv_b"], dt)         # [B, S, H, 256]
    with jax.named_scope("rope"):
        rope = lambda x: rotate_heads(x, cos, sin,
                                      interpret=impl == "flash_interpret")
        q_r = rope(q_r)                                     # [B, H, S, 64]
        k_r = rope(kv_a[..., None, rkv:])                   # [B, 1, S, 64]
    o = (attend or _kernels(cfg))((q_n, q_r), (kv, k_r))    # [B, S, H, 128]
    if after is not None:
        o = after(h, o)
    if head_gate is not None:
        with jax.named_scope("mla/gate"):
            gate = jax.nn.sigmoid(jnp.einsum(
                "bse,eh->bsh", h, head_gate.astype(dt),
                preferred_element_type=jnp.float32))
            o = o * gate[..., None].astype(dt)
    with jax.named_scope("mla/out"):
        H, D, E = layer["wo"].shape
        return jnp.einsum("bsf,fe->bse", o.reshape(*o.shape[:2], H * D),
                          _flat(layer["wo"], (H * D, E), dt),
                          preferred_element_type=dt)


def _sublayer(cfg: Xing4Config, X, layer, name: str, F):
    """``X <- H_res X + H_post (x) F(N(sum_j H_pre[j] X[j]))`` on the stream
    X [B, n, S, C]; ``F`` returns (y, what it reports).  -> (X, the report,
    the largest |row or column sum - 1| of H_res)."""
    norm = layer[f"{name}_norm"]
    clamp = getattr(cfg, "hidden_clamp", None)
    if cfg.hc_mult == 1:
        y, aux = F(rms_norm(X[:, 0], norm, cfg.norm_eps))
        return X + y[:, None], aux, jnp.zeros((), jnp.float32)
    interpret = cfg.attention_impl == "flash_interpret"
    X, u, H_post, H_res = hyper.collect(
        X, layer[f"hc_{name}_phi"], layer[f"hc_{name}_b"],
        layer[f"hc_{name}_alpha"], cfg.hc_sinkhorn_iters, cfg.hc_eps,
        cfg.hc_clamp, cfg.norm_eps, interpret=interpret)
    if clamp:       # ``models/motif.py``: the collected input, clipped
        u = jnp.clip(u, -clamp, clamp)
    y, aux = F(rms_norm(u, norm, cfg.norm_eps))
    return (hyper.deposit(X, H_res, H_post, y, interpret=interpret), aux,
            jax.lax.stop_gradient(hyper.sinkhorn_residual(H_res)))


def _layer(cfg: Xing4Config, cos, sin, X, layer, bias=None):
    """One layer on the stream; ``bias`` is None for a dense layer.  ->
    (X, {"hc_residual", and an expert layer's loads as ``afmoe._moe``'s})."""
    X, _, r_attn = _sublayer(
        cfg, X, layer, "attn", lambda h: (_mla(cfg, cos, sin, h, layer), None))

    def feed_forward(h):
        if bias is not None:
            return _moe(cfg, h, layer, bias)
        with jax.named_scope("block/mlp"):
            return _swiglu(h, layer["w_gate"], layer["w_up"],
                           layer["w_down"], cfg.dtype), {}

    X, loads, r_mlp = _sublayer(cfg, X, layer, "mlp", feed_forward)
    return X, {**loads, "hc_residual": jnp.maximum(r_attn, r_mlp)}


#: how a layer's report over its groups of rows (leading axis) becomes one;
#: what is not named here is a mean
_MERGE = {"hc_residual": jnp.max,
          "counts": lambda a: jnp.sum(a, axis=0), "dropped": jnp.sum,
          "sliced": jnp.sum,
          "top": lambda a: a.reshape(-1, a.shape[-1])}


def _run(cfg, cos, sin, X, layer, bias=None, layer_fn=None, keep=()):
    """The layer under the remat, ``layer_rows`` rows at a time (as
    ``afmoe``'s): (X, the layer's report merged over its groups).
    ``layer_fn``: this module's ``_layer``, or another model's with its
    arguments (``models/motif.py``); ``keep``: ``_lm.remat``'s, the step's
    (``_flash_keep``)."""
    layer_fn = layer_fn or _layer
    one = _lm.remat(lambda X, layer, bias: layer_fn(cfg, cos, sin, X, layer,
                                                    bias), cfg.remat, keep)
    B = X.shape[0]
    n = min(cfg.layer_rows or B, B)
    if B % n:
        raise ValueError(f"a batch of {B} rows does not split into groups "
                         f"of layer_rows={n}")
    if n == B:
        return one(X, layer, bias)
    Y, report = jax.lax.map(lambda rows: one(rows, layer, bias),
                            X.reshape((B // n, n) + X.shape[1:]))
    # (the named ones first and in their order: the program traced for the
    # models without a report of their own is then the one it always was)
    merged = {k: _MERGE.get(k, jnp.mean)(report[k])
              for k in (*(k for k in _MERGE if k in report),
                        *(k for k in report if k not in _MERGE))}
    return Y.reshape(X.shape), merged


def _refuse_a_mesh(cfg: Xing4Config) -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "xing4 on a mesh: the exchange of an expert-parallel group is "
            "not built (ROADMAP M3)")
    if cfg.pp_microbatches:
        raise NotImplementedError(
            "xing4 with pp_microbatches: a pipeline stage hands on one lane, "
            "not hc_mult (ROADMAP M4)")


def _lanes(x, n: int):
    """x [B, S, C] in every one of n lanes: [B, n, S, C]."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], n) + x.shape[1:])


def _collapse(X):
    """The lanes' sum, [B, S, C] in the stream's dtype."""
    return jnp.sum(X.astype(jnp.float32), axis=1).astype(X.dtype)


def _flash_keep(cfg, tokens) -> tuple:
    """``_lm.flash_keep`` for a step on tokens [B, S]: a call of latent
    attention a layer and one for the prediction module's, where there is
    one (``models/motif.py`` counts its noise heads among ``heads``)."""
    return _lm.flash_keep(cfg.remat, cfg.layers + bool(cfg.mtp_layers),
                          (*tokens.shape, cfg.heads, cfg.v_head_dim),
                          cfg.dtype)


def _forward_hidden(params, state, tokens, cfg: Xing4Config, keep=()):
    """tokens [B, S] -> (the lanes' sum after the last layer [B, S, C],
    before the final norm; the expert layers' loads {"counts" [Lm, X],
    "dropped" [Lm], "sliced" [Lm], "top" [Lm, B*S, k]}; the largest
    Sinkhorn residual of the stack; the rotary tables).  ``keep``:
    ``_run``'s."""
    _refuse_a_mesh(cfg)
    dt = cfg.dtype
    with jax.named_scope("embed"):
        X = _lanes(params["embed"].astype(dt)[tokens], cfg.hc_mult)
    cos, sin = rope_lane_tables(cfg.qk_rope_head_dim, cfg.max_seq_len,
                                cfg.rope_theta, cfg.yarn)
    residual = jnp.zeros((), jnp.float32)
    for i in range(cfg.num_dense_layers):
        X, report = _run(cfg, cos, sin, X,
                         jax.tree.map(lambda a: a[i], params["dense"]),
                         keep=keep)
        residual = jnp.maximum(residual, report["hc_residual"])

    def body(X, group):
        return _run(cfg, cos, sin, X, group["layer"], group["bias"],
                    keep=keep)

    Lm = cfg.expert_layers
    if Lm:
        X, loads = jax.lax.scan(body, X, {"layer": params["moe"],
                                          "bias": state["bias"][:Lm]})
        residual = jnp.maximum(residual, jnp.max(loads.pop("hc_residual")))
    else:
        loads = {"counts": jnp.zeros((0, cfg.num_experts), jnp.int32),
                 "dropped": jnp.zeros((0,), jnp.int32),
                 "sliced": jnp.zeros((0,), jnp.int32),
                 "top": jnp.zeros((0, tokens.size, cfg.top_k), jnp.int32)}
    return _collapse(X), loads, residual, (cos, sin)


def forward(params, tokens, cfg: Xing4Config, state=None) -> jax.Array:
    """tokens [B, S] -> next-token logits [B, S, V] float32 (the prediction
    module is a training part: its serving use is ROADMAP's)."""
    x, *_ = _forward_hidden(params, state or init_state(cfg), tokens, cfg)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def mtp_targets_and_mask(targets, mask):
    """The prediction module's targets and mask from the main loss's
    (``_lm.targets_and_mask``): position t is judged on token t + 2 where
    the main loss judges position t + 1, so over t <= S - 3 under a mask
    that covers every position but a row's last."""
    shift = lambda a: jnp.concatenate(
        [a[:, 1:], jnp.zeros_like(a[:, :1])], axis=1)
    return shift(targets), shift(mask)


def _mtp_loss(params, bias, x_out, tables, targets, mask, cfg,
              layer_fn=None, keep=()):
    """(the module's masked mean loss, its layer's report): ``x_out``
    [B, S, C] is the stack's result before the final norm; ``layer_fn``
    and ``keep`` as ``_run``'s."""
    dt, eps, p = cfg.dtype, cfg.norm_eps, params["mtp"]
    with jax.named_scope("mtp"):
        with jax.named_scope("project"):
            # ``targets`` holds token t + 1 at position t.
            pair = jnp.concatenate(
                [rms_norm(x_out, p["h_norm"], eps),
                 rms_norm(params["embed"].astype(dt)[targets], p["e_norm"],
                          eps)], axis=-1)
            z = jnp.einsum("bsf,fe->bse", pair, p["proj"].astype(dt),
                           preferred_element_type=dt)
        Z, report = _run(cfg, *tables, _lanes(z, cfg.hc_mult),
                         jax.tree.map(lambda a: a[0], p["layer"]), bias,
                         layer_fn, keep)
        h = rms_norm(_collapse(Z), p["final_norm"], eps)
        targets2, mask2 = mtp_targets_and_mask(targets, mask)
        with jax.named_scope("loss"):
            total = _lm.token_nll(h, params["lm_head"], targets2,
                                  cfg.loss_chunks, dt, mask2)
        return total / jnp.maximum(jnp.sum(mask2), 1.0), report


def loss_and_report(params, batch, cfg, state=None, stack=None,
                    mtp_layer_fn=None):
    """What the train step differentiates (parallel.spmd): the loss ``main +
    mtp_loss_weight * module's``, and what ``update_state`` turns into the
    step's metrics: both losses, the expert layers' loads (the module's
    layer last), the largest Sinkhorn residual.  ``stack``: this module's
    ``_forward_hidden`` or another model's of the same results, and
    ``mtp_layer_fn`` its module's layer (``models/motif.py``)."""
    state = state or init_state(cfg)
    keep = _flash_keep(cfg, batch["tokens"])
    x, loads, residual, tables = (stack or _forward_hidden)(
        params, state, batch["tokens"], cfg, keep)
    targets, mask, denom = _lm.targets_and_mask(batch)
    with jax.named_scope("final_norm"):
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("loss"):
        main = _lm.token_nll(h, params["lm_head"], targets, cfg.loss_chunks,
                             cfg.dtype, mask) / denom
    loss, mtp = main, jnp.zeros((), jnp.float32)
    if cfg.mtp_layers:
        # Traced on its own, so that the scope ``mtp`` stays a scope in the
        # backward's operations too (models/ouro._scoped has the reason).
        mtp, report = jax.jit(
            lambda params, bias, x, tables, targets, mask: _mtp_loss(
                params, bias, x, tables, targets, mask, cfg, mtp_layer_fn,
                keep))(
            params, state["bias"][-1], x, tables, targets, mask)
        residual = jnp.maximum(residual, report.pop("hc_residual"))
        loads = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]),
                             loads, report)
        loss = main + cfg.mtp_loss_weight * mtp
    return loss, jax.lax.stop_gradient(
        {**loads, "main_loss": main, "mtp_loss": mtp,
         "hc_residual": residual})


def loss_fn(params, batch, cfg: Xing4Config, state=None) -> jax.Array:
    return loss_and_report(params, batch, cfg, state)[0]


def update_state(state, report, cfg: Xing4Config):
    """(the state after a step with this report, the step's metrics):
    ``afmoe``'s metrics of the loads over the expert layers and the
    module's, ``main_loss``, ``mtp_loss`` and ``hc_sinkhorn_residual``."""
    state, metrics = afmoe.update_state(state, report, cfg)
    return state, {**metrics, "main_loss": report["main_loss"],
                   "mtp_loss": report["mtp_loss"],
                   "hc_sinkhorn_residual": report["hc_residual"]}
