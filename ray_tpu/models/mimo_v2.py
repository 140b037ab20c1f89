"""The ``mimo_v2_flash`` decoder (Xiaomi MiMo-V2-Flash, 309B-A15B): window
layers whose softmax carries a learned sink beside full layers, each kind with
head counts and a rotary base of its own, over sigmoid-routed experts.

What it has that no other model here has:

- **A learned attention sink on the window layers**
  (``add_swa_attention_sink_bias``): a scalar ``b_h`` a query head, one more
  column of every row's softmax whose value is zero, ``p_ts = exp(s_ts) /
  (exp(b_h) + sum_s' exp(s_ts'))``: it takes mass and adds nothing
  (``ops/attention``'s ``sink``: the forward kernel starts a row's online
  softmax at it, the backward kernels run as they are).  What it took,
  ``exp(b_h - lse_t)``, is reported a layer (``sink_mass``).
- **Head counts and a rotary base by layer kind**: a full layer has
  ``kv_heads`` key heads and ``rope_theta``, a window layer ``swa_kv_heads``
  and ``swa_rope_theta``: two tables a model, two shapes of ``wk`` / ``wv``.
- **Grouped-query attention at 192 / 128 in one part**: q and k heads of 192
  lanes of which the FIRST ``rotary_dim`` = 64 turn and 128 carry no position,
  every key head with rotary lanes of its own (latent attention's call in
  parts has one); values of 128, scaled by ``value_scale`` = 0.707.
- **A chip's share of the heads** (``heads_held`` of ``heads`` from
  ``head_start``, with the key heads those read) beside its share of the
  experts (``experts_held`` / ``held_start``, ``models/afmoe.py``): ``wq``,
  ``wk``, ``wv``, ``wo`` and ``sink`` hold the held heads alone, the output
  projection adds its own heads' part, and that partial result goes on, as
  one chip of a head-parallel group does before its all-reduce, which is not
  run.  ``take_share`` cuts a whole model's parameters to a share's.

A layer is ``h = x + Attn_kind(N1(x))``, ``y = h + F(N2(h))``: two RMSNorms,
no post-norms; ``num_dense_layers`` leading layers have a SwiGLU for F, the
others ``sum_{e in top} w_e Expert_e`` with no shared expert
(``models/afmoe._moe`` on ``ops/moe.py``: sigmoid router, dropless held
experts, the selection bias as state).  The layers are unrolled (the two
kinds' weights have different shapes), each under the remat ``layer_rows``
rows at a time (``_lm.rows_at_a_time``); a layer's weights are a dictionary
of their own in ``params["layers"]``, as ``models/lfm2.py``'s.  The model's
three multi-token-prediction layers and its vision and audio encoders have
no key in the published language-model configuration and are not built.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import _lm, afmoe
from ..ops.attention import attention as _attention
from ..ops.norms import rms_norm
from ..ops.rope import rope_lane_tables, rotate_heads

WINDOW, FULL = "sliding_attention", "full_attention"


def _published_layer_types(layers: int) -> Tuple[str, ...]:
    """``hybrid_layer_pattern``: layer 0 full, then five window layers to a
    full one."""
    return tuple(FULL if i == 0 or i % 6 == 5 else WINDOW
                 for i in range(layers))


@dataclass(frozen=True)
class MimoV2Config:
    """Defaults are MiMo-V2-Flash's published ``config.json``."""
    vocab_size: int = 152576
    hidden: int = 4096
    layers: int = 48
    layer_types: Optional[Tuple[str, ...]] = None   # None = the published
    heads: int = 64                     # a full layer's query heads
    kv_heads: int = 4                   # and key heads
    swa_heads: int = 64                 # a window layer's query heads
    swa_kv_heads: int = 8               # and key heads
    heads_held: Optional[int] = None    # None = all of them
    head_start: int = 0
    head_dim: int = 192
    v_head_dim: int = 128
    rotary_dim: int = 64                # int(partial_rotary_factor * 192)
    rope_theta: float = 5e6
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    value_scale: float = 0.707          # ``attention_value_scale``
    sink_start: float = 0.0             # where ``init_params`` starts b_h
    mlp_dim: int = 16384                # the dense layers' SwiGLU
    moe_mlp_dim: int = 2048             # an expert's
    num_experts: int = 256              # the router's width
    experts_held: Optional[int] = None  # None = all of them
    held_start: int = 0
    top_k: int = 8
    num_dense_layers: int = 1
    route_scale: float = 1.0            # ``routed_scaling_factor`` null
    route_norm: bool = True             # ``norm_topk_prob``
    route_eps: float = 1e-20            # added to the chosen scores' sum
    bias_update_rate: float = 1e-3
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

    def replace(self, **kw) -> "MimoV2Config":
        return dataclasses.replace(self, **kw)

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = self.layer_types or _published_layer_types(self.layers)
        if len(kinds) < self.layers or set(kinds) - {WINDOW, FULL}:
            raise ValueError(f"layer_types does not name {self.layers} "
                             f"layers of {WINDOW} / {FULL}: {kinds}")
        return tuple(kinds[:self.layers])

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_layers(self) -> int:
        return self.layers - self.num_dense_layers

    def heads_of(self, kind: str) -> Tuple[int, int, int]:
        """(query heads, key heads, the first key head) a layer of ``kind``
        holds here.  A share is ``heads_held`` query heads from
        ``head_start`` and the key heads they read: whole groups of the
        published ``heads / kv_heads`` query heads a key head, or, where a
        share is less than a group, the one key head it lies under (then
        held by every share of that group)."""
        H, K = ((self.heads, self.kv_heads) if kind == FULL
                else (self.swa_heads, self.swa_kv_heads))
        held = self.heads_held
        if held is None:
            return H, K, 0
        group = H // K
        if (self.heads != self.swa_heads or H % held
                or self.head_start % held or self.head_start >= H
                or (held % group and group % held)):
            raise ValueError(
                f"a share of {held} query heads from {self.head_start} does "
                f"not divide {H} heads in groups of {group}")
        return held, max(held // group, 1), self.head_start // group


def mimo_v2_tiny(**kw) -> MimoV2Config:
    """A CPU-test size that keeps what the code must tell apart: the dense
    full layer, then W W F W; 8 query heads over 2 key heads on full layers
    and 4 on window layers, head sizes 24 / 16 of which 8 lanes turn, a
    window shorter than the row, 8 experts with 4 a token, sinks that take
    a visible share."""
    return MimoV2Config(**{**dict(
        vocab_size=256, hidden=64, layers=5,
        layer_types=(FULL, WINDOW, WINDOW, FULL, WINDOW), heads=8,
        kv_heads=2, swa_heads=8, swa_kv_heads=4, head_dim=24, v_head_dim=16,
        rotary_dim=8, sliding_window=24, sink_start=1.0, mlp_dim=96,
        moe_mlp_dim=32, num_experts=8, top_k=4, num_dense_layers=1,
        max_seq_len=64, dtype=jnp.float32, attention_impl="reference",
        remat=False), **kw})


# ------------------------------------------------------------- parameters

def _stack(cfg: MimoV2Config):
    """(kind, whether its F is dense) down the stack."""
    return [(kind, i < cfg.num_dense_layers)
            for i, kind in enumerate(cfg.kinds)]


def _layer_shapes(cfg: MimoV2Config, kind: str, dense: bool) -> Dict[str, Any]:
    E, D, Dv = cfg.hidden, cfg.head_dim, cfg.v_head_dim
    H, K, _ = cfg.heads_of(kind)
    attn = {"wq": ((E, H, D), E), "wk": ((E, K, D), E),
            "wv": ((E, K, Dv), E), "wo": ((H, Dv, E), H * Dv)}
    if kind == WINDOW:      # ``add_swa_attention_sink_bias``; full: none
        attn["sink"] = ((H,), 0, cfg.sink_start)
    if dense:
        M = cfg.mlp_dim
        f = {"w_gate": ((E, M), E), "w_up": ((E, M), E),
             "w_down": ((M, E), M)}
    else:
        Me, X, Xh = cfg.moe_mlp_dim, cfg.num_experts, cfg.held
        f = {"router": ((E, X), E), "w_gate": ((Xh, E, Me), E),
             "w_up": ((Xh, E, Me), E), "w_down": ((Xh, Me, E), Me)}
    return {"attn_norm": ((E,), 0), **attn, "mlp_norm": ((E,), 0), **f}


_ATTN_AXES = {"wq": ("embed", "heads", "head_dim"),
              "wk": ("embed", "kv_heads", "head_dim"),
              "wv": ("embed", "kv_heads", "head_dim"),
              "wo": ("heads", "head_dim", "embed"), "sink": (None,)}
_F_AXES = {
    True: {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
           "w_down": ("mlp", "embed")},
    False: {"router": ("embed", None), "w_gate": ("expert", "embed", "mlp"),
            "w_up": ("expert", "embed", "mlp"),
            "w_down": ("expert", "mlp", "embed")}}


def param_shapes(cfg: MimoV2Config) -> Dict[str, Any]:
    """leaf -> (shape, fan-in[, start]; fan-in 0 marks a weight that starts
    at a constant: a norm's at one, a sink at ``cfg.sink_start``)."""
    V, E = cfg.vocab_size, cfg.hidden
    return {"embed": ((V, E), E),
            "layers": [_layer_shapes(cfg, kind, dense)
                       for kind, dense in _stack(cfg)],
            "final_norm": ((E,), 0),
            "lm_head": ((E, V), E)}


def param_logical_axes(cfg: MimoV2Config) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    return {"embed": ("vocab", "embed"),
            "layers": [{"attn_norm": (None,), "mlp_norm": (None,),
                        **{n: _ATTN_AXES[n] for n in shapes
                           if n in _ATTN_AXES}, **_F_AXES[dense]}
                       for (_, dense), shapes in zip(
                           _stack(cfg), param_shapes(cfg)["layers"])],
            "final_norm": (None,),
            "lm_head": ("embed", "vocab")}


def init_params(cfg: MimoV2Config, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    return _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)


def num_params(cfg: MimoV2Config) -> int:
    return _lm.count_params(param_shapes(cfg))


def init_state(cfg: MimoV2Config) -> Dict[str, jax.Array]:
    """The routers' selection bias, float32 [expert layers, experts]: state
    that no optimizer touches (``models/afmoe.py``)."""
    return {"bias": jnp.zeros((cfg.expert_layers, cfg.num_experts),
                              jnp.float32)}


def take_share(params, cfg: MimoV2Config) -> Dict[str, Any]:
    """The parameters of the share ``cfg`` names (``heads_held`` from
    ``head_start``, ``experts_held`` from ``held_start``) out of those of the
    whole model (``cfg`` with both None): columns of ``wq``, ``wk`` and
    ``wv``, rows of ``wo``, entries of ``sink``, and the held experts'
    matrices.  Everything else, the routers among it, is every share's."""
    layers = []
    for (kind, _), layer in zip(_stack(cfg), params["layers"]):
        H, K, k0 = cfg.heads_of(kind)
        q0 = cfg.head_start if cfg.heads_held else 0
        q, kv = slice(q0, q0 + H), slice(k0, k0 + K)
        cut = {"wq": layer["wq"][:, q], "wk": layer["wk"][:, kv],
               "wv": layer["wv"][:, kv], "wo": layer["wo"][q]}
        if "sink" in layer:
            cut["sink"] = layer["sink"][q]
        if "router" in layer:
            held = slice(cfg.held_start, cfg.held_start + cfg.held)
            cut.update({n: layer[n][held]
                        for n in ("w_gate", "w_up", "w_down")})
        layers.append({**layer, **cut})
    return {**params, "layers": layers}


# ------------------------------------------------------------------ layers

@jax.named_scope("block/attn")
def _attn(cfg: MimoV2Config, kind: str, tables, x, layer):
    """(Attn_kind of the normed stream x [B, S, E], the mean share of a
    row's mass that the layer's sink took or None): the held heads' part."""
    dt = cfg.dtype
    impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
    cos, sin = tables[kind]
    q = _lm.project_heads(x, layer["wq"], dt)
    k = _lm.project_heads(x, layer["wk"], dt)
    v = jnp.einsum("bse,ehd->bhsd", x, layer["wv"].astype(dt),
                   preferred_element_type=dt) * jnp.asarray(cfg.value_scale,
                                                            dt)
    sink = layer.get("sink")
    window = cfg.sliding_window if kind == WINDOW else None
    with jax.named_scope("block/attn_window" if kind == WINDOW
                         else "block/attn_full"):
        q, k = (rotate_heads(t, cos, sin, rotary_dim=cfg.rotary_dim)
                for t in (q, k))
        if sink is None:
            o, mass = _attention(q, k, v, causal=True, impl=impl,
                                 window=window), None
        else:
            sink = sink.astype(jnp.float32)
            o, lse = _attention(q, k, v, causal=True, impl=impl,
                                window=window, sink=sink, lse=True)
            mass = jax.lax.stop_gradient(
                jnp.mean(jnp.exp(sink[None, :, None] - lse)))
    return jnp.einsum("bhsd,hde->bse", o, layer["wo"].astype(dt),
                      preferred_element_type=dt), mass


def _layer(cfg: MimoV2Config, kind: str, tables, x, layer, bias=None):
    """One layer: (y, its report: an expert layer's loads as
    ``afmoe._moe``'s, ``bias is None`` marking a dense layer, and
    ``sink_mass`` where the layer has a sink)."""
    a, mass = _attn(cfg, kind, tables,
                    rms_norm(x, layer["attn_norm"], cfg.norm_eps), layer)
    h = x + a
    f = rms_norm(h, layer["mlp_norm"], cfg.norm_eps)
    if bias is None:
        with jax.named_scope("block/mlp"):
            f, report = afmoe._swiglu(f, layer["w_gate"], layer["w_up"],
                                      layer["w_down"], cfg.dtype), {}
    else:
        f, report = afmoe._moe(cfg, f, layer, bias, route_eps=cfg.route_eps)
    if mass is not None:
        report = {**report, "sink_mass": mass}
    return h + f, report


def _run(cfg: MimoV2Config, kind: str, tables, x, layer, bias, keep):
    """The layer under the remat, ``layer_rows`` rows at a time; ``keep``:
    ``_lm.remat``'s, the stack's."""
    one = _lm.remat(lambda x, layer, bias: _layer(cfg, kind, tables, x,
                                                  layer, bias), cfg.remat,
                    keep)
    return _lm.rows_at_a_time(lambda rows: one(rows, layer, bias), x,
                              cfg.layer_rows, cfg.top_k)


def _refuse_a_mesh(cfg: MimoV2Config) -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "mimo_v2 on a mesh: neither the exchange of an expert-parallel "
            "group nor the all-reduce that joins head shares is built "
            "(ROADMAP M3, M16)")
    if cfg.pp_microbatches:
        raise NotImplementedError(
            "mimo_v2 with pp_microbatches: its layers are not one stack of "
            "like layers that a pipeline stage could slice (ROADMAP M4)")


def _forward_hidden(params, state, tokens, cfg: MimoV2Config):
    """tokens [B, S] -> (final hidden [B, S, E] after the final norm, the
    expert layers' loads {"counts" [Le, X], "dropped" [Le], "sliced" [Le],
    "top" [Le, B*S, k]} with ``sink_mass`` [layers with a sink])."""
    _refuse_a_mesh(cfg)
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    tables = {kind: rope_lane_tables(cfg.rotary_dim, cfg.max_seq_len, theta)
              for kind, theta in ((FULL, cfg.rope_theta),
                                  (WINDOW, cfg.swa_rope_theta))}
    keep = _lm.flash_keep(
        cfg.remat, cfg.layers,
        (*tokens.shape, max(cfg.heads_of(k)[0] for k in (FULL, WINDOW)),
         cfg.v_head_dim), cfg.dtype)
    loads, masses = [], []
    for (kind, dense), layer in zip(_stack(cfg), params["layers"]):
        bias = None if dense else state["bias"][len(loads)]
        x, report = _run(cfg, kind, tables, x, layer, bias, keep)
        if "sink_mass" in report:
            masses.append(report.pop("sink_mass"))
        if not dense:
            loads.append(report)
    if loads:
        loads = jax.tree.map(lambda *a: jnp.stack(a), *loads)
    else:
        loads = {"counts": jnp.zeros((0, cfg.num_experts), jnp.int32),
                 "dropped": jnp.zeros((0,), jnp.int32),
                 "sliced": jnp.zeros((0,), jnp.int32),
                 "top": jnp.zeros((0, tokens.size, cfg.top_k), jnp.int32)}
    loads["sink_mass"] = (jnp.stack(masses) if masses
                          else jnp.zeros((0,), jnp.float32))
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, loads


def forward(params, tokens, cfg: MimoV2Config, state=None) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32."""
    x, _ = _forward_hidden(params, state or init_state(cfg), tokens, cfg)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def loss_and_report(params, batch, cfg: MimoV2Config, state=None):
    """What the train step differentiates (parallel.spmd): the next-token
    cross-entropy (no auxiliary term), and the expert layers' loads and the
    sinks' masses, which ``update_state`` turns into the step's metrics."""
    x, loads = _forward_hidden(params, state or init_state(cfg),
                               batch["tokens"], cfg)
    return _lm.next_token_loss(x, params["lm_head"], batch, cfg.loss_chunks,
                               cfg.dtype), jax.lax.stop_gradient(loads)


def loss_fn(params, batch, cfg: MimoV2Config, state=None) -> jax.Array:
    return loss_and_report(params, batch, cfg, state)[0]


def update_state(state, loads, cfg: MimoV2Config):
    """(the state after a step with these loads, the step's metrics):
    ``afmoe``'s, and ``sink_mass_mean``, the mean over the layers with a sink,
    their held heads and the positions of the share of a row's softmax mass
    that the sink took (absent where no layer has one)."""
    loads = dict(loads)
    mass = loads.pop("sink_mass")
    state, metrics = afmoe.update_state(state, loads, cfg)
    if mass.size:
        metrics["sink_mass_mean"] = jnp.mean(mass)
    return state, metrics
