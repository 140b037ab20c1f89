"""The ``afmoe`` decoder (Arcee Trinity: Trinity-Mini is 26B-A3B): a stack of
layer kinds, sigmoid-routed dropless experts beside a shared one.

What it has that ``models/llama.py`` has not:

- Two kinds of attention in one stack (``layer_types``): ``sliding_attention``
  layers attend in a window (``0 <= t - s < sliding_window``) with rotary
  positions, ``full_attention`` layers over the whole row with no positional
  term.  Both: RMSNorm on each query and key head, grouped-query attention,
  and an output gate ``sigmoid(x W_g)`` on the attention result.
- Four RMSNorms a layer: ``a = h + N2(Attn(N1(h)))``,
  ``h' = a + N4(F(N3(a)))``.
- ``num_dense_layers`` leading layers whose ``F`` is a SwiGLU; in the others
  ``F(x) = Shared(x) + sum_{e in top} w_e Expert_e(x)`` with the sigmoid
  router of ``ops/moe.py``: nothing is dropped.  The selection bias is STATE
  (``init_state``), not a parameter: no gradient reaches it, and
  ``update_state`` moves it after every step from the experts' loads.
- muP's ``sqrt(hidden)`` on the embedding.
- A layer may hold a share of its experts (``experts_held`` of
  ``num_experts`` from ``held_start``): the router scores them all, the layer
  adds the held experts' part, as one chip of an expert-parallel group does,
  without the exchange.  A sliced vocabulary is a smaller ``vocab_size``.

The stack: dense layers are unrolled; the expert layers are one ``lax.scan``
whose body serves both kinds of attention, chosen by a flag that rides with
the layer's weights (``lax.cond`` round the attention call), so compile time
grows neither with depth nor with the period of ``layer_types``.  Cross-entropy, remat, norm and rotary code are ``models/_lm.py``'s and
``ops/``'s, shared with Llama.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import _lm
from ..ops import moe
from ..ops.attention import attention as _attention
from ..ops.norms import rms_norm
from ..ops.rope import rope_lane_tables, rotate_heads

SLIDING, FULL = "sliding_attention", "full_attention"

def _published_layer_types(layers: int, every: int = 4) -> Tuple[str, ...]:
    return tuple(FULL if (i + 1) % every == 0 else SLIDING
                 for i in range(layers))


@dataclass(frozen=True)
class AfmoeConfig:
    """Defaults are Trinity-Mini's published ``config.json``."""
    vocab_size: int = 200192
    hidden: int = 2048
    layers: int = 32
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    mlp_dim: int = 6144                 # the dense layers' SwiGLU
    moe_mlp_dim: int = 1024             # every expert's, and the shared one's
    num_experts: int = 128              # the router's width
    experts_held: Optional[int] = None  # None = all of them
    held_start: int = 0
    top_k: int = 8
    num_shared_experts: int = 1
    num_dense_layers: int = 2
    layer_types: Optional[Tuple[str, ...]] = None   # None = 3 sliding : 1 full
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    route_scale: float = 2.826
    route_norm: bool = True
    bias_update_rate: float = 1e-3      # ``load_balance_coeff``
    mup: bool = True
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    # "auto" (flash on TPU / reference on CPU), "reference", "flash",
    # "flash_interpret"
    attention_impl: str = "auto"
    # None (Pallas grouped matmul on TPU, lax.ragged_dot elsewhere), "gmm",
    # "gmm_interpret", "ragged_dot": ops/moe.grouped_matmul
    moe_impl: Optional[str] = None
    remat: Any = True                   # _lm.remat
    # A layer takes at most this many rows of its batch at a time, one group
    # after the other, each under the remat, so that its temporaries are one
    # group's (None: the whole batch at once).  It trades the rows a kernel
    # call sees for memory: a grouped product's groups and a flash call's
    # batch are a group's, not the step's.
    layer_rows: Optional[int] = None
    loss_chunks: int = 0

    def replace(self, **kw) -> "AfmoeConfig":
        return dataclasses.replace(self, **kw)

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = self.layer_types or _published_layer_types(self.layers)
        if len(kinds) < self.layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError(f"layer_types does not name {self.layers} "
                             f"layers of {SLIDING} / {FULL}: {kinds}")
        return tuple(kinds[:self.layers])

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_layers(self) -> int:
        return self.layers - self.num_dense_layers


def afmoe_tiny(**kw) -> AfmoeConfig:
    """A CPU-test size: 1 dense + 4 expert layers (S S F S after the dense
    layer), 8 experts with 4 a token, a window shorter than the row."""
    return AfmoeConfig(**{**dict(
        vocab_size=256, hidden=64, layers=5, heads=4, kv_heads=2,
        head_dim=16, mlp_dim=96, moe_mlp_dim=32, num_experts=8, top_k=4,
        num_dense_layers=1, sliding_window=24, max_seq_len=64,
        dtype=jnp.float32, attention_impl="reference", remat=False), **kw})


def _attn_axes() -> Dict[str, Any]:
    return {
        "attn_norm": ("layers", None), "attn_post_norm": ("layers", None),
        "mlp_norm": ("layers", None), "mlp_post_norm": ("layers", None),
        "q_norm": ("layers", None), "k_norm": ("layers", None),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wg": ("layers", "embed", "heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed")}


def param_logical_axes(cfg: AfmoeConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    return {
        "embed": ("vocab", "embed"),
        "dense": {**_attn_axes(),
                  "w_gate": ("layers", "embed", "mlp"),
                  "w_up": ("layers", "embed", "mlp"),
                  "w_down": ("layers", "mlp", "embed")},
        "moe": {**_attn_axes(),
                "router": ("layers", "embed", None),
                "shared_gate": ("layers", "embed", "mlp"),
                "shared_up": ("layers", "embed", "mlp"),
                "shared_down": ("layers", "mlp", "embed"),
                "w_gate": ("layers", "expert", "embed", "mlp"),
                "w_up": ("layers", "expert", "embed", "mlp"),
                "w_down": ("layers", "expert", "mlp", "embed")},
        "final_norm": (None,),
        "lm_head": ("embed", "vocab")}


def param_shapes(cfg: AfmoeConfig) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a norm weight, which starts at one)."""
    E, H, K, D, V = (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim,
                     cfg.vocab_size)

    def attn(L):
        return {
            "attn_norm": ((L, E), 0), "attn_post_norm": ((L, E), 0),
            "mlp_norm": ((L, E), 0), "mlp_post_norm": ((L, E), 0),
            "q_norm": ((L, D), 0), "k_norm": ((L, D), 0),
            "wq": ((L, E, H, D), E), "wk": ((L, E, K, D), E),
            "wv": ((L, E, K, D), E), "wg": ((L, E, H, D), E),
            "wo": ((L, H, D, E), H * D)}

    Ld, Lm = cfg.num_dense_layers, cfg.expert_layers
    M, Me, X, Xh = cfg.mlp_dim, cfg.moe_mlp_dim, cfg.num_experts, cfg.held
    Ms = Me * cfg.num_shared_experts
    return {
        "embed": ((V, E), E),
        "dense": {**attn(Ld), "w_gate": ((Ld, E, M), E),
                  "w_up": ((Ld, E, M), E), "w_down": ((Ld, M, E), M)},
        "moe": {**attn(Lm), "router": ((Lm, E, X), E),
                "shared_gate": ((Lm, E, Ms), E),
                "shared_up": ((Lm, E, Ms), E),
                "shared_down": ((Lm, Ms, E), Ms),
                "w_gate": ((Lm, Xh, E, Me), E), "w_up": ((Lm, Xh, E, Me), E),
                "w_down": ((Lm, Xh, Me, E), Me)},
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E)}


_is_shape = _lm.is_shape


def init_params(cfg: AfmoeConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    return _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)


def num_params(cfg: AfmoeConfig) -> int:
    return _lm.count_params(param_shapes(cfg))


def init_state(cfg: AfmoeConfig) -> Dict[str, jax.Array]:
    """What a train step carries beside the parameters and that no optimizer
    touches: the routers' selection bias, float32 [expert layers, experts]."""
    return {"bias": jnp.zeros((cfg.expert_layers, cfg.num_experts),
                              jnp.float32)}


def _swiglu(h, w_gate, w_up, w_down, dt, act=jax.nn.silu):
    gate = jnp.einsum("bse,em->bsm", h, w_gate.astype(dt),
                      preferred_element_type=dt)
    up = jnp.einsum("bse,em->bsm", h, w_up.astype(dt),
                    preferred_element_type=dt)
    return jnp.einsum("bsm,me->bse", act(gate) * up,
                      w_down.astype(dt), preferred_element_type=dt)


def _attend(cfg: AfmoeConfig, full, cos, sin, q, k, v):
    """The layer's kind of attention over q / k as their projections leave
    them, [B, S, H, D], and v [B, H, S, D]: a window layer's rotary
    embedding places q and k head-major on the way (``rotate_heads``), a
    full layer has no positions and only turns them.  ``full`` is a Python
    bool where the kind is known when tracing, or a traced scalar where one
    scanned body serves both kinds (``lax.cond``)."""
    impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
    rope = partial(rotate_heads, cos2=cos, sin2=sin,
                   interpret=impl == "flash_interpret")

    @jax.named_scope("block/attn_window")
    def in_window(q, k, v):
        return _attention(rope(q), rope(k), v, causal=True, impl=impl,
                          window=cfg.sliding_window)

    @jax.named_scope("block/attn_full")
    def over_the_row(q, k, v):
        return _attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), v,
                          causal=True, impl=impl)

    if isinstance(full, bool):
        return (over_the_row if full else in_window)(q, k, v)
    return jax.lax.cond(full, over_the_row, in_window, q, k, v)


@jax.named_scope("block/attn")
def _attn_half(cfg: AfmoeConfig, full, cos, sin, x, layer):
    """a = h + N2(Attn(N1(h))).  x: [B, S, E]."""
    dt, eps = cfg.dtype, cfg.norm_eps
    h = rms_norm(x, layer["attn_norm"], eps)
    q = _lm.project_heads(h, layer["wq"], dt)
    k = _lm.project_heads(h, layer["wk"], dt)
    v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"].astype(dt),
                   preferred_element_type=dt)
    attn = _attend(cfg, full, cos, sin, rms_norm(q, layer["q_norm"], eps),
                   rms_norm(k, layer["k_norm"], eps), v)
    gate = jax.nn.sigmoid(jnp.einsum(
        "bse,ehd->bhsd", h, layer["wg"].astype(dt),
        preferred_element_type=dt))
    out = jnp.einsum("bhsd,hde->bse", attn * gate, layer["wo"].astype(dt),
                     preferred_element_type=dt)
    return x + rms_norm(out, layer["attn_post_norm"], eps)


def _feed_forward(h, w_gate, w_up, w_down, dt, act="silu", act_weights=None):
    """``act(h W_gate) * (h W_up)`` then ``W_down`` (a SwiGLU where ``act``
    is silu), or with no gate (``w_gate`` None) ``act(h W_up) W_down``: the
    forms ``ops/moe.dropless_experts`` takes, as are ``act`` and an
    activation's own weights (``moe.activation_of``)."""
    act = moe.activation_of(act, act_weights)
    if w_gate is not None:
        return _swiglu(h, w_gate, w_up, w_down, dt, act)
    up = jnp.einsum("bse,em->bsm", h, w_up.astype(dt),
                    preferred_element_type=dt)
    return jnp.einsum("bsm,me->bse", act(up),
                      w_down.astype(dt), preferred_element_type=dt)


def _moe(cfg, h, layer, bias, act: str = "silu", route_eps=1e-20,
         act_weights=lambda p: None):
    """F(h) of an expert layer and its loads: (out [B, S, E], {"counts" [X]
    int32 over all the experts, "dropped" int32, "sliced" int32 (1 if the
    call took the buffer in slices), "top" [B*S, k] the router's choices}).
    A layer without ``w_gate`` / ``shared_gate`` has un-gated experts
    (``act(h W_up) W_down``), one without ``shared_up`` no shared expert
    (``models/lfm2.py``); ``cfg`` is any configuration with this one's
    routing and share fields (``models/xing4.py``, ``models/nemotron_h.py``).
    ``act_weights(p)``: the keyword arguments of an activation that has
    weights of its own from a module's ``p`` (``models/motif.py``: PolyNorm's
    four numbers, ``layer["shared_poly"]`` for the shared expert and
    ``layer["expert_poly"]`` for the routed ones together)."""
    dt = cfg.dtype
    gate = layer.get("w_gate")
    B, S, E = h.shape
    with jax.named_scope("block/moe"):
        with jax.named_scope("route"):
            routing = moe.sigmoid_routing(
                h.reshape(B * S, E), layer["router"], bias, cfg.top_k,
                cfg.route_scale, cfg.route_norm, route_eps,
                # one group, but for ``models/bailing_hybrid.py``
                getattr(cfg, "n_group", 1), getattr(cfg, "topk_group", 1))
        shared = None
        if "shared_up" in layer:
            with jax.named_scope("shared"):
                shared = _feed_forward(
                    h, layer.get("shared_gate"), layer["shared_up"],
                    layer["shared_down"], dt, act,
                    act_weights(layer.get("shared_poly")))
        routed, (held, dropped) = moe.dropless_experts(
            h.reshape(B * S, E), routing,
            None if gate is None else gate.astype(dt),
            layer["w_up"].astype(dt), layer["w_down"].astype(dt),
            held_start=cfg.held_start, impl=cfg.moe_impl, activation=act,
            act_weights=act_weights(layer.get("expert_poly")))
        routed = routed.reshape(B, S, E).astype(dt)
        return (routed if shared is None else shared + routed,
                {"counts": routing.counts, "dropped": dropped,
                 "sliced": (held > moe.buffer_rows(
                     B * S, cfg.top_k, layer["w_up"].shape[0],
                     routing.counts.shape[0])).astype(jnp.int32),
                 "top": routing.expert_index})


def _layer(cfg: AfmoeConfig, full, cos, sin, x, layer, bias=None):
    """One layer; ``bias`` is None for a dense layer."""
    a = _attn_half(cfg, full, cos, sin, x, layer)
    h = rms_norm(a, layer["mlp_norm"], cfg.norm_eps)
    if bias is None:
        with jax.named_scope("block/mlp"):
            f, loads = _swiglu(h, layer["w_gate"], layer["w_up"],
                               layer["w_down"], cfg.dtype), None
    else:
        f, loads = _moe(cfg, h, layer, bias)
    return a + rms_norm(f, layer["mlp_post_norm"], cfg.norm_eps), loads


def _forward_hidden(params, state, tokens, cfg: AfmoeConfig):
    """tokens [B, S] -> (final hidden [B, S, E], loads of the expert layers:
    {"counts" [Lm, X], "dropped" [Lm], "sliced" [Lm], "top" [Lm, B*S, k]})."""
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "afmoe on a mesh: the exchange of an expert-parallel group is "
            "not built (ROADMAP)")
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
        if cfg.mup:
            x = x * jnp.asarray(math.sqrt(cfg.hidden), dt)
    cos, sin = rope_lane_tables(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    keep = _lm.flash_keep(cfg.remat, cfg.layers,
                          (*tokens.shape, cfg.heads, cfg.head_dim), dt)

    def run(x, layer, full, bias=None):
        """The layer under the remat, ``layer_rows`` rows at a time.  A
        kind known when tracing (a Python bool) is closed over; a traced
        flag is an argument of the rematted function."""
        static = isinstance(full, bool)
        flag = None if static else full
        one = _lm.remat(
            lambda x, layer, flag, bias: _layer(
                cfg, full if static else flag, cos, sin, x, layer, bias),
            cfg.remat, keep)
        B = x.shape[0]
        n = min(cfg.layer_rows or B, B)
        if B % n:
            raise ValueError(f"a batch of {B} rows does not split into "
                             f"groups of layer_rows={n}")
        if n == B:
            return one(x, layer, flag, bias)
        y, loads = jax.lax.map(
            lambda rows: one(rows, layer, flag, bias),
            x.reshape((B // n, n) + x.shape[1:]))
        if loads is not None:
            loads = {"counts": jnp.sum(loads["counts"], axis=0),
                     "dropped": jnp.sum(loads["dropped"]),
                     "sliced": jnp.sum(loads["sliced"]),
                     "top": loads["top"].reshape(-1, cfg.top_k)}
        return y.reshape(x.shape), loads

    kinds, Ld = [k == FULL for k in cfg.kinds], cfg.num_dense_layers
    for i in range(Ld):
        x, _ = run(x, jax.tree.map(lambda a: a[i], params["dense"]), kinds[i])

    # One scanned body serves every expert layer: the kind of attention is
    # a flag that rides with the layer's weights (or is fixed, if they are
    # all of one kind), so compile time grows neither with depth nor with
    # the period of ``layer_types``.
    moe_kinds = kinds[Ld:]
    mixed = len(set(moe_kinds)) > 1
    stacked = {"layer": params["moe"], "bias": state["bias"]}
    if mixed:
        stacked["full"] = jnp.asarray(moe_kinds)

    def body(x, group):
        return run(x, group["layer"],
                   group["full"] if mixed else moe_kinds[0], group["bias"])

    if moe_kinds:
        x, loads = jax.lax.scan(body, x, stacked)
    else:
        loads = {"counts": jnp.zeros((0, cfg.num_experts), jnp.int32),
                 "dropped": jnp.zeros((0,), jnp.int32),
                 "sliced": jnp.zeros((0,), jnp.int32),
                 "top": jnp.zeros((0, tokens.size, cfg.top_k), jnp.int32)}
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, loads


def forward(params, tokens, cfg: AfmoeConfig, state=None) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32."""
    x, _ = _forward_hidden(params, state or init_state(cfg), tokens, cfg)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def loss_and_loads(params, state, batch, cfg: AfmoeConfig):
    """(next-token cross-entropy, the expert layers' loads).  No auxiliary
    term: balance comes from the selection bias."""
    x, loads = _forward_hidden(params, state, batch["tokens"], cfg)
    return _lm.next_token_loss(x, params["lm_head"], batch, cfg.loss_chunks,
                               cfg.dtype), loads


def loss_and_report(params, batch, cfg: AfmoeConfig, state=None):
    """What the train step differentiates (parallel.spmd): the loss, and the
    loads as what it reports, which ``update_state`` turns into the step's
    metrics."""
    return loss_and_loads(params, state or init_state(cfg), batch, cfg)


def loss_fn(params, batch, cfg: AfmoeConfig, state=None) -> jax.Array:
    return loss_and_report(params, batch, cfg, state)[0]


def update_state(state, loads, cfg: AfmoeConfig):
    """(the state after a step with these loads, the step's metrics from
    them): layer-means of the assignments to held experts, of the held
    experts' largest load over their mean load, and of the assignments not
    computed (0: the dispatch is dropless); the number of layer calls of
    the step that took the buffer in slices; and the routers' choices
    themselves, int32 [expert layers, tokens, k] over all the experts, so
    that what a step routed where can be read from the step that did it."""
    counts = loads["counts"]
    held = counts[:, cfg.held_start:cfg.held_start + cfg.held
                  ].astype(jnp.float32)
    mean = jnp.mean(held, axis=-1)
    metrics = {
        "moe_held_assignments": jnp.mean(jnp.sum(held, axis=-1)),
        "moe_load_max_over_mean": jnp.mean(
            jnp.max(held, axis=-1) / jnp.maximum(mean, 1.0)),
        "moe_dropped": jnp.mean(loads["dropped"].astype(jnp.float32)),
        "moe_sliced_calls": jnp.sum(loads["sliced"].astype(jnp.float32)),
        "moe_choices": loads["top"]}
    return {"bias": moe.update_selection_bias(
        state["bias"], counts, cfg.bias_update_rate)}, metrics
