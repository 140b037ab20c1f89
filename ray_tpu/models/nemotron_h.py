"""The ``nemotron_h`` decoder (NVIDIA Nemotron-3-Nano-30B-A3B): a hybrid
stack whose layers are ONE sublayer each, read from a pattern string.

What it has that no other model here has:

- **A stack spelt by a pattern** (``hybrid_override_pattern``): ``M`` a
  Mamba-2 mixer, ``E`` an expert layer, ``*`` attention.  Every layer is
  ``x <- x + F(N(x; g))`` with F the one sublayer its letter names; every
  other model here has attention and a feed-forward part in each layer.
- **Mamba-2 mixers** (``ops/ssm.py``): one in-projection to a gate ``z``,
  the convolved channels ``[X ; B ; C]`` and a time step a head; a causal
  depthwise convolution of width ``conv_kernel`` with a silu; the recurrence
  ``S_t = exp(dt A) S_{t-1} + dt X_t (x) B_t``, ``y_t = S_t C_t + D X_t`` in
  chunks of ``chunk_size`` with the state handed on between them; a gate and
  an RMSNorm over ``ssm_groups`` groups of channels; one out-projection.
  Every row starts from a zero state (``_mixer`` takes ``segment_ids`` for
  the model that packs documents into a row: ``models/granite_hybrid.py``).
- **Un-gated experts**: ``relu(x W_up)^2 W_down``, the shared one alike (the
  expert layer is ``models/afmoe.py``'s ``_moe`` on ``ops/moe.py``, as it
  is: sigmoid router, dropless held experts, the selection bias as state; a
  layer may hold a share of its experts).
- **Attention without positions**: grouped-query, 32 query heads on 2 key
  heads, no rotary term and no window; the mixers carry position.

The layers are unrolled (their kinds do not repeat with one period), each
under the remat ``layer_rows`` rows at a time; a layer's weights are a
dictionary of their own in ``params["layers"]``, so a gradient is written
where it is used and no stack of a kind is ever assembled.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import _lm, afmoe
from ..ops import ssm
from ..ops.attention import attention as _attention
from ..ops.norms import rms_norm

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

#: Nemotron-3-Nano-30B-A3B's ``hybrid_override_pattern``
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    """Defaults are Nemotron-3-Nano-30B-A3B's published ``config.json``."""
    vocab_size: int = 131072
    hidden: int = 2688
    layers: int = 52
    pattern: str = PUBLISHED_PATTERN    # the stack is its first ``layers``
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8                 # ``n_groups``: B, C and the norm
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001        # the range ``dt_bias`` starts in
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_mlp_dim: int = 1856             # a routed expert's width
    shared_mlp_dim: int = 3712          # the shared expert's
    num_experts: int = 128              # the router's width
    experts_held: Optional[int] = None  # None = all of them
    held_start: int = 0
    top_k: int = 6
    route_scale: float = 2.5            # ``routed_scaling_factor``
    route_norm: bool = True             # ``norm_topk_prob``
    bias_update_rate: float = 1e-3
    expert_act: str = "relu2"           # ``mlp_hidden_act``; no gate
    # every out-projection starts 1 / sqrt(layers) smaller (GPT-2's rule)
    rescale_prenorm_residual: bool = True
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

    def replace(self, **kw) -> "NemotronHConfig":
        return dataclasses.replace(self, **kw)

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = tuple(self.pattern[:self.layers])
        if len(kinds) < self.layers or set(kinds) - {MAMBA, EXPERTS,
                                                     ATTENTION}:
            raise ValueError(f"pattern does not name {self.layers} layers of "
                             f"M / E / *: {self.pattern!r}")
        return kinds

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_layers(self) -> int:
        return self.kinds.count(EXPERTS)

    @property
    def mamba_dim(self) -> int:
        """Channels of a mixer's X, z and y: heads * head size (not
        ``expand`` * hidden)."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution sees: X, B and C."""
        return self.mamba_dim + 2 * self.ssm_groups * self.ssm_state


def nemotron_h_tiny(**kw) -> NemotronHConfig:
    """A CPU-test size that keeps what the code must tell apart: two heads a
    state group, a head size that is not the state size, 16 query heads a
    key head, 8 experts with 4 a token, every kind of layer (``MEM*EM``)."""
    return NemotronHConfig(**{**dict(
        vocab_size=256, hidden=64, layers=6, pattern="MEM*EM", heads=16,
        kv_heads=1, head_dim=16, mamba_heads=4, mamba_head_dim=8,
        ssm_state=16, ssm_groups=2, chunk_size=16, moe_mlp_dim=32,
        shared_mlp_dim=48, num_experts=8, top_k=4, max_seq_len=64,
        dtype=jnp.float32, attention_impl="reference", remat=False), **kw})


# ------------------------------------------------------------- parameters

def _layer_shapes(cfg: NemotronHConfig, kind: str) -> Dict[str, Any]:
    E, d, H = cfg.hidden, cfg.mamba_dim, cfg.mamba_heads
    n = cfg.layers if cfg.rescale_prenorm_residual else 1   # on a fan-in
    if kind == MAMBA:
        return {"norm": ((E,), 0),
                "w_in": ((E, d + cfg.conv_dim + H), E),
                "conv_w": ((cfg.conv_kernel, cfg.conv_dim), cfg.conv_kernel),
                "conv_b": ((cfg.conv_dim,), cfg.conv_kernel),
                "A_log": ((H,), 0), "dt_bias": ((H,), 0), "D": ((H,), 0),
                "gate_norm": ((d,), 0),
                "w_out": ((d, E), d * n)}
    if kind == EXPERTS:
        Me, Ms, X, Xh = (cfg.moe_mlp_dim, cfg.shared_mlp_dim,
                         cfg.num_experts, cfg.held)
        return {"norm": ((E,), 0), "router": ((E, X), E),
                "shared_up": ((E, Ms), E), "shared_down": ((Ms, E), Ms * n),
                "w_up": ((Xh, E, Me), E), "w_down": ((Xh, Me, E), Me * n)}
    Hq, K, D = cfg.heads, cfg.kv_heads, cfg.head_dim
    return {"norm": ((E,), 0), "wq": ((E, Hq, D), E), "wk": ((E, K, D), E),
            "wv": ((E, K, D), E), "wo": ((Hq, D, E), Hq * D * n)}


_LAYER_AXES = {
    MAMBA: {"norm": (None,), "w_in": ("embed", "mlp"),
            "conv_w": (None, None), "conv_b": (None,), "A_log": (None,),
            "dt_bias": (None,), "D": (None,), "gate_norm": (None,),
            "w_out": ("mlp", "embed")},
    EXPERTS: {"norm": (None,), "router": ("embed", None),
              "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed"),
              "w_up": ("expert", "embed", "mlp"),
              "w_down": ("expert", "mlp", "embed")},
    ATTENTION: {"norm": (None,), "wq": ("embed", "heads", "head_dim"),
                "wk": ("embed", "kv_heads", "head_dim"),
                "wv": ("embed", "kv_heads", "head_dim"),
                "wo": ("heads", "head_dim", "embed")}}


def param_shapes(cfg: NemotronHConfig) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant[,
    the constant, 1 if not given]).  ``A_log`` and ``dt_bias`` get their
    published random start in ``init_params``."""
    V, E = cfg.vocab_size, cfg.hidden
    return {"embed": ((V, E), E),
            "layers": [_layer_shapes(cfg, kind) for kind in cfg.kinds],
            "final_norm": ((E,), 0),
            "lm_head": ((E, V), E)}


def param_logical_axes(cfg: NemotronHConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples."""
    return {"embed": ("vocab", "embed"),
            "layers": [dict(_LAYER_AXES[kind]) for kind in cfg.kinds],
            "final_norm": (None,),
            "lm_head": ("embed", "vocab")}


def ssm_start(cfg: NemotronHConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """Mamba-2's published start of a mixer's ``A_log`` and ``dt_bias``,
    float32 [heads]: A uniform in [1, 16]; the time step log-uniform in
    [``time_step_min``, ``time_step_max``], floored at ``time_step_floor``,
    and ``dt_bias`` its inverse softplus.  (``D`` starts at 1.)"""
    ka, kd = jax.random.split(key)
    H = cfg.mamba_heads
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
    step = jnp.maximum(jnp.exp(jax.random.uniform(kd, (H,)) * (hi - lo) + lo),
                       cfg.time_step_floor)
    return {"A_log": jnp.log(jax.random.uniform(ka, (H,), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step))}


def init_params(cfg: NemotronHConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    params = _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)
    for i, kind in enumerate(cfg.kinds):
        if kind == MAMBA:
            start = ssm_start(cfg, jax.random.fold_in(key, i))
            params["layers"][i] |= {k: v.astype(param_dtype)
                                    for k, v in start.items()}
    return params


def num_params(cfg: NemotronHConfig) -> int:
    return _lm.count_params(param_shapes(cfg))


def init_state(cfg: NemotronHConfig) -> Dict[str, jax.Array]:
    """The routers' selection bias, float32 [expert layers, experts]: state
    that no optimizer touches (``models/afmoe.py``)."""
    return {"bias": jnp.zeros((cfg.expert_layers, cfg.num_experts),
                              jnp.float32)}


# ------------------------------------------------------------------ layers

def _mixer(cfg, x, layer, segment_ids=None):
    """F of a Mamba-2 layer on the normed stream x [B, S, E] -> (out
    [B, S, E], the mean share of a state a chunk hands on).  ``cfg``: any
    configuration with the mixer's sizes (``mamba_dim``, ``conv_dim``,
    ``mamba_heads``, ``mamba_head_dim``, ``ssm_groups``, ``ssm_state``,
    ``chunk_size``, ``norm_eps``, ``dtype``): ``models/granite_hybrid.py``
    runs this body too.  ``segment_ids`` [B, S]: a packed row's documents,
    at whose starts the convolution and the state start anew; the second
    result is then ``ops/ssm.chunk_carry``'s pair."""
    dt_ = cfg.dtype
    B, S, _ = x.shape
    d, H, P = cfg.mamba_dim, cfg.mamba_heads, cfg.mamba_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    with jax.named_scope("block/ssm/proj"):
        zcd = jnp.einsum("bse,ef->bsf", x, layer["w_in"].astype(dt_),
                         preferred_element_type=dt_)
    # The convolution reads its columns of ``zcd`` where they lie and writes
    # X, B and C apart; the views below are bitcasts.
    z, delta = zcd[..., :d], zcd[..., d + cfg.conv_dim:]
    X, Bm, Cm = ssm.causal_conv(zcd, layer["conv_w"], layer["conv_b"],
                                segment_ids, start=d, split=(d, G * N, G * N))
    with jax.named_scope("block/ssm/scan"):
        step = jax.nn.softplus(delta.astype(jnp.float32)
                               + layer["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(layer["A_log"].astype(jnp.float32))
        carry = ssm.chunk_carry(step, A, cfg.chunk_size, segment_ids)
    y = ssm.ssd_scan(X.reshape(B, S, H, P), step, A, Bm.reshape(B, S, G, N),
                     Cm.reshape(B, S, G, N), layer["D"], cfg.chunk_size,
                     segment_ids=segment_ids)
    v = ssm.gated_group_norm(y.reshape(B, S, d), z, layer["gate_norm"], G,
                             cfg.norm_eps)
    with jax.named_scope("block/ssm/proj"):
        return jnp.einsum("bsf,fe->bse", v, layer["w_out"].astype(dt_),
                          preferred_element_type=dt_), carry


@jax.named_scope("block/attn")
def _attn(cfg: NemotronHConfig, x, layer):
    """F of an attention layer: grouped-query, causal, no positions."""
    dt = cfg.dtype
    impl = None if cfg.attention_impl == "auto" else cfg.attention_impl
    q = _lm.project_heads(x, layer["wq"], dt)
    k = _lm.project_heads(x, layer["wk"], dt)
    v = jnp.einsum("bse,ehd->bhsd", x, layer["wv"].astype(dt),
                   preferred_element_type=dt)
    o = _attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), v,
                   causal=True, impl=impl)
    return jnp.einsum("bhsd,hde->bse", o, layer["wo"].astype(dt),
                      preferred_element_type=dt)


def _layer(cfg: NemotronHConfig, kind: str, x, layer, bias=None):
    """One layer, ``x + F(N(x))``: (x', what the layer reports: an expert
    layer's loads as ``afmoe._moe``'s, a mixer's ``carry``)."""
    h = rms_norm(x, layer["norm"], cfg.norm_eps)
    if kind == MAMBA:
        f, carry = _mixer(cfg, h, layer)
        report = {"carry": carry}
    elif kind == EXPERTS:
        f, report = afmoe._moe(cfg, h, layer, bias, act=cfg.expert_act)
    else:
        f, report = _attn(cfg, h, layer), {}
    return x + f, report


def _merge(kind: str, report, cfg: NemotronHConfig):
    """A layer's reports over its groups of rows (leading axis) as one."""
    if kind == MAMBA:
        return {"carry": jnp.mean(report["carry"])}
    if kind == EXPERTS:
        return {"counts": jnp.sum(report["counts"], axis=0),
                "dropped": jnp.sum(report["dropped"]),
                "sliced": jnp.sum(report["sliced"]),
                "top": report["top"].reshape(-1, cfg.top_k)}
    return {}


def _run(cfg: NemotronHConfig, kind: str, x, layer, bias=None, keep=()):
    """The layer under the remat, ``layer_rows`` rows at a time (as
    ``afmoe``'s); ``keep``: ``_lm.remat``'s, the stack's."""
    one = _lm.remat(lambda x, layer, bias: _layer(cfg, kind, x, layer, bias),
                    cfg.remat, keep)
    B = x.shape[0]
    n = min(cfg.layer_rows or B, B)
    if B % n:
        raise ValueError(f"a batch of {B} rows does not split into groups "
                         f"of layer_rows={n}")
    if n == B:
        return one(x, layer, bias)
    y, report = jax.lax.map(lambda rows: one(rows, layer, bias),
                            x.reshape((B // n, n) + x.shape[1:]))
    return y.reshape(x.shape), _merge(kind, report, cfg)


def _refuse_a_mesh(cfg: NemotronHConfig) -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "nemotron_h on a mesh: the exchange of an expert-parallel group "
            "and a scan split over heads are not built (ROADMAP M3, M8)")
    if cfg.pp_microbatches:
        raise NotImplementedError(
            "nemotron_h with pp_microbatches: its layers are not one stack "
            "of like layers that a pipeline stage could slice (ROADMAP M4)")


def _forward_hidden(params, state, tokens, cfg: NemotronHConfig):
    """tokens [B, S] -> (final hidden [B, S, E] after the final norm; the
    expert layers' loads {"counts" [Le, X], "dropped" [Le], "sliced" [Le],
    "top" [Le, B*S, k]}; the mixers' mean chunk carry)."""
    _refuse_a_mesh(cfg)
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    keep = _lm.flash_keep(
        cfg.remat, sum(kind == ATTENTION for kind in cfg.kinds),
        (*tokens.shape, cfg.heads, cfg.head_dim), cfg.dtype)
    loads, carries = [], []
    for kind, layer in zip(cfg.kinds, params["layers"]):
        bias = state["bias"][len(loads)] if kind == EXPERTS else None
        x, report = _run(cfg, kind, x, layer, bias, keep)
        if kind == EXPERTS:
            loads.append(report)
        elif kind == MAMBA:
            carries.append(report["carry"])
    if loads:
        loads = jax.tree.map(lambda *a: jnp.stack(a), *loads)
    else:
        loads = {"counts": jnp.zeros((0, cfg.num_experts), jnp.int32),
                 "dropped": jnp.zeros((0,), jnp.int32),
                 "sliced": jnp.zeros((0,), jnp.int32),
                 "top": jnp.zeros((0, tokens.size, cfg.top_k), jnp.int32)}
    carry = jnp.mean(jnp.stack(carries)) if carries \
        else jnp.ones((), jnp.float32)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, loads, carry


def forward(params, tokens, cfg: NemotronHConfig, state=None) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32."""
    x, *_ = _forward_hidden(params, state or init_state(cfg), tokens, cfg)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def loss_and_report(params, batch, cfg: NemotronHConfig, state=None):
    """What the train step differentiates (parallel.spmd): the next-token
    cross-entropy (no auxiliary term), and what ``update_state`` turns into
    the step's metrics: the expert layers' loads and the mixers' chunk
    carry."""
    x, loads, carry = _forward_hidden(params, state or init_state(cfg),
                                      batch["tokens"], cfg)
    # Traced on its own, so that the scope ``loss`` stays a scope in the
    # backward's operations too (models/ouro._scoped has the reason).
    loss = jax.jit(lambda x, head, batch: _lm.next_token_loss(
        x, head, batch, cfg.loss_chunks, cfg.dtype))(x, params["lm_head"],
                                                     batch)
    return loss, jax.lax.stop_gradient({**loads, "ssm_chunk_carry": carry})


def loss_fn(params, batch, cfg: NemotronHConfig, state=None) -> jax.Array:
    return loss_and_report(params, batch, cfg, state)[0]


def update_state(state, report, cfg: NemotronHConfig):
    """(the state after a step with this report, the step's metrics):
    ``afmoe``'s metrics of the loads, and ``ssm_chunk_carry``."""
    state, metrics = afmoe.update_state(state, report, cfg)
    return state, {**metrics, "ssm_chunk_carry": report["ssm_chunk_carry"]}
