"""The ``ouro`` decoder (ByteDance Ouro LoopLM: Ouro-2.6B): one stack of
layers run ``loops`` times over the same weights, a loss at every pass,
weighed per token by a learned exit gate.

What it has that ``models/llama.py`` has not:

- Four RMSNorms a layer: ``a = x + N2(Attn(N1(x)))``,
  ``x' = a + N4(SwiGLU(N3(a)))``.  ``Attn`` and ``SwiGLU`` are Llama's
  (``llama.attention_branch`` / ``mlp_branch``), as are the configuration's
  fields: ``OuroConfig`` extends ``LlamaConfig``.
- The loop: ``h^t = N_f(Stack(h^{t-1}))`` for t = 1..``loops``, ``h^0`` the
  embedding; the final norm closes every pass and the normed state opens
  the next.  A layer weight is used ``loops`` times a step, so its gradient
  is the sum over that many uses.  Each pass's backward scan leaves a
  stacked gradient of the weights' own type, and the sum is the
  compiler's: it fuses it into the optimizer's update.  (Summed pass by
  pass into one accumulator instead, behind a barrier, the step held 1 GB
  less and took 0.5 % longer at Ouro-2.6B's widths, twelve layers and four
  loops: PERF.md, PR 34.)
- The objective.  Under the one head every pass has its per-token
  ``nll^t``, and an exit gate ``lambda^t = sigmoid(h^t . w_g + b_g)`` turns
  the passes into a distribution per token: ``q^1 = lambda^1``,
  ``q^t = lambda^t prod_{j<t} (1 - lambda^j)``, and the last pass takes what
  is left.  ``loss = mean_masked( sum_t q^t nll^t - beta H(q) )``: the
  expected loss at the exit, with the entropy of ``q`` held up against a
  collapse on one pass (``exit_entropy_coef`` = beta).  With one loop
  ``q = 1`` and the loss is Llama's.

The loss hands the step what it reports (``loss_and_report``): the passes'
masked mean losses ``loop_loss`` [loops], the mean exit distribution
``loop_exit_share`` [loops] and its mean entropy ``loop_exit_entropy``.

The per-token NLL, remat, norm and rotary code are ``models/_lm.py``'s and
``ops/``'s, shared with Llama and afmoe.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import _lm
from .llama import LlamaConfig, attention_branch, flash_keep, mlp_branch
from .llama import param_logical_axes as llama_logical_axes
from ..ops.norms import rms_norm
from ..ops.rope import rope_lane_tables


@dataclass(frozen=True)
class OuroConfig(LlamaConfig):
    """Defaults are Ouro-2.6B's published ``config.json``."""
    vocab_size: int = 49152
    hidden: int = 2048
    layers: int = 48
    heads: int = 16
    kv_heads: int = 16
    head_dim: int = 128
    mlp_dim: int = 5632
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    loops: int = 4                      # ``total_ut_steps``
    exit_entropy_coef: float = 0.1      # beta of the objective


def ouro_tiny(**kw) -> OuroConfig:
    """A CPU-test size: 3 layers run 4 times."""
    return OuroConfig(**{**dict(
        vocab_size=256, hidden=64, layers=3, heads=4, kv_heads=4,
        head_dim=16, mlp_dim=96, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference", remat=False), **kw})


def param_shapes(cfg: OuroConfig) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant:
    a norm's at one, the exit gate's bias at zero; _lm.init_from_shapes)."""
    L, E, H, K, D, M, V = (cfg.layers, cfg.hidden, cfg.heads, cfg.kv_heads,
                           cfg.head_dim, cfg.mlp_dim, cfg.vocab_size)
    return {
        "embed": ((V, E), E),
        "blocks": {
            "attn_norm": ((L, E), 0), "attn_post_norm": ((L, E), 0),
            "mlp_norm": ((L, E), 0), "mlp_post_norm": ((L, E), 0),
            "wq": ((L, E, H, D), E), "wk": ((L, E, K, D), E),
            "wv": ((L, E, K, D), E), "wo": ((L, H, D, E), H * D),
            "w_gate": ((L, E, M), E), "w_up": ((L, E, M), E),
            "w_down": ((L, M, E), M)},
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E),
        "exit_gate": {"w": ((E,), E), "b": ((), 0)}}


_is_shape = _lm.is_shape


def param_logical_axes(cfg: OuroConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of logical axis tuples: Llama's, two
    more norms a layer and the exit gate."""
    axes = llama_logical_axes(cfg)
    return {**axes, "exit_gate": {"w": (None,), "b": ()},
            "blocks": {**axes["blocks"], "attn_post_norm": ("layers", None),
                       "mlp_post_norm": ("layers", None)}}


def init_params(cfg: OuroConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    return _lm.init_from_shapes(param_shapes(cfg), key, param_dtype)


def num_params(cfg: OuroConfig) -> int:
    return _lm.count_params(param_shapes(cfg))


def _scoped(name: str, fn):
    """``fn`` under the scope ``name``, traced on its own (``jax.jit``; the
    compiler inlines the call).  A scope entered directly under
    ``jax.grad`` is named ``jvp(name)`` and ``transpose(jvp(name))`` in the
    program's text, which a reader of scopes cannot tell from JAX's own
    wrappers; entered inside a separately traced function it stays
    ``.../name/...`` in the forward, the recomputed and the backward
    operations alike."""
    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)
    return jax.jit(scoped)


def _layer(cfg: OuroConfig, cos, sin, positions, x, layer):
    """One layer, four norms.  x: [B, S, E]."""
    eps = cfg.norm_eps
    with jax.named_scope("block/attn"):
        a = x + rms_norm(
            attention_branch(cfg, cos, sin, positions,
                             rms_norm(x, layer["attn_norm"], eps), layer),
            layer["attn_post_norm"], eps)
    with jax.named_scope("block/mlp"):
        return a + rms_norm(
            mlp_branch(cfg, rms_norm(a, layer["mlp_norm"], eps), layer),
            layer["mlp_post_norm"], eps)


def hidden_states(params: Dict[str, Any], tokens: jax.Array, cfg: OuroConfig,
                  positions: Optional[jax.Array] = None):
    """tokens [B, S] -> the normed hidden state after every pass, a list of
    ``loops`` arrays [B, S, E].  The passes are unrolled: each is one
    ``lax.scan`` over the stacked layers under its own scope ``loop/<t>``,
    so that a trace tells them apart and compile time grows with ``loops``
    and not with depth.  (One scanned body for all the passes compiled in
    13 s against 20 s at Ouro-2.6B's widths, twelve layers and four loops,
    and ran 10 % slower with 2.5 GB more reserved; a remat round each whole
    pass on top of the layers' own ran 17 % slower for 2.5 GB less:
    PERF.md, PR 34.)"""
    if cfg.pp_microbatches:
        raise NotImplementedError(
            "a looped stack under the pipeline: the last stage feeds the "
            "first (ROADMAP)")
    if cfg.remat == "mlp_only":
        raise ValueError("remat 'mlp_only' is Llama's; a looped stack takes "
                         "the modes of _lm.remat")
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
    cos, sin = rope_lane_tables(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    layer = _lm.remat(partial(_layer, cfg, cos, sin, positions), cfg.remat,
                      flash_keep(cfg, tokens, cfg.layers * cfg.loops))

    def one_pass(x, blocks, final_norm):
        x, _ = jax.lax.scan(lambda x, w: (layer(x, w), None), x, blocks)
        with jax.named_scope("final_norm"):
            return rms_norm(x, final_norm, cfg.norm_eps)

    out = []
    for t in range(cfg.loops):
        x = _scoped(f"loop/{t}", one_pass)(x, params["blocks"],
                                           params["final_norm"])
        out.append(x)
    return out


def exit_distribution(gate_logits: jax.Array):
    """(log q, q) [T, ...] of gate logits z [T, ...], lambda = sigmoid(z):
    ``q^t = lambda^t prod_{j<t} (1 - lambda^j)`` and the last pass takes
    what is left, ``q^T = prod_{j<T} (1 - lambda^j)``, whatever its own
    gate says.  In logarithms, so that a saturated gate gives a small q and
    not log 0."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), axis=0)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    log_q = jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits[:-1]) + before[:-1], before[-1:]],
        axis=0)
    return log_q, jnp.exp(log_q)


def loss_and_report(params: Dict[str, Any], batch: Dict[str, jax.Array],
                    cfg: OuroConfig, state=None,
                    positions: Optional[jax.Array] = None):
    """(the exit-weighed loss, what the step reports of it: ``loop_loss``
    [loops], ``loop_exit_share`` [loops], ``loop_exit_entropy``).  The
    model carries no state; ``state`` is the step's argument for one that
    does."""
    hs = hidden_states(params, batch["tokens"], cfg, positions)
    targets, mask, denom = _lm.targets_and_mask(batch)
    dt = cfg.dtype

    def weigh(hs, lm_head, gate):
        mean = lambda a: jnp.sum(a * mask, axis=(-2, -1)) / denom
        # Per token, not as weighted sums out of the chunks: the weights
        # are the gate's and take their gradient through these very
        # numbers.  (Two sums a head instead, the pass's share of the loss
        # and its own mean, ran 0.26 % slower here: PERF.md, PR 34.)
        nll = jnp.stack([_lm.token_nll(h, lm_head, targets, cfg.loss_chunks,
                                       dt) for h in hs])
        z = jnp.stack([jnp.einsum("bse,e->bs", h, gate["w"].astype(dt),
                                  preferred_element_type=jnp.float32)
                       for h in hs]) + gate["b"].astype(jnp.float32)
        log_q, q = exit_distribution(z)
        entropy = -jnp.sum(q * log_q, axis=0)
        loss = mean(jnp.sum(q * nll, axis=0)
                    - cfg.exit_entropy_coef * entropy)
        return loss, {"loop_loss": mean(nll), "loop_exit_share": mean(q),
                      "loop_exit_entropy": mean(entropy)}

    loss, report = _scoped("loss", weigh)(hs, params["lm_head"],
                                          params["exit_gate"])
    return loss, jax.lax.stop_gradient(report)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            cfg: OuroConfig,
            positions: Optional[jax.Array] = None) -> jax.Array:
    return loss_and_report(params, batch, cfg, positions=positions)[0]


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: OuroConfig,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] -> the last pass's logits [B, S, V] float32 (no early
    exit: ``early_exit_threshold`` is a serving key)."""
    x = hidden_states(params, tokens, cfg, positions)[-1]
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)
