"""The plain reference of the ``mimo_v2_flash`` stack (MiMo-V2-Flash): forward,
loss, the gradient in every judged weight (every RMSNorm weight and every
window layer's sink) and the routers' choices, in float32 ``jax.numpy`` at the
highest matmul precision.  Nothing here comes from ``ray_tpu``; the elementary
pieces (a linear layer, an RMSNorm, the rotary embedding on split halves, a
SwiGLU, the dense loop over the held experts, the head's loss) are
``reference.py``'s and ``reference_afmoe.py``'s.

The equations (``config.json``'s keys; what no key settles is marked (assumed)
and listed in ``configs/mimo-v2-flash.json`` under ``assumed``).  N(x; g) =
x / rms(x) g with eps ``layernorm_epsilon``.  Every layer is ``h = x +
Attn_kind(N(x; g_1))``, ``y = h + F(N(h; g_2))``, the kind by the layer's entry
in ``hybrid_layer_pattern`` (0 full, 1 window).

- ``Attn_kind``, no bias: ``q = x W_q`` (heads of ``head_dim`` = 192), ``k = x
  W_k`` (``num_key_value_heads`` / ``swa_num_key_value_heads`` heads of 192),
  ``v = attention_value_scale x W_v`` (heads of ``v_head_dim`` = 128; the
  scale on v, which equals on the result (assumed)).  The FIRST 64 =
  int(``partial_rotary_factor`` x 192) lanes of every q and k head turn as two
  halves of 32 (assumed), base ``rope_theta`` on full and ``swa_rope_theta`` on
  window layers; the other 128 carry no position.  ``s_ts = q_t . k_s /
  sqrt(192)``; full: ``s <= t``; window: ``0 <= t - s < sliding_window``;
  query head i reads key head ``i // (heads / key heads)``, the counts read
  off the weights' shapes.  A window layer (``add_swa_attention_sink_bias``)
  has a scalar ``b_h`` a query head: **one more column of the row's softmax,
  of score b_h and value zero** (assumed), ``p_ts = exp(s_ts) / (exp(b_h) +
  sum exp(s_ts'))``; a layer whose weights hold no ``sink`` has none.
  ``Attn = concat_h(o_h) W_o``, over the heads the weights hold: a share's
  part.  One head at a time, recomputed in the backward pass.
- F of a dense layer: ``(silu(x W_1) * x W_3) W_2`` at ``intermediate_size``.
- F of the others: ``s = sigmoid(x W_r)`` over ``n_routed_experts``; the top k
  of s + bias (``noaux_tc``; ``n_group`` 1); ``w = s[top] / (sum + 1e-20)``
  (``norm_topk_prob``; ``routed_scaling_factor`` null: 1); ``F = sum over the
  top that are held of w_e Expert_e(x)``, a dense loop over the held experts.
  No shared expert.
- End: N(x_L; g_out), an untied head, the masked mean cross-entropy against
  token t + 1, no auxiliary term.

DEPARTURES from the published code: the selection bias is state that a rule
outside the model moves (the sign rule the other sigmoid-routed cells use) and
is handed in; the layer computes the held heads' and the held experts' parts
alone and the vocabulary is the chip's slice (``share`` in the configuration's
file), in the program and here alike; the three multi-token-prediction layers
and the vision and audio encoders are not built.  The walk is a Python loop
over jitted pieces, a layer at a time, so that a row of 8,192 tokens fits a
chip in float32; the arithmetic is the same.

``quant="int8"`` is the control: the inputs of every linear layer (the
routers' and the head's too) rounded to 8-bit integers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _linear, _rms_norm, _rope,
                                 relative_distance)  # noqa: F401
from benchmark.reference_afmoe import (_nll, _swiglu, _widen, held_experts,
                                       routing_mismatch_share)  # noqa: F401

#: a layer's judged weights, where it has them: both RMSNorm weights and a
#: window layer's sink, whose gradient exists only through the softmax
JUDGED = ("attn_norm", "mlp_norm", "sink")


def _rope_first(x, theta, lanes):
    """x [B, S, H, D]: the first ``lanes`` of every head turned (two halves
    of ``lanes / 2``, frequencies over ``lanes``), the others as they are."""
    return jnp.concatenate([_rope(x[..., :lanes], theta), x[..., lanes:]], -1)


def _attention(q, k, v, window, sink):
    """Causal softmax attention, q / k [B, S, H, D], v [B, S, H, Dv], with an
    optional window and, ``sink`` [H] or None, one more column a row whose
    score is the head's sink and whose value is zero.  One head at a time,
    recomputed in the backward pass."""
    (B, S, H, D) = q.shape
    gap = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]       # t - s
    visible = gap >= 0 if window is None else (gap >= 0) & (gap < window)

    @jax.checkpoint
    def head(args):
        q, k, v, b = args                                   # [B, S, D], []
        scores = jnp.einsum("bqd,bkd->bqk", q, k,
                            precision="highest") * D ** -0.5
        scores = jnp.where(visible, scores, -jnp.inf)
        if b is not None:
            scores = jnp.concatenate(
                [scores, jnp.broadcast_to(b, (B, S, 1))], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :S]
        return jnp.einsum("bqk,bkd->bqd", probs, v, precision="highest")

    heads = tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v))
    out = jax.lax.map(head, heads + (sink,))
    return jnp.moveaxis(out, 0, 2)


def attention_operator(h, w, s, kind, quant=None):
    """Attn_kind(h) over the heads ``w`` holds; kind ``w`` or ``f``."""
    Bt, S, E = h.shape
    (_, H, D), K, Dv = w["wq"].shape, w["wk"].shape[1], w["wv"].shape[2]
    theta, window = ((s["swa_theta"], s["W"]) if kind == "w"
                     else (s["theta"], None))
    q = _linear(h, w["wq"].reshape(E, H * D), quant).reshape(Bt, S, H, D)
    k = _linear(h, w["wk"].reshape(E, K * D), quant).reshape(Bt, S, K, D)
    v = _linear(h, w["wv"].reshape(E, K * Dv), quant).reshape(Bt, S, K, Dv)
    v = v * s["value_scale"]
    q, k = _rope_first(q, theta, s["R"]), _rope_first(k, theta, s["R"])
    k, v = (jnp.repeat(t, H // K, axis=2) for t in (k, v))
    out = _attention(q, k, v, window, w.get("sink"))
    return _linear(out.reshape(Bt, S, H * Dv), w["wo"].reshape(H * Dv, E),
                   quant)


def route(x, router, bias, s, quant=None):
    """x [T, E] -> (top [T, k] indices over all the experts, w [T, k])."""
    scores = jax.nn.sigmoid(_linear(x, router, quant))
    _, top = jax.lax.top_k(scores + bias, s["k"])
    w = jnp.take_along_axis(scores, top, axis=-1)
    return top, w / (jnp.sum(w, -1, keepdims=True) + s["route_eps"]) \
        * s["route_scale"]


def layer(x, w, bias, s, kind, quant=None):
    """One layer: (y, the router's choices [T, k] or None for a dense
    layer, which ``bias is None`` marks)."""
    Bt, S, E = x.shape
    h = x + attention_operator(_rms_norm(x, w["attn_norm"], s["eps"]), w, s,
                               kind, quant)
    f = _rms_norm(h, w["mlp_norm"], s["eps"])
    if bias is None:
        return h + _swiglu(f, w["w_gate"], w["w_up"], w["w_down"],
                           quant), None
    flat = f.reshape(Bt * S, E)
    top, wts = route(flat, w["router"], bias, s, quant)
    return h + held_experts(flat, top, wts, w["w_gate"], w["w_up"],
                            w["w_down"], s["held_start"], quant).reshape(
                                Bt, S, E), top


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes: a layer forward
    and backward (jit keys them by the layer's letter and by the weights it
    is handed), the head."""
    s = dict(sizes)
    forward = jax.jit(lambda x, w, b, kind: layer(x, _widen(w), b, s, kind,
                                                  quant),
                      static_argnames="kind")

    def backward(x, w, b, gx, kind):
        _, vjp = jax.vjp(lambda x, w: layer(x, w, b, s, kind, quant)[0], x,
                         _widen(w))
        gx, gw = vjp(gx)
        return gx, {n: gw[n] for n in JUDGED if n in gw}

    @jax.jit
    def head(x, final_norm, lm_head, tokens, mask):
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
        loss, vjp = jax.vjp(
            lambda x, n, h: _nll(x, n, h, targets, mask.astype(F32), s,
                                 quant),
            x, final_norm.astype(F32), lm_head.astype(F32))
        gx, g_final, _ = vjp(jnp.ones((), F32))
        return loss, gx, g_final

    return forward, jax.jit(backward, static_argnames="kind"), head


def _stack(weights, bias, s):
    """(one layer's weights, its bias or None, its letter) down the stack."""
    for i, (kind, w) in enumerate(zip(s["kinds"], weights["layers"])):
        yield w, (None if i < s["Ld"] else bias[i - s["Ld"]]), kind


def loss_judged_grads_and_routing(weights, bias, tokens, mask, s, quant=None):
    """(loss over the masked positions of tokens [B, S], its gradient in
    every judged weight, the routers' choices [expert layers, B*S, k]).

    The gradient tree: ``final_norm`` and ``layers``, a list with the names
    of ``JUDGED`` that each layer has.  The walk is a Python loop over jitted
    pieces, one ``jax.vjp`` of a layer at a time in reverse: call it outside
    ``jax.jit``."""
    forward, backward, head = _programs(tuple(sorted(s.items())), quant)
    x = weights["embed"].astype(F32)[tokens]
    stack = list(_stack(weights, bias, s))
    xs, tops = [], []
    for w, b, kind in stack:
        xs.append(x)
        x, top = forward(x, w, b, kind)
        if top is not None:
            tops.append(top)
    loss, gx, g_final = head(x, weights["final_norm"], weights["lm_head"],
                             tokens, mask)
    grads = []
    for x, (w, b, kind) in reversed(list(zip(xs, stack))):
        gx, g = backward(x, w, b, gx, kind)
        grads.append(g)
    grads.reverse()
    return loss, {"final_norm": g_final, "layers": grads}, jnp.stack(tops)


def routing(weights, bias, tokens, s, quant=None):
    """The routers' choices [expert layers, B*S, k] for tokens [B, S], row
    after row through the walk's forward programs: no gradient.  Call it
    outside ``jax.jit``."""
    forward, _, _ = _programs(tuple(sorted(s.items())), quant)
    stack = list(_stack(weights, bias, s))
    embed = weights["embed"].astype(F32)
    rows = []
    for row in tokens:
        x, tops = embed[row[None]], []
        for w, b, kind in stack:
            x, top = forward(x, w, b, kind)
            if top is not None:
                tops.append(top)
        rows.append(jnp.stack(tops))
    return jnp.concatenate(rows, axis=1)


def logits(weights, bias, tokens, s, quant=None):
    """tokens [B, S] -> logits [B, S, V] float32."""
    x = weights["embed"].astype(F32)[tokens]
    for w, b, kind in _stack(weights, bias, s):
        x, _ = layer(x, _widen(w), b, s, kind, quant)
    x = _rms_norm(x, weights["final_norm"].astype(F32), s["eps"])
    return _linear(x, weights["lm_head"].astype(F32), quant)
