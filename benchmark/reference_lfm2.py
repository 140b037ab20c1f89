"""The plain reference of the ``lfm2_moe`` stack (LFM2-24B-A2B): forward, loss,
the gradient in every judged weight and in the tied embedding, and the
routers' choices, in float32 ``jax.numpy`` at the highest matmul precision.
Nothing here comes from ``ray_tpu``; the elementary pieces (a linear layer, an
RMSNorm, the rotary embedding on split halves, softmax attention a head at a
time over the whole row, a SwiGLU, the dense loop over the held experts, the
head's loss) are ``reference.py``'s and ``reference_afmoe.py``'s.

The equations (``config.json``'s keys; what no key settles is marked
(assumed) and listed in ``configs/lfm2-24b-a2b.json`` under ``assumed``).
C = ``hidden_size``, N(x; g) = x / rms(x) g with eps ``norm_eps``.  Every
layer is ``h = x + Op(N(x; g_op))``, ``x' = h + F(N(h; g_ffn))``, Op chosen by
the layer's entry in ``layer_types``.

- ``conv``, the double-gated short convolution: ``[B ; C ; u] = x W_in`` of
  widths C each, in this order (assumed), no bias (``conv_bias`` false).
  ``v[t] = sum_j w[j] (B u)[t - (K - 1) + j]`` with K = ``conv_L_cache`` = 3
  and zeros before the row's start: **an explicit loop over the taps on a
  padded array**; depthwise, no activation.  ``Op = (C v) W_out``.
- ``full_attention``: q of ``num_attention_heads`` heads, k and v of
  ``num_key_value_heads``, head size C / heads = 64, no bias; each query and
  key head N(.; g_q), N(.; g_k) over its 64 channels, **then** (assumed) the
  rotary embedding over the whole head (halves of 32, theta
  ``rope_parameters.rope_theta``); causal softmax of ``64^-1/2 q.k`` over the
  whole row, a key head for H / Hkv query heads; ``W_o``.
- F of the first ``num_dense_layers`` layers: ``(silu(x W_1) * x W_3) W_2`` at
  ``intermediate_size``.
- F of the others: ``s = sigmoid(x W_r)``; the top k of s + bias
  (``use_expert_bias``); ``w = s[top] / (sum + 1e-6)`` (``norm_topk_prob``;
  the epsilon assumed) ``* routed_scaling_factor``; ``F = sum over the top that
  are held of w_e (silu(x W_1e) * x W_3e) W_2e``: a dense loop over the
  experts it is told it holds, every token through every held expert, masked.
  No shared expert.
- End: N(x_L; g_out), the logits by the embedding's own matrix (assumed: the
  LFM2 family ties them), the masked mean cross-entropy against token t + 1.

DEPARTURES from the published code: the selection bias is state that a rule
outside the model moves (torchtitan's sign rule at 1e-3, as Trinity-Mini's
file has it) and is handed in; the layer computes the held experts' part
alone and the vocabulary is the chip's slice (``share`` in the
configuration's file), in the program and here alike.  The convolution's
weight lies [K, channels] where the published tensor is [channels, 1, K].
The walk is a Python loop over jitted pieces, a layer at a time, so that a
row of 8,192 tokens fits a chip in float32; the arithmetic is the same.

``quant="int8"`` is the control: the inputs of every linear layer (the
routers' and the tied head's too) rounded to 8-bit integers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _attention, _linear, _rms_norm, _rope,
                                 relative_distance)  # noqa: F401
from benchmark.reference_afmoe import (_nll, _swiglu, _widen, held_experts,
                                       routing_mismatch_share)  # noqa: F401

#: a layer's judged weights, by its letter (``c`` a convolution layer, ``a``
#: an attention layer): every RMSNorm weight, and the convolution's taps,
#: whose gradient exists only through the gated convolution's backward
JUDGED = {"c": ("op_norm", "ffn_norm", "conv_w"),
          "a": ("op_norm", "ffn_norm", "q_norm", "k_norm")}


def short_conv(B, C, u, w):
    """B, C, u [rows, S, Ch], w [K, Ch]: the taps one after another on the
    padded product, then the second gate."""
    K, S = w.shape[0], u.shape[1]
    padded = jnp.pad(B * u, ((0, 0), (K - 1, 0), (0, 0)))
    v = jnp.zeros_like(u)
    for j in range(K):                  # tap j reads the token K - 1 - j ago
        v = v + padded[:, j:j + S] * w[j]
    return C * v


def conv_operator(h, w, s, quant=None):
    B, C, u = jnp.split(_linear(h, w["w_in"], quant), 3, axis=-1)
    return _linear(short_conv(B, C, u, w["conv_w"]), w["w_out"], quant)


def attention_operator(h, w, s, quant=None):
    Bt, S, E = h.shape
    H, K, D, eps = s["H"], s["Hkv"], s["D"], s["eps"]
    q = _linear(h, w["wq"].reshape(E, H * D), quant).reshape(Bt, S, H, D)
    k = _linear(h, w["wk"].reshape(E, K * D), quant).reshape(Bt, S, K, D)
    v = _linear(h, w["wv"].reshape(E, K * D), quant).reshape(Bt, S, K, D)
    q = _rope(_rms_norm(q, w["q_norm"], eps), s["theta"])
    k = _rope(_rms_norm(k, w["k_norm"], eps), s["theta"])
    k, v = (jnp.repeat(t, H // K, axis=2) for t in (k, v))
    return _linear(_attention(q, k, v).reshape(Bt, S, H * D),
                   w["wo"].reshape(H * D, E), quant)


def route(x, router, bias, s, quant=None):
    """x [T, E] -> (top [T, k] indices over all the experts, w [T, k])."""
    scores = jax.nn.sigmoid(_linear(x, router, quant))
    _, top = jax.lax.top_k(scores + bias, s["k"])
    w = jnp.take_along_axis(scores, top, axis=-1)
    return top, w / (jnp.sum(w, -1, keepdims=True) + s["route_eps"]) \
        * s["route_scale"]


def layer(x, w, bias, s, kind, quant=None):
    """One layer: (x', the router's choices [T, k] or None for a dense
    layer, which ``bias is None`` marks)."""
    Bt, S, E = x.shape
    op = {"c": conv_operator, "a": attention_operator}[kind]
    h = x + op(_rms_norm(x, w["op_norm"], s["eps"]), w, s, quant)
    f = _rms_norm(h, w["ffn_norm"], s["eps"])
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
    and backward for each kind (its letter, and whether it is dense), the
    tied head."""
    s = dict(sizes)
    forward = jax.jit(lambda x, w, b, kind: layer(x, _widen(w), b, s, kind,
                                                  quant),
                      static_argnames="kind")

    def backward(x, w, b, gx, kind):
        _, vjp = jax.vjp(lambda x, w: layer(x, w, b, s, kind, quant)[0], x,
                         _widen(w))
        gx, gw = vjp(gx)
        return gx, {n: gw[n] for n in JUDGED[kind]}

    @jax.jit
    def head(x, final_norm, embed, tokens, mask):
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
        loss, vjp = jax.vjp(
            lambda x, n, e: _nll(x, n, e.T, targets, mask.astype(F32), s,
                                 quant),
            x, final_norm.astype(F32), embed.astype(F32))
        return (loss,) + vjp(jnp.ones((), F32))

    return forward, jax.jit(backward, static_argnames="kind"), head


def _stack(weights, bias, s):
    """(one layer's weights, its bias or None, its letter) down the stack."""
    for i, (kind, w) in enumerate(zip(s["kinds"], weights["layers"])):
        yield w, (None if i < s["Ld"] else bias[i - s["Ld"]]), kind


def loss_judged_grads_and_routing(weights, bias, tokens, mask, s, quant=None):
    """(loss over the masked positions of tokens [B, S], its gradient in
    every judged weight and in the tied embedding, the routers' choices
    [expert layers, B*S, k]).

    The gradient tree: ``final_norm``, ``layers`` (a list with the names of
    ``JUDGED`` of each layer's letter) and ``embed`` [V, E], the head's part
    and the lookup's added.  The walk is a Python loop over jitted pieces,
    one ``jax.vjp`` of a layer at a time in reverse: call it outside
    ``jax.jit``."""
    forward, backward, head = _programs(tuple(sorted(s.items())), quant)
    embed = weights["embed"].astype(F32)
    x = embed[tokens]
    stack = list(_stack(weights, bias, s))
    xs, tops = [], []
    for w, b, kind in stack:
        xs.append(x)
        x, top = forward(x, w, b, kind)
        if top is not None:
            tops.append(top)
    loss, gx, g_final, g_embed = head(x, weights["final_norm"],
                                      weights["embed"], tokens, mask)
    grads = []
    for x, (w, b, kind) in reversed(list(zip(xs, stack))):
        gx, g = backward(x, w, b, gx, kind)
        grads.append(g)
    grads.reverse()
    return loss, {"final_norm": g_final, "layers": grads,
                  "embed": g_embed.at[tokens].add(gx)}, jnp.stack(tops)


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
    return _linear(x, weights["embed"].astype(F32).T, quant)
