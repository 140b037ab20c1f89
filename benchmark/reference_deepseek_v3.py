"""The plain reference of the ``deepseek_v3`` block (kanana-2-30b-a3b):
forward, loss, the gradient in every RMSNorm weight (or in every weight, for
the CPU tests) and the routers' choices, in float32 ``jax.numpy`` at the
highest matmul precision.  Nothing here comes from ``ray_tpu`` or from
``reference_xing4.py``; the elementary pieces (RMSNorm, rotary embedding on
split halves, a linear layer with its int8 control, the distance) are
``reference.py``'s and the expert layer's (router, held experts one at a
time, SwiGLU, the head's loss) ``reference_afmoe.py``'s.

The equations (``config.json``'s keys; what no key settles is marked
(assumed) and listed in ``configs/kanana-2-30b-a3b.json`` under ``assumed``).
C = hidden, N(x; g) = x / rms(x) g with eps ``rms_norm_eps``.

- Stream: ``h0 = Emb[tokens]``; a layer is ``a = h + Attn(N(h; g_1))``,
  ``h' = a + F(N(a; g_2))``: pre-norm, one lane.
- Attention, H heads: ``q = x W_q`` (heads of 128 + 64; with a
  ``q_lora_rank`` it is ``N(x W_qa; g_q) W_qb``, which kanana has not);
  ``[c ; k_r] = x W_kva`` (``kv_lora_rank`` + 64); ``[k_n ; v] = N(c; g_kv)
  W_kvb`` (heads of 128 + 128); rotary embedding at ``rope_theta`` with no
  scaling (``rope_scaling`` null) on ``q_r`` and on the ONE ``k_r`` all heads
  share; scores ``(q_n . k_n + q_r . k_r) / sqrt(192)``, causal softmax,
  ``o = P v``, ``o W_o``.  DEPARTURE: the published code
  (``rope_interleave``) pairs the rotary channels (2i, 2i+1); this reference,
  like the program, pairs (i, i + 32).  With seeded random weights the two
  differ by a permutation of W_q's and W_kva's rotary columns, which no
  score sees.
- Feed-forward: below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; after, ``Shared(x) + sum_{e in top, held} w_e
  Expert_e(x)``: ``s = sigmoid(x W_r)``, ``top`` the ``num_experts_per_tok``
  largest of ``s + b`` (``noaux_tc``, one group), ``w = s[top] / (sum + 1e-20)
  x routed_scaling_factor``; every expert a SwiGLU of
  ``moe_intermediate_size``, ``Shared`` ONE SwiGLU of ``n_shared_experts`` x
  that width, added unweighted.  ``b`` is the selection bias, state
  (assumed: torchtitan's rule moves it after a step).
- End: masked mean cross-entropy of ``N(h_L; g_f) W_head`` against token
  t + 1, untied, no auxiliary term (assumed).

The share: as ``reference_afmoe``: the experts held are ``held_start <= e <
held_start + Xh`` of the router's ``X``; what the others would add is left
out, and that partial result goes on to the next layer, as in the program.

``quant="int8"`` is the control: the inputs of every linear layer (the
routers' too) rounded to 8-bit integers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _linear, _rms_norm, _rope,
                                 relative_distance)  # noqa: F401
from benchmark.reference_afmoe import (_attention, _nll, _swiglu, _widen,
                                       held_experts, route,
                                       routing_mismatch_share)  # noqa: F401

#: a layer's RMSNorm weights (``q_norm`` too where there is a bottleneck);
#: ``kv_norm``'s gradient exists only through the latent path
NORMS = ("attn_norm", "mlp_norm", "kv_norm")


def latent_attention(x, w, s, quant=None):
    """x [B, S, C] (normed) -> [B, S, C]."""
    B, S, C = x.shape
    H, dn, dr, dv, rkv, eps = (s[k] for k in ("H", "dn", "dr", "dv", "rkv",
                                              "eps"))
    if "wq" in w:
        c_q, wq = x, w["wq"]
    else:
        c_q, wq = _rms_norm(_linear(x, w["wq_a"], quant), w["q_norm"],
                            eps), w["wq_b"]
    q = _linear(c_q, wq.reshape(-1, H * (dn + dr)), quant
                ).reshape(B, S, H, dn + dr)
    kv_a = _linear(x, w["wkv_a"], quant)
    c = _rms_norm(kv_a[..., :rkv], w["kv_norm"], eps)
    kv = _linear(c, w["wkv_b"].reshape(rkv, H * (dn + dv)), quant
                 ).reshape(B, S, H, dn + dv)
    k_r = _rope(kv_a[..., None, rkv:], s["theta"])          # one head
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s["theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, H, dr))], -1)
    # A full masked softmax, a head at a time, scaled by the width of q and
    # k, 192^-1/2; the values are narrower (reference_afmoe's, no window).
    o = _attention(q, k, kv[..., dn:], None)
    return _linear(o.reshape(B, S, H * dv), w["wo"].reshape(H * dv, C), quant)


def feed_forward(x, w, bias, s, quant=None):
    """(F(x), the router's choices [T, k] or None for a dense layer, which
    ``bias is None`` marks).  x [B, S, C] (normed)."""
    B, S, C = x.shape
    if bias is None:
        return _swiglu(x, w["w_gate"], w["w_up"], w["w_down"], quant), None
    flat = x.reshape(B * S, C)
    top, wts = route(flat, w["router"], bias, s, quant)
    return (_swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                    quant)
            + held_experts(flat, top, wts, w["w_gate"], w["w_up"],
                           w["w_down"], s["held_start"], quant
                           ).reshape(B, S, C), top)


def layer(h, w, bias, s, quant=None):
    """One layer: (h', the router's choices or None)."""
    a = h + latent_attention(_rms_norm(h, w["attn_norm"], s["eps"]), w, s,
                             quant)
    f, top = feed_forward(_rms_norm(a, w["mlp_norm"], s["eps"]), w, bias, s,
                          quant)
    return a + f, top


def _stack(weights, bias, s):
    """(one layer's weights, its bias or None) down the stack."""
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    for i in range(s["L"]):
        j = i - s["Ld"]
        yield (at(weights["dense"], i), None) if j < 0 else \
            (at(weights["moe"], j), bias[j])


def _shift(tokens):
    return jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)


def _hidden(weights, bias, tokens, s, quant):
    """The stack's result before the final norm, whole: a test's sizes."""
    h = weights["embed"][tokens]
    for w, b in _stack(weights, bias, s):
        h, _ = layer(h, w, b, s, quant)
    return h


def logits(weights, bias, tokens, s, quant=None):
    """tokens [B, S] -> logits [B, S, V] float32."""
    weights = _widen(weights)
    h = _rms_norm(_hidden(weights, bias, tokens, s, quant),
                  weights["final_norm"], s["eps"])
    return _linear(h, weights["lm_head"], quant)


def loss(weights, bias, tokens, mask, s, quant=None):
    """The masked mean next-token loss as one function of float32 weights:
    what ``jax.grad`` differentiates whole at a test's sizes."""
    return _nll(_hidden(weights, bias, tokens, s, quant),
                weights["final_norm"], weights["lm_head"], _shift(tokens),
                mask.astype(F32), s, quant)


def norms_of_layer(g):
    return {n: g[n] for n in NORMS + ("q_norm",) if n in g}


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes (the items of
    ``s``): a layer forward, a layer backward, the head."""
    s = dict(sizes)
    forward = jax.jit(lambda h, w, b: layer(h, _widen(w), b, s, quant))

    @jax.jit
    def backward(h, w, b, gh):
        _, vjp = jax.vjp(lambda h, w: layer(h, w, b, s, quant)[0], h,
                         _widen(w))
        gh, gw = vjp(gh)
        return gh, norms_of_layer(gw)

    @jax.jit
    def head(h, final_norm, lm_head, tokens, mask):
        value, vjp = jax.vjp(
            lambda h, n, m: _nll(h, n, m, _shift(tokens), mask.astype(F32), s,
                                 quant),
            h, final_norm.astype(F32), lm_head.astype(F32))
        gh, g_final, _ = vjp(jnp.ones((), F32))
        return value, gh, g_final

    return forward, backward, head


def loss_norm_grads_and_routing(weights, bias, tokens, mask, s, quant=None):
    """(loss over the masked positions of tokens [B, S], its gradient in the
    weights of every RMSNorm, the routers' choices [expert layers, B*S, k]).
    ``bias`` [expert layers, X].

    The gradient tree: ``final_norm [E]`` and, under ``dense`` and ``moe``,
    the norms of ``NORMS`` with a leading layer axis.  The walk is a Python
    loop over jitted pieces, one ``jax.vjp`` of a layer at a time in
    reverse, so that a row of 8,192 tokens fits beside the weights and
    compiling it does not grow with depth: call it outside ``jax.jit``."""
    forward, backward, head = _programs(tuple(sorted(s.items())), quant)
    h = weights["embed"].astype(F32)[tokens]
    stack = list(_stack(weights, bias, s))
    hs, tops = [], []
    for w, b in stack:
        hs.append(h)
        h, top = forward(h, w, b)
        if top is not None:
            tops.append(top)
    value, gh, g_final = head(h, weights["final_norm"], weights["lm_head"],
                              tokens, mask)
    grads = []
    for h, (w, b) in reversed(list(zip(hs, stack))):
        gh, g = backward(h, w, b, gh)
        grads.append(g)
    grads.reverse()
    collect = lambda part: jax.tree.map(lambda *a: jnp.stack(a), *part)
    return value, {"final_norm": g_final,
                   "dense": collect(grads[:s["Ld"]]),
                   "moe": collect(grads[s["Ld"]:])}, jnp.stack(tops)


def routing(weights, bias, tokens, s, quant=None):
    """The routers' choices [expert layers, B*S, k] for tokens [B, S], row
    after row through the walk's forward program: no loss, no gradient.
    Call it outside ``jax.jit``."""
    forward, _, _ = _programs(tuple(sorted(s.items())), quant)
    stack = list(_stack(weights, bias, s))
    embed = weights["embed"].astype(F32)
    rows = []
    for row in tokens:
        h, tops = embed[row[None]], []
        for w, b in stack:
            h, top = forward(h, w, b)
            if top is not None:
                tops.append(top)
        rows.append(jnp.stack(tops))
    return jnp.concatenate(rows, axis=1)
