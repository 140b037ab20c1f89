"""The plain reference of the ``afmoe`` block (Trinity-Mini): forward, loss,
the gradient in every RMSNorm weight and the routers' choices, in float32
``jax.numpy`` at the highest matmul precision.  Nothing here comes from
``ray_tpu``; the elementary pieces (RMSNorm, rotary embedding on split halves,
a linear layer with its int8 control, the distance) are ``reference.py``'s.

The equations (Trinity-Mini's ``config.json``; the lines marked (assumed) are
from the family's description or torchtitan's MoE module and are listed in
``configs/trinity-mini.json`` under ``assumed``).  ``h0 = E[tokens] *
sqrt(hidden)`` (``mup_enabled``).  Layer i: ``a = h + N2(Attn(N1(h)))``,
``h' = a + N4(F(N3(a)))``, every N an RMSNorm with a weight, eps 1e-5.
``Attn(x)``: ``q = n_q(x Wq)`` [heads x 128], ``k = n_k(x Wk)``, ``v = x Wv``
[kv heads x 128], n an RMSNorm over the 128 of each head; on
``sliding_attention`` layers rotary embedding (theta 10,000, split halves) on
q and k and a key visible iff ``0 <= t - s < sliding_window``; on
``full_attention`` layers no positional term and plain causal visibility;
scale 128^-1/2, float32 softmax, grouped queries; ``out = (attn *
sigmoid(x Wg)) Wo``.  ``F`` below ``num_dense_layers``: SwiGLU of width
``intermediate_size``.  Otherwise ``F(x) = S(x) + sum_{e in top} w_e
Expert_e(x)``: ``s = sigmoid(x Wr)`` over all the experts; ``top`` the
``num_experts_per_tok`` largest of ``s + b``; ``w = s[top] / (sum s[top] +
1e-20) * route_scale``; every expert and the shared ``S`` a SwiGLU of width
``moe_intermediate_size``; nothing is dropped.  ``b`` is the selection bias,
state and not a parameter (assumed: torchtitan's rule moves it after a step).
Loss: masked mean cross-entropy of ``N_f(h_L) W_head``, untied, no auxiliary
term (assumed).

The share: the experts held are ``held_start <= e < held_start + Xh`` of the
router's ``X``; what the others would add is left out, and that partial
result goes on to the next layer, as in the program.  Every held expert is
evaluated over every token and masked by its weight (0 where the token did
not choose it), one expert at a time, so one check row of 8,192 tokens fits.

``quant="int8"`` is the control, as in ``reference.py``: the inputs of every
linear layer (the router's too) rounded to 8-bit integers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _linear, _rms_norm, _rope,
                                 relative_distance)  # noqa: F401

NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
         "q_norm", "k_norm")


def _attention(q, k, v, window):
    """Causal softmax attention with an optional window, q/k/v [B, S, H, D].
    One head at a time, recomputed in the backward pass."""
    S, D = q.shape[1], q.shape[3]
    gap = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]       # t - s
    visible = gap >= 0 if window is None else (gap >= 0) & (gap < window)

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv                                       # [B, S, D]
        scores = jnp.einsum("bqd,bkd->bqk", q, k,
                            precision="highest") * D ** -0.5
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, v, precision="highest")

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 2)


def _swiglu(x, w_gate, w_up, w_down, quant):
    return _linear(jax.nn.silu(_linear(x, w_gate, quant))
                   * _linear(x, w_up, quant), w_down, quant)


def attention_branch(x, w, s, kind, quant=None):
    """N2(Attn(N1(x))).  x [B, S, E]; w one layer's weights."""
    B, S, E = x.shape
    H, K, D, eps = s["H"], s["Hkv"], s["D"], s["eps"]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _linear(h, w["wq"].reshape(E, H * D), quant).reshape(B, S, H, D)
    k = _linear(h, w["wk"].reshape(E, K * D), quant).reshape(B, S, K, D)
    v = _linear(h, w["wv"].reshape(E, K * D), quant).reshape(B, S, K, D)
    q, k = _rms_norm(q, w["q_norm"], eps), _rms_norm(k, w["k_norm"], eps)
    window = None
    if kind == "sliding_attention":
        q, k, window = _rope(q, s["theta"]), _rope(k, s["theta"]), s["window"]
    elif kind != "full_attention":
        raise ValueError(f"unknown layer type {kind!r}")
    k, v = (jnp.repeat(t, H // K, axis=2) for t in (k, v))
    attn = _attention(q, k, v, window)
    gate = jax.nn.sigmoid(_linear(h, w["wg"].reshape(E, H * D), quant))
    out = _linear(attn.reshape(B, S, H * D) * gate,
                  w["wo"].reshape(H * D, E), quant)
    return _rms_norm(out, w["attn_post_norm"], eps)


def route(x, router, bias, s, quant=None):
    """x [T, E] -> (top [T, k] indices over all the experts, w [T, k])."""
    scores = jax.nn.sigmoid(_linear(x, router, quant))
    _, top = jax.lax.top_k(scores + bias, s["k"])
    w = jnp.take_along_axis(scores, top, axis=-1)
    return top, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * s["route_scale"]


def held_experts(x, top, w, w_gate, w_up, w_down, held_start, quant=None):
    """sum over the held experts e of coef_e[t] * Expert_e(x[t]), coef_e[t]
    the weight token t gave e (0 if it did not choose it).  x [T, E]."""
    def one(acc, expert):
        e, wg, wu, wd = expert
        coef = jnp.sum(jnp.where(top == held_start + e, w, 0.0), axis=-1)
        return acc + coef[:, None] * _swiglu(x, wg, wu, wd, quant), None

    held = jnp.arange(w_gate.shape[0])
    out, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                          (held, w_gate, w_up, w_down))
    return out


def layer(x, w, bias, s, kind, quant=None):
    """One layer: (h', the router's choices [T, k] or None for a dense
    layer, which ``bias is None`` marks)."""
    B, S, E = x.shape
    a = x + attention_branch(x, w, s, kind, quant)
    h = _rms_norm(a, w["mlp_norm"], s["eps"])
    top = None
    if bias is None:
        f = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)
    else:
        flat = h.reshape(B * S, E)
        top, wts = route(flat, w["router"], bias, s, quant)
        f = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                    quant) + held_experts(
            flat, top, wts, w["w_gate"], w["w_up"], w["w_down"],
            s["held_start"], quant).reshape(B, S, E)
    return a + _rms_norm(f, w["mlp_post_norm"], s["eps"]), top


def _widen(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _layers(weights, bias, s):
    """(one layer's weights, its bias or None, its kind) down the stack."""
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    for i in range(s["L"]):
        j = i - s["Ld"]
        yield ((at(weights["dense"], i), None) if j < 0 else
               (at(weights["moe"], j), bias[j])) + (s["layer_types"][i],)


def _nll(x, final_norm, lm_head, targets, mask, s, quant):
    x = _rms_norm(x, final_norm, s["eps"])
    lg = _linear(x, lm_head, quant)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, targets[..., None], -1)[..., 0]
    return jnp.sum(nll * mask) / jnp.sum(mask)


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes (the items of
    ``s``): a layer forward, a layer backward, the head."""
    s = dict(sizes)
    forward = jax.jit(lambda x, w, b, kind: layer(x, _widen(w), b, s, kind,
                                                  quant),
                      static_argnames="kind")

    def backward(x, w, b, gx, kind):
        _, vjp = jax.vjp(lambda x, w: layer(x, w, b, s, kind, quant)[0],
                         x, _widen(w))
        gx, gw = vjp(gx)
        return gx, {n: gw[n] for n in NORMS}

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


def loss_norm_grads_and_routing(weights, bias, tokens, mask, s, quant=None):
    """(loss over the masked positions of tokens [B, S], its gradient in the
    weights of every RMSNorm, the routers' choices [expert layers, B*S, k]).

    The gradient tree: ``final_norm [E]`` and, under ``dense`` and ``moe``,
    the six norms of ``NORMS`` with a leading layer axis.  As in
    ``reference.loss_and_norm_grads`` the backward pass walks the layers in
    reverse, one ``jax.vjp`` of ``layer`` at a time.  The walk is a Python
    loop over jitted pieces, one program for each kind of layer and none for
    the whole stack, so compiling it does not grow with depth: call it
    outside ``jax.jit``.
    """
    forward, backward, head = _programs(tuple(sorted(s.items())), quant)
    x = weights["embed"].astype(F32)[tokens] * s["E"] ** 0.5
    stack = list(_layers(weights, bias, s))
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
    collect = lambda part: jax.tree.map(lambda *a: jnp.stack(a), *part)
    return loss, {"final_norm": g_final, "dense": collect(grads[:s["Ld"]]),
                  "moe": collect(grads[s["Ld"]:])}, jnp.stack(tops)


def routing(weights, bias, tokens, s, quant=None):
    """The routers' choices [expert layers, B*S, k] for tokens [B, S], row
    after row through the walk's forward programs: no loss, no gradient.
    Call it outside ``jax.jit``, as the walk above."""
    forward, _, _ = _programs(tuple(sorted(s.items())), quant)
    stack = list(_layers(weights, bias, s))
    embed = weights["embed"].astype(F32)
    rows = []
    for row in tokens:
        x, tops = embed[row[None]] * s["E"] ** 0.5, []
        for w, b, kind in stack:
            x, top = forward(x, w, b, kind)
            if top is not None:
                tops.append(top)
        rows.append(jnp.stack(tops))
    return jnp.concatenate(rows, axis=1)


def logits(weights, bias, tokens, s, quant=None):
    """tokens [B, S] -> logits [B, S, V] float32."""
    x = weights["embed"].astype(F32)[tokens] * s["E"] ** 0.5
    for w, b, kind in _layers(weights, bias, s):
        x, _ = layer(x, _widen(w), b, s, kind, quant)
    x = _rms_norm(x, weights["final_norm"].astype(F32), s["eps"])
    return _linear(x, weights["lm_head"].astype(F32), quant)


def routing_mismatch_share(got, want, num_experts):
    """Share of the assignments on which two routings choose differently:
    got / want [layers, T, k] expert indices; an assignment of ``want``
    counts when ``got`` did not choose that expert for that token."""
    member = lambda top: jnp.any(
        top[..., None] == jnp.arange(num_experts), axis=-2)
    return jnp.mean(jnp.sum(member(want) & ~member(got), axis=-1)
                    / want.shape[-1])
