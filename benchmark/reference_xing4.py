"""The plain reference of the ``xing4_0`` block (Xing4.0-29B-A4B): forward,
both losses, the gradient in every judged weight and the routers' choices,
in float32 ``jax.numpy`` at the highest matmul precision.  Nothing here comes
from ``ray_tpu``; the elementary pieces are ``reference.py``'s and the expert
layer's (router, held experts one at a time, SwiGLU) ``reference_afmoe.py``'s.

The equations (``config.json``'s keys; what no key settles is marked
(assumed) and listed in ``configs/xing4.0-29b-a4b.json`` under ``assumed``).
C = hidden, n = ``hc_mult`` lanes, N(x; g) = x / rms(x) g with eps
``rms_norm_eps``.

- Stream: a token's state is X [n, C]; X_0 is the embedding row in every
  lane (assumed, arXiv:2409.19606).  Float32 here; the program holds it in
  bfloat16 (assumed).
- Maps of a sublayer (its own phi [nC, 2n + n n], b, three gains alpha):
  ``m = vec(X) / rms(vec(X)) . phi`` (no weight, assumed); ``H_pre =
  sigmoid(alpha_pre m[:n] + b[:n])``; ``H_post = 2 sigmoid(alpha_post
  m[n:2n] + b[n:2n])``; ``R = clamp(alpha_res mat(m[2n:]) + mat(b[2n:]),
  mhc_h_res_clamp_min, mhc_h_res_clamp_max)``; ``M = exp(R)``, then
  ``hc_sinkhorn_iters`` times: every column divided by its sum + ``hc_eps``,
  then every row by its (assumed: columns first, eps in the denominators;
  arXiv:2512.24880); ``H_res = M``.  A plain loop.
- Sublayer: ``u = sum_j H_pre[j] X[j]``; ``y = F(N(u; g))``; ``X <- H_res X
  + H_post (x) y``.  F is attention, then the feed-forward part.
- Attention: ``c_q = N(h W_qa; g_q)``; ``[q_n ; q_r] = c_q W_qb`` (heads of
  128 + 64); ``[c_kv ; k_r] = h W_kva``; ``[k_n ; v] = N(c_kv; g_kv) W_kvb``
  (heads of 128 + 128); ``q = [q_n ; RoPE(q_r)]``, ``k = [k_n ; RoPE(k_r)]``
  with one rotary key for all heads; causal softmax of ``s q.k``, ``s =
  192^-1/2 m_y^2``, ``m_y = 0.1 mscale_all_dim ln(factor) + 1``; ``y = o
  W_o``.  RoPE with yarn's blended frequencies (assumed: DeepSeek-V3's
  published modelling code for these keys).  DEPARTURE: that code pairs the
  rotary channels (2i, 2i+1); this reference, like the program, pairs (i, i +
  32).  With seeded random weights the two differ by a permutation of
  W_qb's and W_kva's rotary columns, which no score sees.
- Feed-forward: below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; after, ``Shared(h) + sum_{e in top, held} w_e
  Expert_e(h)`` with the sigmoid router of ``reference_afmoe.route`` (top 4
  of s + bias, ``norm_topk_prob``, ``routed_scaling_factor``; one group).
- End: ``x_out = sum_j X_L[j]`` (assumed); main loss = masked mean CE of
  ``N(x_out; g_f) W_head`` against token t + 1.
- Prediction module (assumed: DeepSeek-V3's form): ``z_t = [N(x_out,t; g_h)
  ; N(Emb(token_{t+1}); g_e)] W_eh`` in every lane, one expert layer of its
  own, ``N(sum_j Z[j]; g_m) W_head`` against token t + 2, masked where the
  main loss's mask covers position t + 1.  Loss = main + ``mtp_weight`` *
  module's (assumed: 0.3).

The share: as ``reference_afmoe``; the module's layer holds the same experts.

``quant="int8"`` is the control: the inputs of every linear layer (the maps'
thin product and the routers' too) rounded to 8-bit integers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _linear, _rms_norm,
                                 relative_distance)  # noqa: F401
from benchmark.reference_afmoe import (_nll, _swiglu, _widen, held_experts,
                                       route,
                                       routing_mismatch_share)  # noqa: F401

#: a layer's judged weights: its four RMSNorm weights and, for each of its
#: two sublayers, the hyper-connection's phi, b and gains, whose gradients
#: exist only through the maps' and Sinkhorn's backward
NORMS = ("attn_norm", "mlp_norm", "q_norm", "kv_norm")
MAPS = tuple(f"hc_{sub}_{part}" for sub in ("attn", "mlp")
             for part in ("phi", "b", "alpha"))
JUDGED = NORMS + MAPS


def yarn_angles(S, s):
    """Position times frequency, [S, 32]: ``theta_i = theta^(-2i/64)``
    blended with ``theta_i / factor`` by a ramp from the pair that turns
    ``beta_fast`` times over the original context to the one that turns
    ``beta_slow`` times."""
    D, base = s["dr"], s["theta"]
    pair = lambda turns: D * math.log(
        s["yarn_original"] / (turns * 2 * math.pi)) / (2 * math.log(base))
    lo = max(math.floor(pair(s["yarn_beta_fast"])), 0)
    hi = min(math.ceil(pair(s["yarn_beta_slow"])), D - 1)
    i = jnp.arange(D // 2, dtype=F32)
    ramp = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    freq = base ** (-2 * i / D)
    freq = freq * (1 - ramp) + freq / s["yarn_factor"] * ramp
    return jnp.arange(S, dtype=F32)[:, None] * freq[None, :]


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, s):
    """x [B, S, H, 64]: the pairs (i, i + 32) turned by yarn's angles, the
    tables scaled by mscale / mscale_all_dim."""
    ang = yarn_angles(x.shape[1], s)
    scale = (_mscale(s["yarn_factor"], s["yarn_mscale"])
             / _mscale(s["yarn_factor"], s["yarn_mscale_all_dim"]))
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal softmax attention, q / k [B, S, H, Dqk], v [B, S, H, Dv] ->
    [B, S, H, Dv].  One head at a time, recomputed in the backward pass."""
    S = q.shape[1]
    visible = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv
        scores = jnp.einsum("bqd,bkd->bqk", q, k, precision="highest") * scale
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, v, precision="highest")

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 2)


def latent_attention(h, w, s, quant=None):
    """h [B, S, C] -> [B, S, C]."""
    B, S, C = h.shape
    H, dn, dr, dv, rkv, eps = (s[k] for k in ("H", "dn", "dr", "dv", "rkv",
                                              "eps"))
    c_q = _rms_norm(_linear(h, w["wq_a"], quant), w["q_norm"], eps)
    q = _linear(c_q, w["wq_b"].reshape(-1, H * (dn + dr)), quant
                ).reshape(B, S, H, dn + dr)
    kv_a = _linear(h, w["wkv_a"], quant)
    c = _rms_norm(kv_a[..., :rkv], w["kv_norm"], eps)
    kv = _linear(c, w["wkv_b"].reshape(-1, H * (dn + dv)), quant
                 ).reshape(B, S, H, dn + dv)
    k_r = _rope(kv_a[..., None, rkv:], s)                   # one head
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, H, dr))], -1)
    scale = (dn + dr) ** -0.5 * _mscale(s["yarn_factor"],
                                        s["yarn_mscale_all_dim"]) ** 2
    o = _attention(q, k, kv[..., dn:], scale)
    return _linear(o.reshape(B, S, H * dv), w["wo"].reshape(H * dv, C), quant)


def sinkhorn(R, iters, eps):
    """exp(R) [..., n, n] (rows, columns) made doubly stochastic."""
    M = jnp.exp(R)
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)  # each column
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)  # each row
    return M


def maps(X, w, sub, s, quant=None):
    """X [B, S, n, C] -> (H_pre [B, S, n], H_post [B, S, n], H_res
    [B, S, n, n])."""
    B, S, n, C = X.shape
    vec = X.reshape(B, S, n * C)
    xhat = vec * jax.lax.rsqrt(jnp.mean(vec * vec, -1, keepdims=True)
                               + s["eps"])
    m = _linear(xhat, w[f"hc_{sub}_phi"], quant)
    b, alpha = w[f"hc_{sub}_b"], w[f"hc_{sub}_alpha"]
    H_pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + b[:n])
    H_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + b[n:2 * n])
    R = jnp.clip(alpha[2] * m[..., 2 * n:] + b[2 * n:], s["hc_lo"],
                 s["hc_hi"]).reshape(B, S, n, n)
    return H_pre, H_post, sinkhorn(R, s["hc_iters"], s["hc_eps"])


def sublayer(X, w, sub, F, s, quant=None):
    H_pre, H_post, H_res = maps(X, w, sub, s, quant)
    u = jnp.einsum("bsj,bsjc->bsc", H_pre, X, precision="highest")
    y = F(_rms_norm(u, w[f"{sub}_norm"], s["eps"]))
    return (jnp.einsum("bsij,bsjc->bsic", H_res, X, precision="highest")
            + H_post[..., None] * y[:, :, None, :])


def feed_forward(h, w, bias, s, quant=None):
    """(F(h), the router's choices [T, k] or None for a dense layer, which
    ``bias is None`` marks)."""
    B, S, C = h.shape
    if bias is None:
        return _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant), None
    flat = h.reshape(B * S, C)
    top, wts = route(flat, w["router"], bias, s, quant)
    return (_swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                    quant)
            + held_experts(flat, top, wts, w["w_gate"], w["w_up"],
                           w["w_down"], s["held_start"], quant
                           ).reshape(B, S, C), top)


def layer(X, w, bias, s, quant=None):
    """One layer on the stream X [B, S, n, C]: (X', the router's choices)."""
    X = sublayer(X, w, "attn", lambda h: latent_attention(h, w, s, quant), s,
                 quant)
    tops = []

    def F(h):
        y, top = feed_forward(h, w, bias, s, quant)
        tops.append(top)
        return y

    return sublayer(X, w, "mlp", F, s, quant), tops[0]


def _lanes(x, n):
    return jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n,)
                            + x.shape[2:])


def _shift(a):
    return jnp.concatenate([a[:, 1:], jnp.zeros_like(a[:, :1])], 1)


def tail(x_out, final_norm, lm_head, embed, mtp, bias, tokens, mask, s,
         quant=None):
    """(loss, (main loss, the module's loss, its router's choices)) from the
    stack's result x_out [B, S, C] (the lanes' sum, before the final
    norm)."""
    targets, mask = _shift(tokens), mask.astype(F32)
    main = _nll(x_out, final_norm, lm_head, targets, mask, s, quant)
    pair = jnp.concatenate(
        [_rms_norm(x_out, mtp["h_norm"], s["eps"]),
         _rms_norm(embed[targets], mtp["e_norm"], s["eps"])], -1)
    Z, top = layer(_lanes(_linear(pair, mtp["proj"], quant), s["n"]),
                   jax.tree.map(lambda a: a[0], mtp["layer"]), bias, s, quant)
    module = _nll(jnp.sum(Z, axis=2), mtp["final_norm"], lm_head,
                  _shift(targets), _shift(mask), s, quant)
    return main + s["mtp_weight"] * module, (main, module, top)


def judged_of_layer(g):
    return {n: g[n] for n in JUDGED}


def judged_of_tail(final_norm, mtp):
    return {"final_norm": final_norm,
            "mtp": {"h_norm": mtp["h_norm"], "e_norm": mtp["e_norm"],
                    "final_norm": mtp["final_norm"],
                    "layer": judged_of_layer(mtp["layer"])}}


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes: a layer forward,
    a layer backward, the tail (both losses and the module) with its
    backward."""
    s = dict(sizes)
    forward = jax.jit(lambda X, w, b: layer(X, _widen(w), b, s, quant))

    @jax.jit
    def backward(X, w, b, gX):
        _, vjp = jax.vjp(lambda X, w: layer(X, w, b, s, quant)[0], X,
                         _widen(w))
        gX, gw = vjp(gX)
        return gX, judged_of_layer(gw)

    @jax.jit
    def tail_forward(x_out, final_norm, lm_head, embed, mtp, bias, tokens,
                     mask):
        return tail(x_out, *_widen((final_norm, lm_head, embed, mtp)), bias,
                    tokens, mask, s, quant)

    @jax.jit
    def tail_backward(x_out, final_norm, lm_head, embed, mtp, bias, tokens,
                      mask):
        loss, vjp, parts = jax.vjp(
            lambda x, n, m: tail(x, n, lm_head.astype(F32),
                                 embed.astype(F32), m, bias, tokens, mask, s,
                                 quant),
            x_out, *_widen((final_norm, mtp)), has_aux=True)
        gx, g_final, g_mtp = vjp(jnp.ones((), F32))
        return loss, parts, gx, judged_of_tail(g_final, g_mtp)

    return forward, backward, tail_forward, tail_backward


def _stack(weights, bias, s):
    """(one layer's weights, its bias or None) down the stack."""
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    for i in range(s["L"]):
        j = i - s["Ld"]
        yield (at(weights["dense"], i), None) if j < 0 else \
            (at(weights["moe"], j), bias[j])


def loss_judged_grads_and_routing(weights, bias, tokens, mask, s, quant=None):
    """(loss, {"main_loss", "mtp_loss"}, the loss's gradient in every judged
    weight, the routers' choices [expert layers + 1, B*S, k], the module's
    last).  ``bias`` [expert layers + 1, X], the module's last.

    The gradient tree: ``final_norm``; under ``dense`` and ``moe`` the names
    of ``JUDGED`` with a leading layer axis; under ``mtp`` its three norms
    and its ``layer``.  The walk is a Python loop over jitted pieces, one
    ``jax.vjp`` of a layer at a time in reverse: call it outside
    ``jax.jit``."""
    forward, backward, _, tail_backward = _programs(
        tuple(sorted(s.items())), quant)
    X = _lanes(weights["embed"].astype(F32)[tokens], s["n"])
    stack = list(_stack(weights, bias, s))
    Xs, tops = [], []
    for w, b in stack:
        Xs.append(X)
        X, top = forward(X, w, b)
        if top is not None:
            tops.append(top)
    loss, (main, module, top), gx, g_tail = tail_backward(
        jnp.sum(X, axis=2), weights["final_norm"], weights["lm_head"],
        weights["embed"], weights["mtp"], bias[-1], tokens, mask)
    gX = _lanes(gx, s["n"])
    grads = []
    for X, (w, b) in reversed(list(zip(Xs, stack))):
        gX, g = backward(X, w, b, gX)
        grads.append(g)
    grads.reverse()
    collect = lambda part: jax.tree.map(lambda *a: jnp.stack(a), *part)
    return (loss, {"main_loss": main, "mtp_loss": module},
            {**g_tail, "dense": collect(grads[:s["Ld"]]),
             "moe": collect(grads[s["Ld"]:])}, jnp.stack(tops + [top]))


def routing(weights, bias, tokens, s, quant=None):
    """The routers' choices [expert layers + 1, B*S, k] for tokens [B, S],
    row after row through the walk's forward programs: no gradient.  Call it
    outside ``jax.jit``."""
    forward, _, tail_forward, _ = _programs(tuple(sorted(s.items())), quant)
    stack = list(_stack(weights, bias, s))
    embed = weights["embed"].astype(F32)
    rows = []
    for row in tokens:
        X, tops = _lanes(embed[row[None]], s["n"]), []
        for w, b in stack:
            X, top = forward(X, w, b)
            if top is not None:
                tops.append(top)
        _, (_, _, top) = tail_forward(
            jnp.sum(X, axis=2), weights["final_norm"], weights["lm_head"],
            weights["embed"], weights["mtp"], bias[-1], row[None],
            jnp.ones_like(row[None]))
        rows.append(jnp.stack(tops + [top]))
    return jnp.concatenate(rows, axis=1)
