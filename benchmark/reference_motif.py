"""The plain reference of the ``Motif`` block (Motif-3-Beta): forward, the
loss (with the prediction module's where the sizes hold one), the gradient
in every judged weight and the routers' choices, in float32 ``jax.numpy`` at
the highest matmul precision.  Nothing here comes from ``ray_tpu``; the
elementary pieces are ``reference.py``'s, the router and the masked softmax
a head ``reference_afmoe.py``'s and the stream's maps ``reference_xing4.py``'s.

The equations (``config.json``'s keys; what no key settles is marked
(assumed) and listed in ``configs/motif-3-beta.json`` under ``assumed``).
C = hidden, n = ``mhc_expansion_rate`` lanes, N(x; g) = x / rms(x) g with
eps ``rms_norm_eps``.

- Stream: a token's state is X [n, C], X_0 the embedding row in every lane
  (assumed); every sublayer ``u = sum_j H_pre[j] X[j]``, ``y = F(N(clip(u,
  +-hidden_clamp); g))`` (assumed: where the clamp stands), ``X <- H_res X +
  H_post (x) y`` with the maps of arXiv:2512.24880 as ``reference_xing4.maps``
  writes them, ``mhc_sinkhorn_iters`` passes, NO clamp on H_res' logits (no
  key is published; assumed).
- Attention (``gdla``): ``c_q = N(h W_qa; g_q)``; query head i < 80 ``[q_n ;
  q_r] = c_q W_qb,i`` (128 + 64); ``[c_kv ; k_r] = h W_kva``; key head j <
  16 ``[k_n ; v] = N(c_kv; g_kv) W_kvb,j`` (128 + 128), its key ``[k_n ;
  RoPE(k_r)]`` with the one rotary key for all heads; plain RoPE at theta
  10,000 on the pairs (i, i + 32) (``apply_yarn_scaling`` false; DEPARTURE
  as ``reference_xing4``'s: the published code pairs (2i, 2i + 1), a
  permutation of columns no score sees).  Query head i reads key head i //
  5; ``o_i = softmax(mask(q_i k^T 192^-1/2)) v``, one explicit softmax a
  query head, the mask causal and on a window layer also ``t - s < 128``;
  layer l is full where ``(l + 1) % 4 == 0`` (assumed: which of four).  Of
  a key head's five query heads the first four are signal heads and the
  fifth its noise head (assumed: which of five); signal head m of key head
  j: ``d_m = o_m - sigmoid(h w_lambda,m) o_noise(j)`` (``diff_v2``); ``a =
  (d * sigmoid(h W_g)) W_o`` over the 64 x 128 channels (assumed: the
  gate's form, arXiv:2505.06708).
- Feed-forward: ``W_down(P(h W_gate) * (h W_up))``, P PolyNorm: ``s (p_0
  n(x^3) + p_1 n(x^2) + p_2 n(x) + clip(p_3, +-c))``, ``n(y) = y / rms(y)``
  over the width, s = ``polynorm_output_scale``, c =
  ``polynorm_bias_clamp`` (assumed: where s and c stand), one set of four
  numbers a feed-forward module: the dense one, the shared expert, a
  layer's routed experts together (assumed).  After ``n_dense_first_layers``
  ``Shared(h) + sum_{e in top, held} w_e Expert_e(h)`` with the sigmoid
  router of ``reference_afmoe.route`` (top 8 of s + bias, ``route_norm``,
  ``route_scale``), the held experts one at a time in a loop.
- End: ``x_out = sum_j X_L[j]`` (assumed); loss = masked mean CE of
  ``N(x_out; g_f) W_head`` against token t + 1, plus, where the sizes hold a
  prediction module (``mtp`` 1; DeepSeek-V3's form as ``reference_xing4``'s
  tail), ``mtp_weight`` times its loss against token t + 2.

The share: as ``reference_afmoe``; the module's layer holds the same experts.

``quant="int8"`` is the control: the inputs of every linear layer (the maps'
thin product, the routers', ``w_lambda``'s and the gate's too) rounded to
8-bit integers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _linear, _rms_norm, _rope,
                                 relative_distance)  # noqa: F401
from benchmark.reference_afmoe import (_attention, _nll, _widen, route,
                                       routing_mismatch_share)  # noqa: F401
from benchmark.reference_xing4 import _lanes, _shift, maps

#: a layer's judged weights: its four RMSNorm weights, its two sublayers'
#: hyper-connection weights, PolyNorm's numbers (``mlp_poly`` in a dense
#: layer, ``shared_poly`` and ``expert_poly`` in an expert layer) and the
#: differential pair's ``w_lambda``, whose gradient exists only through the
#: subtraction
NORMS = ("attn_norm", "mlp_norm", "q_norm", "kv_norm")
MAPS = tuple(f"hc_{sub}_{part}" for sub in ("attn", "mlp")
             for part in ("phi", "b", "alpha"))
POLYS = ("mlp_poly", "shared_poly", "expert_poly")
JUDGED = NORMS + MAPS + POLYS + ("w_lambda",)


def is_full(i, s):
    """Whether layer ``i`` here is full-causal (the module's is ``L``)."""
    return (s["first_layer"] + i + 1) % s["period"] == 0


def gdla(h, w, s, full, quant=None):
    """h [B, S, C] -> [B, S, C]."""
    B, S, C = h.shape
    H, Hkv, dn, dr, dv, rkv, eps = (s[k] for k in (
        "H", "Hkv", "dn", "dr", "dv", "rkv", "eps"))
    group, Hs = H // Hkv, H - s["noise"]
    c_q = _rms_norm(_linear(h, w["wq_a"], quant), w["q_norm"], eps)
    q = _linear(c_q, w["wq_b"].reshape(-1, H * (dn + dr)), quant
                ).reshape(B, S, H, dn + dr)
    kv_a = _linear(h, w["wkv_a"], quant)
    c = _rms_norm(kv_a[..., :rkv], w["kv_norm"], eps)
    kv = _linear(c, w["wkv_b"].reshape(-1, Hkv * (dn + dv)), quant
                 ).reshape(B, S, Hkv, dn + dv)
    k_r = _rope(kv_a[..., None, rkv:], s["theta"])          # one head
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s["theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, Hkv, dr))], -1)
    # query head i reads key head i // group
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, kv[..., dn:]))
    # 80 explicit softmaxes under an explicit mask, a head at a time, the
    # scale (dn + dr) ** -0.5 of q's width (``reference_afmoe._attention``)
    o = _attention(q, k, v, None if full else s["W"])
    if s["noise"]:
        o = o.reshape(B, S, Hkv, group, dv)
        lam = jax.nn.sigmoid(_linear(h, w["w_lambda"], quant))
        o = o[..., :group - 1, :] - lam.reshape(
            B, S, Hkv, group - 1, 1) * o[..., group - 1:, :]
    d = o.reshape(B, S, Hs * dv) * jax.nn.sigmoid(
        _linear(h, w["w_attn_gate"], quant))
    return _linear(d, w["wo"].reshape(Hs * dv, C), quant)


def poly_norm(x, p, s):
    n = lambda y: y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                                    + s["eps"])
    return s["poly_scale"] * (
        p[0] * n(x ** 3) + p[1] * n(x ** 2) + p[2] * n(x)
        + jnp.clip(p[3], -s["poly_clamp"], s["poly_clamp"]))


def _polyglu(x, w_gate, w_up, w_down, p, s, quant):
    return _linear(poly_norm(_linear(x, w_gate, quant), p, s)
                   * _linear(x, w_up, quant), w_down, quant)


def held_experts(x, top, wts, w, s, quant=None):
    """sum over the held experts e of coef_e[t] * Expert_e(x[t]), coef_e[t]
    the weight token t gave e (0 if it did not choose it), an expert at a
    time.  x [T, E]."""
    def one(acc, expert):
        e, wg, wu, wd = expert
        coef = jnp.sum(jnp.where(top == s["held_start"] + e, wts, 0.0), -1)
        return acc + coef[:, None] * _polyglu(
            x, wg, wu, wd, w["expert_poly"], s, quant), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(x),
        (jnp.arange(w["w_gate"].shape[0]), w["w_gate"], w["w_up"],
         w["w_down"]))
    return out


def feed_forward(h, w, bias, s, quant=None):
    """(F(h), the router's choices [T, k] or None for a dense layer, which
    ``bias is None`` marks)."""
    B, S, C = h.shape
    if bias is None:
        return _polyglu(h, w["w_gate"], w["w_up"], w["w_down"],
                        w["mlp_poly"], s, quant), None
    flat = h.reshape(B * S, C)
    top, wts = route(flat, w["router"], bias, s, quant)
    return (_polyglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                     w["shared_poly"], s, quant)
            + held_experts(flat, top, wts, w, s, quant).reshape(B, S, C), top)


def sublayer(X, w, sub, F, s, quant=None):
    H_pre, H_post, H_res = maps(X, w, sub, s, quant)
    u = jnp.einsum("bsj,bsjc->bsc", H_pre, X, precision="highest")
    u = jnp.clip(u, -s["hidden_clamp"], s["hidden_clamp"])
    y = F(_rms_norm(u, w[f"{sub}_norm"], s["eps"]))
    return (jnp.einsum("bsij,bsjc->bsic", H_res, X, precision="highest")
            + H_post[..., None] * y[:, :, None, :])


def layer(X, w, bias, s, full, quant=None):
    """One layer on the stream X [B, S, n, C]: (X', the router's choices)."""
    X = sublayer(X, w, "attn", lambda h: gdla(h, w, s, full, quant), s, quant)
    tops = []

    def F(h):
        y, top = feed_forward(h, w, bias, s, quant)
        tops.append(top)
        return y

    return sublayer(X, w, "mlp", F, s, quant), tops[0]


def tail(x_out, final_norm, lm_head, embed, mtp, bias, tokens, mask, s,
         quant=None):
    """(loss, (main loss, the module's loss, its router's choices)) from the
    stack's result x_out [B, S, C] (the lanes' sum, before the final norm);
    without a module (``mtp`` None) its loss is 0 and it has no choices."""
    targets, mask = _shift(tokens), mask.astype(F32)
    main = _nll(x_out, final_norm, lm_head, targets, mask, s, quant)
    if mtp is None:
        return main, (main, jnp.zeros((), F32), None)
    pair = jnp.concatenate(
        [_rms_norm(x_out, mtp["h_norm"], s["eps"]),
         _rms_norm(embed[targets], mtp["e_norm"], s["eps"])], -1)
    Z, top = layer(_lanes(_linear(pair, mtp["proj"], quant), s["n"]),
                   jax.tree.map(lambda a: a[0], mtp["layer"]), bias, s,
                   is_full(s["L"], s), quant)
    module = _nll(jnp.sum(Z, axis=2), mtp["final_norm"], lm_head,
                  _shift(targets), _shift(mask), s, quant)
    return main + s["mtp_weight"] * module, (main, module, top)


def judged_of_layer(g):
    return {n: g[n] for n in JUDGED if n in g}


def judged_of_tail(final_norm, mtp):
    out = {"final_norm": final_norm}
    if mtp is not None:
        out["mtp"] = {"h_norm": mtp["h_norm"], "e_norm": mtp["e_norm"],
                      "final_norm": mtp["final_norm"],
                      "layer": judged_of_layer(mtp["layer"])}
    return out


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes: a layer forward
    and a layer backward (``full`` static: one program a kind), the tail
    with its backward."""
    s = dict(sizes)

    @functools.partial(jax.jit, static_argnames="full")
    def forward(X, w, b, full):
        return layer(X, _widen(w), b, s, full, quant)

    @functools.partial(jax.jit, static_argnames="full")
    def backward(X, w, b, gX, full):
        _, vjp = jax.vjp(lambda X, w: layer(X, w, b, s, full, quant)[0], X,
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
    """(one layer's weights, its bias or None, its kind) down the stack."""
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    for i in range(s["L"]):
        j = i - s["Ld"]
        yield ((at(weights["dense"], i), None) if j < 0 else
               (at(weights["moe"], j), bias[j])) + (is_full(i, s),)


def loss_judged_grads_and_routing(weights, bias, tokens, mask, s, quant=None):
    """(loss, {"main_loss", "mtp_loss"}, the loss's gradient in every judged
    weight, the routers' choices [expert layers (+ 1), B*S, k], the module's
    last).  ``bias`` [expert layers (+ 1), X].

    The gradient tree: ``final_norm``; under ``dense`` and ``moe`` the names
    of ``JUDGED`` a layer of that kind has, with a leading layer axis; with
    a module, under ``mtp`` its three norms and its ``layer``.  The walk is
    a Python loop over jitted pieces, one ``jax.vjp`` of a layer at a time
    in reverse: call it outside ``jax.jit``."""
    forward, backward, _, tail_backward = _programs(
        tuple(sorted(s.items())), quant)
    X = _lanes(weights["embed"].astype(F32)[tokens], s["n"])
    stack = list(_stack(weights, bias, s))
    Xs, tops = [], []
    for w, b, full in stack:
        Xs.append(X)
        X, top = forward(X, w, b, full=full)
        if top is not None:
            tops.append(top)
    loss, (main, module, top), gx, g_tail = tail_backward(
        jnp.sum(X, axis=2), weights["final_norm"], weights["lm_head"],
        weights["embed"], weights.get("mtp"), bias[-1], tokens, mask)
    gX = _lanes(gx, s["n"])
    grads = []
    for X, (w, b, full) in reversed(list(zip(Xs, stack))):
        gX, g = backward(X, w, b, gX, full=full)
        grads.append(g)
    grads.reverse()
    def collect(part, kind):
        """One gradient a layer as a stack; of no layers, empty stacks."""
        if part:
            return jax.tree.map(lambda *a: jnp.stack(a), *part)
        return jax.tree.map(lambda a: a.astype(F32),
                            judged_of_layer(weights[kind]))

    return (loss, {"main_loss": main, "mtp_loss": module},
            {**g_tail, "dense": collect(grads[:s["Ld"]], "dense"),
             "moe": collect(grads[s["Ld"]:], "moe")},
            jnp.stack(tops + ([] if top is None else [top])))


def routing(weights, bias, tokens, s, quant=None):
    """The routers' choices [expert layers (+ 1), B*S, k] for tokens [B, S],
    row after row through the walk's forward programs: no gradient.  Call it
    outside ``jax.jit``."""
    forward, _, tail_forward, _ = _programs(tuple(sorted(s.items())), quant)
    stack = list(_stack(weights, bias, s))
    embed = weights["embed"].astype(F32)
    rows = []
    for row in tokens:
        X, tops = _lanes(embed[row[None]], s["n"]), []
        for w, b, full in stack:
            X, top = forward(X, w, b, full=full)
            if top is not None:
                tops.append(top)
        if "mtp" in weights:
            _, (_, _, top) = tail_forward(
                jnp.sum(X, axis=2), weights["final_norm"],
                weights["lm_head"], weights["embed"], weights["mtp"],
                bias[-1], row[None], jnp.ones_like(row[None]))
            tops.append(top)
        rows.append(jnp.stack(tops))
    return jnp.concatenate(rows, axis=1)


def logits(weights, bias, tokens, s, quant=None):
    """tokens [B, S] -> next-token logits [B, S, V] float32 (no module)."""
    X = _lanes(weights["embed"].astype(F32)[tokens], s["n"])
    for w, b, full in _stack(weights, bias, s):
        X, _ = layer(X, _widen(w), b, s, full, quant)
    x = _rms_norm(jnp.sum(X, axis=2), weights["final_norm"].astype(F32),
                  s["eps"])
    return _linear(x, weights["lm_head"].astype(F32), quant)
