"""The plain reference of the ``bailing_hybrid`` block (Ling-3.0-flash):
forward, loss, the gradient in every RMSNorm weight (or in every weight, for
the CPU tests) and the routers' choices, in float32 ``jax.numpy`` at the
highest matmul precision.  Nothing here comes from ``ray_tpu``: no kernel, no
chunked delta rule, no grouped product.  The elementary pieces (RMSNorm,
rotary embedding on split halves, a linear layer with its int8 control, the
distance) are ``reference.py``'s, the masked softmax a head at a time, the
SwiGLU, the held experts one at a time and the head's loss
``reference_afmoe.py``'s.

The equations (``config.json``'s keys; what no key settles is marked
(assumed) and listed in ``configs/ling-3.0-flash.json`` under ``assumed``).
C = hidden, N(x; w) = x / rms(x) w with eps ``rms_norm_eps``.

- Stream: ``h0 = Emb[tokens]``; a layer is ``a = h + Mixer(N(h; w_1))``,
  ``h' = a + F(N(a; w_2))``.  Layer ``i`` (published index) mixes by latent
  attention where ``(i + 1) % layer_group_size == 0`` and by KDA otherwise.
- KDA, H heads of d = ``head_dim``: ``[q ; k ; v] = silu(conv4(x W_qkv))``, a
  causal depthwise convolution of ``short_conv_kernel_size`` taps whose last
  tap reads the token itself, no bias; ``q`` and ``k`` divided by
  ``sqrt(sum of squares + 1e-6)`` a head (assumed: the eps);
  ``beta = sigmoid(x W_beta)`` a head; ``g = kda_lower_bound *
  sigmoid(exp(A_log_h) (x W_a + dt_bias))`` a channel; the recurrence a
  head, token by token, from a zero state::

      S_t = Diag(exp(g_t)) S_{t-1}
      S_t = S_t + beta_t k_t (v_t - S_t^T k_t)^T
      o_t = S_t^T q_t / sqrt(d)

  then ``(N_head(o; w_o) * sigmoid(x W_g)) W_out``, the norm over each
  head's d channels with one weight of d.
- Latent attention: ``reference_deepseek_v3.py``'s equations (no query
  bottleneck, one rotary key head, scores over 128 + 64 channels scaled by
  ``192^-1/2``, rotary pairs (i, i + 32): the DEPARTURE that file states),
  plus the head-wise gate: head h's result times ``sigmoid(x W_theta)_h``
  before ``W_o``.
- Feed-forward: below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; after, ``Shared(x) + sum_{e in top, held} w_e
  Expert_e(x)``: ``s = sigmoid(x W_r)``; ``c = s + b``; the experts are
  ``n_group`` consecutive groups, a group scores the sum of its two largest
  ``c``, the ``topk_group`` best groups are kept, ``top`` the
  ``num_experts_per_tok`` largest ``c`` inside them; ``w = s[top] / (sum +
  1e-20) x routed_scaling_factor``.  ``b`` is the selection bias, state.
- End: masked mean cross-entropy of ``N(h_L; w_f) W_head`` against token
  t + 1, untied, no auxiliary term, no prediction module (assumed: its
  published loss weight is 0).

The share: the experts held are ``held_start <= e < held_start + Xh`` of the
router's ``X``; what the others would add is left out, and that partial
result goes on to the next layer, as in the program.

``quant="int8"`` is the control: the inputs of every linear layer (the
routers' too) rounded to 8-bit integers.  The recurrence has no linear layer
and is not rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _linear, _rms_norm, _rope,
                                 relative_distance)  # noqa: F401
from benchmark.reference_afmoe import (_attention, _nll, _swiglu, _widen,
                                       held_experts,
                                       routing_mismatch_share)  # noqa: F401

KDA, MLA = "kda", "mla"

#: tokens the recurrence walks between two kept states (``jax.checkpoint``:
#: a row of 8,192 keeps 64 states a head and not 8,192)
WALK = 128


def norm_names(kind: str):
    """A layer's RMSNorm weights: ``o_norm``'s gradient exists only through
    the delta rule, ``kv_norm``'s only through the latent."""
    return ("attn_norm", "mlp_norm", "o_norm" if kind == KDA else "kv_norm")


def conv_silu(c, w):
    """c [B, S, Ch], w [K, Ch]: ``silu(sum_j w[j] c[t - (K - 1) + j])``,
    nothing before a row's start."""
    K, S = w.shape[0], c.shape[1]
    padded = jnp.pad(c, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + S] * w[j] for j in range(K)))


def delta_rule(q, k, v, g, beta):
    """The recurrence above, token by token: q, k, g [B, S, H, d], v
    [B, S, H, dv], beta [B, S, H] -> o [B, S, H, dv]."""
    B, S, H, d = q.shape
    dv = v.shape[-1]

    def token(St, x):
        q_, k_, v_, g_, b_ = x                  # [B, H, d], b_ [B, H]
        St = jnp.exp(g_)[..., None] * St        # [B, H, d, dv]
        read = jnp.einsum("bhk,bhkv->bhv", k_, St, precision="highest")
        St = St + k_[..., None] * (b_[..., None] * (v_ - read))[..., None, :]
        return St, jnp.einsum("bhk,bhkv->bhv", q_, St,
                              precision="highest") * d ** -0.5

    @jax.checkpoint
    def walk(St, xs):
        return jax.lax.scan(token, St, xs)

    n = -(-S // WALK)
    pad = n * WALK - S
    by_walk = lambda a: jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)),
        1, 0).reshape((n, WALK, B) + a.shape[2:])
    _, o = jax.lax.scan(walk, jnp.zeros((B, H, d, dv), F32),
                        tuple(by_walk(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((n * WALK, B, H, dv)), 0, 1)[:, :S]


def kda_mixer(x, w, s, quant=None):
    """x [B, S, C] (normed) -> [B, S, C]."""
    B, S, _ = x.shape
    H, d, eps = s["H"], s["D"], s["eps"]
    qkv = conv_silu(_linear(x, w["w_qkv"], quant), w["conv_w"])
    q, k, v = (c.reshape(B, S, H, d) for c in jnp.split(qkv, 3, axis=-1))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(_linear(x, w["w_beta"], quant))
    g = s["bound"] * jax.nn.sigmoid(
        jnp.repeat(jnp.exp(w["A_log"]), d)
        * (_linear(x, w["w_a"], quant) + w["dt_bias"]))
    o = delta_rule(unit(q), unit(k), v, g.reshape(B, S, H, d), beta)
    o = _rms_norm(o, w["o_norm"], eps).reshape(B, S, H * d)
    return _linear(o * jax.nn.sigmoid(_linear(x, w["w_g"], quant)), w["wo"],
                   quant)


def latent_attention(x, w, s, quant=None):
    """x [B, S, C] (normed) -> [B, S, C], with the head-wise gate."""
    B, S, C = x.shape
    H, dn, dr, dv, rkv, eps = (s[k] for k in ("H", "dn", "dr", "dv", "rkv",
                                              "eps"))
    q = _linear(x, w["wq"].reshape(C, H * (dn + dr)), quant
                ).reshape(B, S, H, dn + dr)
    kv_a = _linear(x, w["wkv_a"], quant)
    c = _rms_norm(kv_a[..., :rkv], w["kv_norm"], eps)
    kv = _linear(c, w["wkv_b"].reshape(rkv, H * (dn + dv)), quant
                 ).reshape(B, S, H, dn + dv)
    k_r = _rope(kv_a[..., None, rkv:], s["theta"])          # one head
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s["theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, H, dr))], -1)
    o = _attention(q, k, kv[..., dn:], None)                # / sqrt(192)
    o = o * jax.nn.sigmoid(_linear(x, w["w_head_gate"], quant))[..., None]
    return _linear(o.reshape(B, S, H * dv), w["wo"].reshape(H * dv, C), quant)


def route(x, router, bias, s, quant=None):
    """x [T, C] -> (top [T, k] indices over all the experts, w [T, k]):
    DeepSeek-V3's group-limited ``noaux_tc``."""
    scores = jax.nn.sigmoid(_linear(x, router, quant))              # [T, X]
    T, X = scores.shape
    n, keep = s["n_group"], s["topk_group"]
    choice = scores + bias
    by_group = choice.reshape(T, n, X // n)
    group_score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)
    kept = jnp.argsort(-group_score, axis=-1)[:, :keep]             # [T, keep]
    in_kept = jnp.zeros((T, n), bool).at[jnp.arange(T)[:, None], kept
                                         ].set(True)
    allowed = jnp.repeat(in_kept, X // n, axis=-1)
    top = jnp.argsort(-jnp.where(allowed, choice, -jnp.inf), axis=-1
                      )[:, :s["k"]]
    w = jnp.take_along_axis(scores, top, axis=-1)
    return top, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * s["route_scale"]


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


def layer(h, w, bias, s, kind, quant=None):
    """One layer: (h', the router's choices or None)."""
    mixer = kda_mixer if kind == KDA else latent_attention
    a = h + mixer(_rms_norm(h, w["attn_norm"], s["eps"]), w, s, quant)
    f, top = feed_forward(_rms_norm(a, w["mlp_norm"], s["eps"]), w, bias, s,
                          quant)
    return a + f, top


def _stack(weights, bias, s):
    """(one layer's weights, its bias or None, its kind) down the stack."""
    sparse = 0
    for i, (kind, w) in enumerate(zip(s["kinds"], weights["layers"])):
        if i < s["Ld"]:
            yield w, None, kind
        else:
            yield w, bias[sparse], kind
            sparse += 1


def _shift(tokens):
    return jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)


def _hidden(weights, bias, tokens, s, quant):
    """The stack's result before the final norm, whole: a test's sizes."""
    h = weights["embed"][tokens]
    for w, b, kind in _stack(weights, bias, s):
        h, _ = layer(h, w, b, s, kind, quant)
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


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes (the items of
    ``s``): a layer forward and a layer backward (one program a kind of
    mixer and of feed-forward), the head."""
    s = dict(sizes)
    forward = jax.jit(
        lambda h, w, b, kind: layer(h, _widen(w), b, s, kind, quant),
        static_argnames="kind")

    def backward(h, w, b, gh, kind):
        _, vjp = jax.vjp(lambda h, w: layer(h, w, b, s, kind, quant)[0], h,
                         _widen(w))
        gh, gw = vjp(gh)
        return gh, {n: gw[n] for n in norm_names(kind)}

    @jax.jit
    def head(h, final_norm, lm_head, tokens, mask):
        value, vjp = jax.vjp(
            lambda h, n, m: _nll(h, n, m, _shift(tokens), mask.astype(F32), s,
                                 quant),
            h, final_norm.astype(F32), lm_head.astype(F32))
        gh, g_final, _ = vjp(jnp.ones((), F32))
        return value, gh, g_final

    return forward, jax.jit(backward, static_argnames="kind"), head


def loss_norm_grads_and_routing(weights, bias, tokens, mask, s, quant=None):
    """(loss over the masked positions of tokens [B, S], its gradient in the
    weights of every RMSNorm, the routers' choices [expert layers, B*S, k]).
    ``bias`` [expert layers, X].

    The gradient tree: ``final_norm [E]`` and ``layers``, a list with each
    layer's norms (``norm_names``).  The walk is a Python loop over jitted
    pieces, one ``jax.vjp`` of a layer at a time in reverse, so that a row
    of 8,192 tokens fits beside the weights: call it outside ``jax.jit``."""
    forward, backward, head = _programs(tuple(sorted(s.items())), quant)
    h = weights["embed"].astype(F32)[tokens]
    stack = list(_stack(weights, bias, s))
    hs, tops = [], []
    for w, b, kind in stack:
        hs.append(h)
        h, top = forward(h, w, b, kind)
        if top is not None:
            tops.append(top)
    value, gh, g_final = head(h, weights["final_norm"], weights["lm_head"],
                              tokens, mask)
    grads = []
    for h, (w, b, kind) in reversed(list(zip(hs, stack))):
        gh, g = backward(h, w, b, gh, kind)
        grads.append(g)
    grads.reverse()
    return value, {"final_norm": g_final, "layers": grads}, jnp.stack(tops)


def routing(weights, bias, tokens, s, quant=None):
    """The routers' choices [expert layers, B*S, k] for tokens [B, S], row
    after row through the walk's forward programs: no loss, no gradient.
    Call it outside ``jax.jit``."""
    forward, _, _ = _programs(tuple(sorted(s.items())), quant)
    stack = list(_stack(weights, bias, s))
    embed = weights["embed"].astype(F32)
    rows = []
    for row in tokens:
        h, tops = embed[row[None]], []
        for w, b, kind in stack:
            h, top = forward(h, w, b, kind)
            if top is not None:
                tops.append(top)
        rows.append(jnp.stack(tops))
    return jnp.concatenate(rows, axis=1)
