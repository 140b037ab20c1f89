"""The plain reference of the ``nemotron_h`` stack (Nemotron-3-Nano-30B-A3B):
forward, loss, the gradient in every judged weight and the routers' choices,
in float32 ``jax.numpy`` at the highest matmul precision.  Nothing here comes
from ``ray_tpu``; the elementary pieces (a linear layer, an RMSNorm, softmax
attention a head at a time, the sigmoid router, the head's loss) are
``reference.py``'s and ``reference_afmoe.py``'s.

The equations (``config.json``'s keys; what no key settles is marked
(assumed) and listed in ``configs/nemotron-3-nano-30b-a3b.json`` under
``assumed``).  C = hidden, N(x; g) = x / rms(x) g with eps
``layer_norm_epsilon``.  Every layer is ``x <- x + F(N(x; g_l))`` with F one
of three, chosen by the layer's letter in ``hybrid_override_pattern``.

- ``M``, a Mamba-2 mixer (H = ``mamba_num_heads``, P = ``mamba_head_dim``,
  d = H P, G = ``n_groups``, N = ``ssm_state_size``, K = ``conv_kernel``):
  ``[z ; c ; delta] = u W_in`` of widths d, d + 2 G N, H, no bias.  The
  convolution, causal and depthwise, as K shifted products: ``c'[t] =
  silu(b + sum_j w[j] c[t - (K - 1) + j])``, ``c[s] = 0`` before the row's
  start.  ``c' = [X ; B ; C]``; head h reads group ``h // (H / G)``.
  ``dt = softplus(delta + dt_bias)``, not clamped (assumed); ``A =
  -exp(A_log)``.  **The recurrence itself, token by token** (a ``lax.scan``
  over t, no chunks), every row from a zero state:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t X_t (x) B_t``; ``y_t = S_t C_t + D X_t``.
  Gate, then norm (assumed: ``norm_before_gate`` false): ``v = y silu(z)``,
  each of the G groups of d / G channels divided by its own rms, times g_m.
  ``F = v W_out``.
- ``E``, an expert layer: ``s = sigmoid(u W_r)``; the top k of s + bias; ``w =
  s[top] / (sum + 1e-20) * routed_scaling_factor``; ``Expert_e(u) = relu(u
  W_up,e)^2 W_down,e`` with no gate, the shared expert the same form; ``F =
  Shared(u) + sum over the top that are held of w_e Expert_e(u)``: a dense
  loop over the experts it is told it holds.
- ``*``, attention: q of ``num_attention_heads`` heads, k and v of
  ``num_key_value_heads``, no bias, no positional term (assumed), causal
  softmax of ``head_dim^-1/2 q.k``, a key head for H / Hkv query heads.
- End: the masked mean cross-entropy of ``N(x_L; g_f) W_head`` against token
  t + 1.

DEPARTURES from the published description: the recurrence is cut into blocks
of ``SCAN_BLOCK`` tokens that are recomputed in the backward pass (a block's
states are kept, not a row's: 8,192 states of 2 MB do not fit a chip); the
arithmetic is the same.  The convolution's weight lies [K, channels] where
the published tensor is [channels, 1, K].

``quant="int8"`` is the control: the inputs of every linear layer (the
routers' too) rounded to 8-bit integers; the recurrence stays float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _attention, _linear, _rms_norm,
                                 relative_distance)  # noqa: F401
from benchmark.reference_afmoe import (_nll, _widen, route,
                                       routing_mismatch_share)  # noqa: F401

#: a mixer's judged weights beside its norms: their gradient exists only
#: through the recurrence's and the convolution's backward
SSM = ("A_log", "dt_bias", "D", "conv_w", "conv_b")
#: the judged weights of a layer, by its letter
JUDGED = {"M": ("norm", "gate_norm") + SSM, "E": ("norm",), "*": ("norm",)}

#: tokens of the recurrence kept at a time in the backward pass
SCAN_BLOCK = 128


def convolution(c, w, b):
    """c [B, S, Ch], w [K, Ch], b [Ch]: K shifted products, then silu."""
    K, S = w.shape[0], c.shape[1]
    acc = b
    for j in range(K):
        back = K - 1 - j                # tap j reads the token ``back`` ago
        shifted = jnp.pad(c, ((0, 0), (back, 0), (0, 0)))[:, :S]
        acc = acc + shifted * w[j]
    return jax.nn.silu(acc)


def recurrence(X, dt, A, B, C, D):
    """X [Bt, S, G, R, P], dt [Bt, S, G, R], A / D [G, R], B / C [Bt, S, G, N]
    (a group's R heads share them) -> y [Bt, S, G, R, P].  One token at a
    time; products of two numbers and sums, no matrix product."""
    Bt, S, G, R, P = X.shape
    N = B.shape[-1]

    def token(state, t):
        x, d, b, c = t
        state = jnp.exp(d * A)[..., None, None] * state \
            + (d[..., None] * x)[..., None] * b[:, :, None, None, :]
        y = jnp.sum(state * c[:, :, None, None, :], axis=-1) \
            + D[..., None] * x
        return state, y

    @jax.checkpoint
    def block(state, ts):
        return jax.lax.scan(token, state, ts)

    pad = -S % SCAN_BLOCK               # dt 0: the state stays, y unused
    blocks = lambda a: jnp.pad(
        jnp.moveaxis(a, 1, 0), ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    ).reshape((-1, SCAN_BLOCK, Bt) + a.shape[2:])
    _, y = jax.lax.scan(block, jnp.zeros((Bt, G, R, P, N), F32),
                        tuple(blocks(a) for a in (X, dt, B, C)))
    return jnp.moveaxis(y.reshape((-1, Bt, G, R, P))[:S], 0, 1)


def mixer(h, w, s, quant=None):
    """F of a Mamba-2 layer on the normed stream h [B, S, C]."""
    Bt, S, _ = h.shape
    H, P, G, N = s["Hm"], s["P"], s["G"], s["N"]
    d, R = H * P, H // G
    zcd = _linear(h, w["w_in"], quant)
    z, c, delta = jnp.split(zcd, (d, 2 * d + 2 * G * N), axis=-1)
    c = convolution(c, w["conv_w"], w["conv_b"])
    X, B, C = jnp.split(c, (d, d + G * N), axis=-1)
    y = recurrence(
        X.reshape(Bt, S, G, R, P),
        jax.nn.softplus(delta + w["dt_bias"]).reshape(Bt, S, G, R),
        -jnp.exp(w["A_log"]).reshape(G, R), B.reshape(Bt, S, G, N),
        C.reshape(Bt, S, G, N), w["D"].reshape(G, R))
    v = (y.reshape(Bt, S, d) * jax.nn.silu(z)).reshape(Bt, S, G, d // G)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + s["eps"])
    return _linear(v.reshape(Bt, S, d) * w["gate_norm"], w["w_out"], quant)


def _relu2(x, w_up, w_down, quant):
    return _linear(jnp.square(jax.nn.relu(_linear(x, w_up, quant))), w_down,
                   quant)


def held_experts(x, top, w, w_up, w_down, held_start, quant=None):
    """sum over the held experts e of coef_e[t] * Expert_e(x[t]), coef_e[t]
    the weight token t gave e (0 if it did not choose it).  x [T, C]."""
    def one(acc, expert):
        e, wu, wd = expert
        coef = jnp.sum(jnp.where(top == held_start + e, w, 0.0), axis=-1)
        return acc + coef[:, None] * _relu2(x, wu, wd, quant), None

    out, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                          (jnp.arange(w_up.shape[0]), w_up, w_down))
    return out


def experts(h, w, bias, s, quant=None):
    """(F of an expert layer, the router's choices [T, k])."""
    Bt, S, C = h.shape
    flat = h.reshape(Bt * S, C)
    top, wts = route(flat, w["router"], bias, s, quant)
    return (_relu2(h, w["shared_up"], w["shared_down"], quant)
            + held_experts(flat, top, wts, w["w_up"], w["w_down"],
                           s["held_start"], quant).reshape(Bt, S, C), top)


def attention(h, w, s, quant=None):
    Bt, S, C = h.shape
    H, K, D = s["H"], s["Hkv"], s["D"]
    q = _linear(h, w["wq"].reshape(C, H * D), quant).reshape(Bt, S, H, D)
    k = _linear(h, w["wk"].reshape(C, K * D), quant).reshape(Bt, S, K, D)
    v = _linear(h, w["wv"].reshape(C, K * D), quant).reshape(Bt, S, K, D)
    k, v = (jnp.repeat(t, H // K, axis=2) for t in (k, v))
    return _linear(_attention(q, k, v).reshape(Bt, S, H * D),
                   w["wo"].reshape(H * D, C), quant)


def layer(x, w, bias, s, kind, quant=None):
    """One layer: (x', the router's choices [T, k], None unless ``E``)."""
    h = _rms_norm(x, w["norm"], s["eps"])
    if kind == "M":
        return x + mixer(h, w, s, quant), None
    if kind == "E":
        f, top = experts(h, w, bias, s, quant)
        return x + f, top
    if kind == "*":
        return x + attention(h, w, s, quant), None
    raise ValueError(f"unknown layer letter {kind!r}")


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes: a layer forward
    and backward for each letter, the head."""
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
    e = 0
    for kind, w in zip(s["kinds"], weights["layers"]):
        yield w, (bias[e] if kind == "E" else None), kind
        e += kind == "E"


def loss_judged_grads_and_routing(weights, bias, tokens, mask, s, quant=None):
    """(loss over the masked positions of tokens [B, S], its gradient in
    every judged weight, the routers' choices [expert layers, B*S, k]).

    The gradient tree: ``final_norm`` and ``layers``, a list with the names
    of ``JUDGED`` of each layer's letter.  The walk is a Python loop over
    jitted pieces, one ``jax.vjp`` of a layer at a time in reverse: call it
    outside ``jax.jit``."""
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
