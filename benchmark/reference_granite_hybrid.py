"""The plain reference of the ``granitemoehybrid`` stack without experts
(Granite-4.0-H-Micro) on packed rows: forward, loss and the gradient in every
judged weight, in float32 ``jax.numpy`` at the highest matmul precision.
Nothing here comes from ``ray_tpu``: no kernel, no chunk of the scan, no
segment-id operand; the elementary pieces (a linear layer, an RMSNorm) are
``reference.py``'s.

The equations (``config.json``'s keys; what no key settles is marked
(assumed) and listed in ``configs/granite-4.0-h-micro.json`` under
``assumed``).  C = hidden, N(x; g) = x / rms(x) g with eps ``rms_norm_eps``,
r = ``residual_multiplier``.  ``doc[t]`` counts the documents of a row: a
token whose segment id is not its predecessor's starts one.

- Start: ``x_0 = embedding_multiplier E[token]``.
- Every layer: ``x <- x + r Mix(N(x; g_1))``, then ``x <- x + r W_out
  (silu(g) * u)`` with ``[g ; u] = N(x; g_2) W_in`` (``W_in`` held as its
  two halves, ``w_gate`` and ``w_up``).
- ``Mix`` of a ``mamba`` layer, a Mamba-2 mixer (H = ``mamba_n_heads``, P =
  ``mamba_d_head``, d = H P, G = ``mamba_n_groups`` = 1, N =
  ``mamba_d_state``, K = ``mamba_d_conv``): ``[z ; c ; delta] = u W`` of
  widths d, d + 2 G N, H, no bias.  The convolution, causal and depthwise,
  as K shifted, masked products: ``c'[t] = silu(b + sum_j w[j] c[t - (K - 1)
  + j])`` with ``c[s] = 0`` where s lies before the row's start **or in
  another document than t**.  ``c' = [X ; B ; C]``: all H heads read the
  one B and C.  ``dt = softplus(delta + dt_bias)``, not clamped (assumed);
  ``A = -exp(A_log)``.  **The recurrence itself, token by token** (a
  ``lax.scan`` over t, no chunks): ``S_t = exp(dt_t A) S_{t-1} + dt_t X_t
  (x) B_t`` with ``S_{t-1} = 0`` **where t starts a document** or the row;
  ``y_t = S_t C_t + D X_t``.  Gate, then norm (assumed): ``v = y silu(z)``
  divided by its rms over all d channels (one group), times g_m.  ``Mix = v
  W_out``.
- ``Mix`` of an ``attention`` layer: q of ``num_attention_heads`` heads, k
  and v of ``num_key_value_heads``, no bias, no positional term
  (``position_embedding_type`` nope), the softmax of
  ``attention_multiplier q.k`` over the keys s with ``s <= t`` **and
  doc[s] = doc[t]**, a key head for H / Hkv query heads.
- End: the masked mean cross-entropy of ``N(x_L; g_f) E^T /
  logits_scaling`` against token t + 1.

DEPARTURES from the published description: the recurrence is cut into blocks
of ``SCAN_BLOCK`` tokens that are recomputed in the backward pass, attention
is taken ``ATTN_BLOCK`` queries of a head at a time, the feed-forward
``FF_BLOCK`` tokens and the head's loss ``HEAD_BLOCK`` positions at a time,
each recomputed in the backward pass (a row of 32,768 holds 32,768 states of
2 MB, 4 GB of scores a head, 1 GB a feed-forward array and 13 GB of logits);
the arithmetic is the same.  The convolution's weight lies [K,
channels] where the published tensor is [channels, 1, K].

``quant="int8"`` is the control: the inputs of every linear layer (the tied
head's too) rounded to 8-bit integers; the recurrence stays float32.  With
``segment_ids`` None a row is one document.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _linear, _rms_norm,
                                 relative_distance)  # noqa: F401

#: a mixer's judged weights beside its norms: their gradient exists only
#: through the recurrence's and the convolution's backward
SSM = ("A_log", "dt_bias", "D", "conv_w", "conv_b")
#: the judged weights of a layer, by its kind's letter
JUDGED = {"M": ("norm", "gate_norm", "mlp_norm") + SSM,
          "*": ("norm", "mlp_norm")}

#: tokens of the recurrence kept at a time in the backward pass
SCAN_BLOCK = 128
#: queries of a head whose scores are alive at a time
ATTN_BLOCK = 1024
#: positions whose logits are alive at a time
HEAD_BLOCK = 2048
#: tokens whose feed-forward channels are alive at a time
FF_BLOCK = 4096


def documents(segment_ids, shape):
    """int32 [B, S]: the documents of each row counted from 0."""
    if segment_ids is None:
        return jnp.zeros(shape, jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros_like(segment_ids[:, :1], dtype=bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
    return jnp.cumsum(starts.astype(jnp.int32), axis=1)


def convolution(c, w, b, doc):
    """c [B, S, Ch], w [K, Ch], b [Ch]: K shifted products, each masked to
    the token's own document, then silu."""
    K, S = w.shape[0], c.shape[1]
    acc = b
    for j in range(K):
        back = K - 1 - j                # tap j reads the token ``back`` ago
        shifted = jnp.pad(c, ((0, 0), (back, 0), (0, 0)))[:, :S]
        came_from = jnp.pad(doc, ((0, 0), (back, 0)),
                            constant_values=-1)[:, :S]
        acc = acc + jnp.where((came_from == doc)[..., None], shifted, 0.0) \
            * w[j]
    return jax.nn.silu(acc)


def recurrence(X, dt, A, B, C, D, doc):
    """X [Bt, S, H, P], dt [Bt, S, H], A / D [H], B / C [Bt, S, N] (every
    head reads them), doc [Bt, S] -> y [Bt, S, H, P].  One token at a time;
    the state is zeroed where a document starts."""
    Bt, S, H, P = X.shape
    N = B.shape[-1]
    starts = jnp.concatenate([jnp.zeros((Bt, 1), bool),
                              doc[:, 1:] != doc[:, :-1]], axis=1)

    def token(state, t):
        x, d, b, c, new = t
        state = jnp.where(new[:, None, None, None], 0.0, state)
        state = jnp.exp(d * A)[..., None, None] * state \
            + (d[..., None] * x)[..., None] * b[:, None, None, :]
        y = jnp.sum(state * c[:, None, None, :], axis=-1) + D[:, None] * x
        return state, y

    @jax.checkpoint
    def block(state, ts):
        return jax.lax.scan(token, state, ts)

    pad = -S % SCAN_BLOCK               # dt 0: the state stays, y unused
    blocks = lambda a: jnp.pad(
        jnp.moveaxis(a, 1, 0), ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    ).reshape((-1, SCAN_BLOCK, Bt) + a.shape[2:])
    _, y = jax.lax.scan(block, jnp.zeros((Bt, H, P, N), F32),
                        tuple(blocks(a) for a in (X, dt, B, C, starts)))
    return jnp.moveaxis(y.reshape((-1, Bt, H, P))[:S], 0, 1)


def mixer(h, w, s, doc, quant=None):
    """Mix of a ``mamba`` layer on the normed stream h [B, S, C]."""
    Bt, S, _ = h.shape
    H, P, N = s["Hm"], s["P"], s["N"]
    d = H * P
    zcd = _linear(h, w["w_in"], quant)
    z, c, delta = jnp.split(zcd, (d, 2 * d + 2 * N), axis=-1)
    c = convolution(c, w["conv_w"], w["conv_b"], doc)
    X, B, C = jnp.split(c, (d, d + N), axis=-1)
    y = recurrence(X.reshape(Bt, S, H, P),
                   jax.nn.softplus(delta + w["dt_bias"]),
                   -jnp.exp(w["A_log"]), B, C, w["D"], doc)
    v = y.reshape(Bt, S, d) * jax.nn.silu(z)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + s["eps"])
    return _linear(v * w["gate_norm"], w["w_out"], quant)


def softmax_attention(q, k, v, doc, scale):
    """q, k, v [B, S, H, D] (keys laid out a query head) -> [B, S, H, D]:
    softmax over the keys no later than the query and of its document.  A
    head at a time and ``ATTN_BLOCK`` queries of it at a time, recomputed
    in the backward pass."""
    Bt, S, _, _ = q.shape
    blk = math.gcd(S, ATTN_BLOCK)
    at = jnp.arange(S)

    def head(qkv):
        q, k, v = qkv                                       # [B, S, D]

        @jax.checkpoint
        def queries(part):
            qb, tb, db = part           # [B, blk, D], [blk], [B, blk]
            scores = jnp.einsum("bqd,bkd->bqk", qb, k,
                                precision="highest") * scale
            seen = (at[None, None, :] <= tb[None, :, None]) \
                & (doc[:, None, :] == db[:, :, None])
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", probs, v, precision="highest")

        out = jax.lax.map(queries, (
            jnp.moveaxis(q.reshape(Bt, S // blk, blk, -1), 1, 0),
            at.reshape(S // blk, blk),
            jnp.moveaxis(doc.reshape(Bt, S // blk, blk), 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(Bt, S, -1)

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 2)


def attention(h, w, s, doc, quant=None):
    """Mix of an ``attention`` layer."""
    Bt, S, C = h.shape
    H, K, D = s["H"], s["Hkv"], s["D"]
    q = _linear(h, w["wq"].reshape(C, H * D), quant).reshape(Bt, S, H, D)
    k = _linear(h, w["wk"].reshape(C, K * D), quant).reshape(Bt, S, K, D)
    v = _linear(h, w["wv"].reshape(C, K * D), quant).reshape(Bt, S, K, D)
    k, v = (jnp.repeat(t, H // K, axis=2) for t in (k, v))
    o = softmax_attention(q, k, v, doc, s["attention_multiplier"])
    return _linear(o.reshape(Bt, S, H * D), w["wo"].reshape(H * D, C), quant)


def feed_forward(h, w, quant=None):
    """SwiGLU of h [B, S, C], ``FF_BLOCK`` tokens at a time, recomputed in
    the backward pass (it reads a token alone)."""
    Bt, S, C = h.shape
    blk = math.gcd(S, FF_BLOCK)

    @jax.checkpoint
    def tokens(hb):
        return _linear(jax.nn.silu(_linear(hb, w["w_gate"], quant))
                       * _linear(hb, w["w_up"], quant), w["w_down"], quant)

    out = jax.lax.map(tokens, jnp.moveaxis(
        h.reshape(Bt, S // blk, blk, C), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(Bt, S, C)


def layer(x, w, s, kind, doc, quant=None):
    """One layer: the mixer its letter names (``M`` / ``*``), then the
    feed-forward, each times the residual multiplier."""
    r = s["residual_multiplier"]
    h = _rms_norm(x, w["norm"], s["eps"])
    if kind == "M":
        x = x + r * mixer(h, w, s, doc, quant)
    elif kind == "*":
        x = x + r * attention(h, w, s, doc, quant)
    else:
        raise ValueError(f"unknown layer letter {kind!r}")
    return x + r * feed_forward(_rms_norm(x, w["mlp_norm"], s["eps"]), w,
                                quant)


def _nll(x, final_norm, embed, targets, mask, s, quant):
    """The masked mean loss under the tied, scaled head, ``HEAD_BLOCK``
    positions' logits at a time."""
    Bt, S, C = x.shape
    x = _rms_norm(x, final_norm, s["eps"])
    blk = math.gcd(S, HEAD_BLOCK)

    @jax.checkpoint
    def part(xtm):
        xb, tb, mb = xtm
        lg = _linear(xb, embed.T, quant) / s["logits_scaling"]
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tb[..., None], -1)[..., 0]
        return jnp.sum(nll * mb)

    cut = lambda a: jnp.moveaxis(
        a.reshape((Bt, S // blk, blk) + a.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(part, (cut(x), cut(targets), cut(mask)))) \
        / jnp.sum(mask)


def _widen(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes: a layer forward
    and backward for each letter, the head."""
    s = dict(sizes)
    forward = jax.jit(lambda x, w, doc, kind: layer(x, _widen(w), s, kind,
                                                    doc, quant),
                      static_argnames="kind")

    def backward(x, w, doc, gx, kind):
        _, vjp = jax.vjp(lambda x, w: layer(x, w, s, kind, doc, quant), x,
                         _widen(w))
        gx, gw = vjp(gx)
        return gx, {n: gw[n] for n in JUDGED[kind]}

    @jax.jit
    def head(x, final_norm, embed, tokens, mask):
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
        loss, vjp = jax.vjp(
            lambda x, n: _nll(x, n, embed.astype(F32), targets,
                              mask.astype(F32), s, quant),
            x, final_norm.astype(F32))
        gx, g_final = vjp(jnp.ones((), F32))
        return loss, gx, g_final

    return forward, jax.jit(backward, static_argnames="kind"), head


def loss_and_judged_grads(weights, tokens, mask, segment_ids, s, quant=None):
    """(loss over the masked positions of tokens [B, S], its gradient in
    every judged weight).  The gradient tree: ``final_norm`` and ``layers``,
    a list with the names of ``JUDGED`` of each layer's letter.  The walk is
    a Python loop over jitted pieces, one ``jax.vjp`` of a layer at a time
    in reverse: call it outside ``jax.jit``."""
    forward, backward, head = _programs(tuple(sorted(s.items())), quant)
    doc = documents(segment_ids, tokens.shape)
    x = weights["embed"].astype(F32)[tokens] * s["embedding_multiplier"]
    stack = list(zip(weights["layers"], s["kinds"]))
    xs = []
    for w, kind in stack:
        xs.append(x)
        x = forward(x, w, doc, kind)
    loss, gx, g_final = head(x, weights["final_norm"], weights["embed"],
                             tokens, mask)
    grads = []
    for x, (w, kind) in reversed(list(zip(xs, stack))):
        gx, g = backward(x, w, doc, gx, kind)
        grads.append(g)
    grads.reverse()
    return loss, {"final_norm": g_final, "layers": grads}


def logits(weights, tokens, segment_ids, s, quant=None):
    """tokens [B, S] -> logits [B, S, V] float32 (small sizes: the tests)."""
    doc = documents(segment_ids, tokens.shape)
    x = weights["embed"].astype(F32)[tokens] * s["embedding_multiplier"]
    for w, kind in zip(weights["layers"], s["kinds"]):
        x = layer(x, _widen(w), s, kind, doc, quant)
    x = _rms_norm(x, weights["final_norm"].astype(F32), s["eps"])
    return _linear(x, weights["embed"].astype(F32).T, quant) \
        / s["logits_scaling"]
