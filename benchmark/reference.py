"""The plain reference: the block's forward, loss and gradient norm in
float32 ``jax.numpy`` at the highest matmul precision.

Nothing here comes from ``ray_tpu``: no kernel, no cache, no batching, no
remat policy of the program's.  It follows the published description of the
Llama-shaped block that Yi and Mistral share: pre-norm RMSNorm, rotary
embedding on split halves, grouped-query causal attention with a float32
softmax, SwiGLU, untied output head.  Weights are the benchmark's own
(``weights.make``), stored in bfloat16 and widened exactly.

``quant="int8"`` is the control: the same mathematics with the inputs of
every linear layer rounded to 8-bit integers (a scale per row, as a W8A8
deployment would), the step below bfloat16 that would tempt a later PR.
``correct`` has to call it wrong (tests/test_control.py, control.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _int8(x, axis):
    """Round to 255 levels with one scale along ``axis``; the gradient
    passes straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _linear(x, w, quant):
    """x [..., K] @ w [K, N]."""
    if quant == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, D]: rotate the two halves of each head by position."""
    S, D = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal softmax attention, q/k/v [B, S, H, D] -> [B, S, H, D].  One
    head at a time, and recomputed in the backward pass, so that only one
    head's [S, S] scores are ever alive."""
    S, D = q.shape[1], q.shape[3]
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv                                       # [B, S, D]
        scores = jnp.einsum("bqd,bkd->bqk", q, k,
                            precision="highest") * D ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, v, precision="highest")

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 2)


def block(x, w, s, quant=None):
    """One decoder block.  x [B, S, E] float32; w one layer's weights."""
    B, S, E = x.shape
    H, K, D = s["H"], s["Hkv"], s["D"]
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    q = _linear(h, w["wq"].reshape(E, H * D), quant).reshape(B, S, H, D)
    k = _linear(h, w["wk"].reshape(E, K * D), quant).reshape(B, S, K, D)
    v = _linear(h, w["wv"].reshape(E, K * D), quant).reshape(B, S, K, D)
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    k, v = (jnp.repeat(t, H // K, axis=2) for t in (k, v))
    attn = _attention(q, k, v)
    x = x + _linear(attn.reshape(B, S, H * D), w["wo"].reshape(H * D, E),
                    quant)
    h = _rms_norm(x, w["mlp_norm"], s["eps"])
    gate = _linear(h, w["w_gate"], quant)
    up = _linear(h, w["w_up"], quant)
    return x + _linear(jax.nn.silu(gate) * up, w["w_down"], quant)


def _widen(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def logits(weights, tokens, s, quant=None):
    """tokens [B, S] -> logits [B, S, V] float32."""
    x = weights["embed"].astype(F32)[tokens]

    def body(x, w):
        return block(x, _widen(w), s, quant), None

    x, _ = jax.lax.scan(body, x, weights["blocks"])
    x = _rms_norm(x, weights["final_norm"].astype(F32), s["eps"])
    return _linear(x, weights["lm_head"].astype(F32), quant)


def _nll(x, final_norm, lm_head, targets, mask, s, quant):
    x = _rms_norm(x, final_norm, s["eps"])
    lg = _linear(x, lm_head, quant)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, targets[..., None], -1)[..., 0]
    return jnp.sum(nll * mask) / jnp.sum(mask)


def loss_and_norm_grads(weights, tokens, mask, s, quant=None):
    """Next-token loss over the masked positions of tokens [B, S] (position
    t predicts token t+1), and its gradient in the weights of every RMSNorm:
    ``final_norm [E]``, ``attn_norm`` and ``mlp_norm [L, E]``.

    Those few weights see the whole backward pass (a layer's ``attn_norm``
    feeds q, k and v), and each of their gradients is a sum over tokens
    whose relative error is that of the arithmetic, so their distance from
    the program's separates one precision from another where the loss and
    the gradient's overall norm, both averages, do not.  The backward pass
    walks the layers in reverse, one ``jax.vjp`` of ``block`` at a time: a
    whole float32 gradient of these models does not fit beside the weights.
    """
    targets = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
    mask = mask.astype(F32)
    x0 = weights["embed"].astype(F32)[tokens]

    def fwd(x, w):
        return block(x, _widen(w), s, quant), x

    xL, xs = jax.lax.scan(fwd, x0, weights["blocks"])
    loss, head_vjp = jax.vjp(
        lambda x, n, h: _nll(x, n, h, targets, mask, s, quant),
        xL, weights["final_norm"].astype(F32), weights["lm_head"].astype(F32))
    gx, g_final, _ = head_vjp(jnp.ones((), F32))

    def bwd(gx, xw):
        x, w = xw
        _, vjp = jax.vjp(lambda x, w: block(x, w, s, quant), x, _widen(w))
        gx, gw = vjp(gx)
        return gx, (gw["attn_norm"], gw["mlp_norm"])

    _, (g_attn, g_mlp) = jax.lax.scan(bwd, gx, (xs, weights["blocks"]),
                                      reverse=True)
    return loss, {"final_norm": g_final, "attn_norm": g_attn,
                  "mlp_norm": g_mlp}


def relative_distance(got, want):
    """||got - want|| / ||want|| over two matching trees, in float32."""
    diff = sum(jnp.sum((a.astype(F32) - b.astype(F32)) ** 2)
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    norm = sum(jnp.sum(b.astype(F32) ** 2) for b in jax.tree.leaves(want))
    return jnp.sqrt(diff / norm)


def served_margins(weights, seqs, scored, s, quant=None):
    """How far each served token trails the reference's best.

    seqs [N, W] holds prompt + answer (zero padded); scored [N, W-1] marks
    the positions t whose next token seqs[:, t+1] the system chose.  Returns
    per position ``max(logits[t]) - logits[t, seqs[t+1]]`` (0 where the
    reference agrees) and, for the control, the same margin for the token
    that the ``quant`` forward would have chosen there.
    """
    def one(row):
        seq, = row
        lg = logits(weights, seq[None], s)[0, :-1]
        best = lg.max(-1)
        served = best - jnp.take_along_axis(lg, seq[1:, None], -1)[:, 0]
        if quant is None:
            return served, served
        pick = logits(weights, seq[None], s, quant)[0, :-1].argmax(-1)
        return served, best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]

    served, control = jax.lax.map(one, (seqs,))
    return jnp.where(scored, served, 0.0), jnp.where(scored, control, 0.0)
