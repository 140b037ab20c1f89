"""The plain reference of the ``evabyte`` decoder (EvaByte 6.5B): forward,
the eight-head loss, each head's own loss, and the gradient in every RMSNorm
weight and in the two pooling vectors of every layer, in float32
``jax.numpy`` at the highest matmul precision.  Nothing here comes from
``ray_tpu``; the elementary pieces (RMSNorm, rotary embedding on split
halves, a linear layer with its int8 control, the distance) are
``reference.py``'s.

The equations (sizes from EvaByte's ``config.json``; the lines marked
(assumed) are from the family's modelling code as the issue for this
configuration states them, and are listed in ``configs/evabyte-6.5b.json``
under ``assumed``).  E = 4096, H = 32 heads of D = 128, no grouping,
s = D^-1/2, window W = 2048, chunk C = 16, eps = 1e-5.

    N(x; g) = x / rms(x) * (1 + g)            # norm_add_unit_offset
    x = Embed[tokens]                         # float32 stream (fp32_skip_add)
    for l = 1..L:
        h = N(x; g1);  q = RoPE(h Wq), k = RoPE(h Wk), v = h Wv   # no bias
        per head, per chunk c of C positions:                     (assumed)
            a_i = softmax_{i in c}(s k_i . mu_h);   k~_c = sum_i a_i k_i
            b_i = softmax_{i in c}(s k_i . phi_h);  v~_c = sum_i b_i v_i
        position t, window w = t // W:
            local  L_t = { j : W w <= j <= t }
            remote R_t = { c : c < (W / C) w }   # every chunk of every
                                                 # earlier window
            o_t = softmax over L_t and R_t together of (s q_t . k_j,
                  s q_t . k~_c), applied to (v_j, v~_c)
        x = x + o Wo
        x = x + W_down( silu(W_gate N(x; g2)) * (W_up N(x; g2)) )
    y = N(x; g_f)
    head j = 1..J: logits_j = y W_j (float32); position t predicts byte t + j
    loss = (1 / J) sum_j mean over { t : t + j <= S - 1, mask_t } of
           CE(logits_j[t], token[t + j])                          (assumed)

Departures: none in the mathematics.  A window's scores are formed at once
against ALL the row's summaries with the later ones masked (one window's
[H, W, S / C + W] float32 at a time, recomputed in the backward pass), and
the gated MLP takes 4,096 positions at a time (``_by_rows``), so that a row
of 32,768 fits.  For the size of a check row the walk below runs a
layer at a time (``loss_and_judged_grads``); the whole function in one piece
is ``loss_and_report``, and the tests hold the two together.

``quant="int8"`` is the control, as in ``reference.py``: the inputs of every
linear layer (the eight heads' too) rounded to 8-bit integers.  The pooling's
products with ``mu`` and ``phi`` and the attention's own are no linear
layers and stay in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _linear, _rms_norm, _rope,
                                 relative_distance)  # noqa: F401

#: a layer's judged weights: its two norms and its two pooling vectors
JUDGED = ("attn_norm", "mlp_norm", "eva_mu", "eva_phi")


def norm(x, g, eps):
    """RMSNorm with a unit offset."""
    return _rms_norm(x, 1.0 + g, eps)


def summaries(k, v, mu, phi, chunk):
    """k, v [B, S, H, D]; mu, phi [H, D] -> (k~, v~) [B, S / chunk, H, D]."""
    B, S, H, D = k.shape
    k5 = k.reshape(B, S // chunk, chunk, H, D)
    v5 = v.reshape(B, S // chunk, chunk, H, D)

    def pooled(vec, x5):
        logits = jnp.einsum("bnchd,hd->bnch", k5, vec,
                            precision="highest") * D ** -0.5
        return jnp.einsum("bnch,bnchd->bnhd", jax.nn.softmax(logits, axis=2),
                          x5, precision="highest")

    return pooled(mu, k5), pooled(phi, v5)


def eva_attention(q, k, v, k_sum, v_sum, window, chunk):
    """q, k, v [B, S, H, D]; k_sum, v_sum [B, S / chunk, H, D] ->
    [B, S, H, D].  One window at a time, recomputed in the backward pass."""
    B, S, H, D = q.shape
    window = min(window, S)
    n, per = S // window, window // chunk
    if n * window != S or per * chunk != window:
        raise ValueError(f"row {S}, window {window}, chunk {chunk}")
    causal = jnp.tril(jnp.ones((window, window), bool))
    chunks = jnp.arange(k_sum.shape[1])

    @jax.checkpoint
    def one(args):
        w, qw, kw, vw = args                               # [B, W, H, D]
        local = jnp.einsum("bqhd,bkhd->bhqk", qw, kw,
                           precision="highest") * D ** -0.5
        remote = jnp.einsum("bqhd,bchd->bhqc", qw, k_sum,
                            precision="highest") * D ** -0.5
        scores = jnp.concatenate(
            [jnp.where(chunks < w * per, remote, -jnp.inf),
             jnp.where(causal, local, -jnp.inf)], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqc,bchd->bqhd", probs[..., :chunks.size], v_sum,
                          precision="highest") \
            + jnp.einsum("bhqk,bkhd->bqhd", probs[..., chunks.size:], vw,
                         precision="highest")

    split = lambda a: jnp.moveaxis(a.reshape(B, n, window, H, D), 1, 0)
    out = jax.lax.map(one, (jnp.arange(n), split(q), split(k), split(v)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, D)


def layer(x, w, s, quant=None):
    """One layer.  x [B, S, E] float32; w one layer's weights."""
    B, S, E = x.shape
    H, D, eps = s["H"], s["D"], s["eps"]
    h = norm(x, w["attn_norm"], eps)
    q, k, v = (_linear(h, w[n].reshape(E, H * D), quant).reshape(B, S, H, D)
               for n in ("wq", "wk", "wv"))
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    k_sum, v_sum = summaries(k, v, w["eva_mu"], w["eva_phi"], s["chunk"])
    o = eva_attention(q, k, v, k_sum, v_sum, s["window"], s["chunk"])
    x = x + _linear(o.reshape(B, S, H * D), w["wo"].reshape(H * D, E), quant)

    def mlp(x):
        h = norm(x, w["mlp_norm"], eps)
        return _linear(jax.nn.silu(_linear(h, w["w_gate"], quant))
                       * _linear(h, w["w_up"], quant), w["w_down"], quant)

    return x + _by_rows(mlp, x)


#: rows of a sequence the gated MLP takes at a time
MLP_ROWS = 4096


def _by_rows(fn, x):
    """``fn`` of x [B, S, E], which works on every position alone, over
    ``MLP_ROWS`` positions at a time and recomputed in the backward pass: a
    row of 32,768 holds three [S, 11008] float32 arrays otherwise."""
    B, S, E = x.shape
    if S <= MLP_ROWS or S % MLP_ROWS:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn), jnp.moveaxis(
        x.reshape(B, S // MLP_ROWS, MLP_ROWS, E), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, E)


def head_targets_and_masks(tokens, mask, heads):
    """(targets [B, S, J], masks [B, S, J]): head j (from 1) at position t
    predicts token t + j, where t + j is in the row and ``mask[t]`` is
    set."""
    S = tokens.shape[1]
    targets = jnp.stack([jnp.roll(tokens, -j, axis=1)
                         for j in range(1, heads + 1)], axis=-1)
    ahead = jnp.arange(S)[:, None] + jnp.arange(1, heads + 1)[None, :] <= S - 1
    return targets, mask.astype(F32)[..., None] * ahead


def heads_loss(x, final_norm, lm_head, targets, masks, s, quant=None):
    """x [B, S, E], lm_head [E, J, V] -> (loss, head_loss [J])."""
    E, J, V = lm_head.shape
    y = norm(x, final_norm, s["eps"])
    lg = _linear(y, lm_head.reshape(E, J * V), quant).reshape(
        *x.shape[:2], J, V)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, targets[..., None], -1)[..., 0]
    head_loss = jnp.sum(nll * masks, axis=(0, 1)) / jnp.sum(masks, axis=(0, 1))
    return jnp.mean(head_loss), head_loss


def _widen(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _stack(w, tokens, s, quant):
    """tokens [B, S] -> the stream after the last layer, a Python loop over
    the layers of the widened weights ``w``."""
    x = w["embed"][tokens]
    for i in range(s["L"]):
        x = layer(x, jax.tree.map(lambda a: a[i], w["blocks"]), s, quant)
    return x


def loss_and_report(weights, tokens, mask, s, quant=None):
    """The whole function in one piece: (loss, {"head_loss": [J]}) for
    tokens [B, S].  For sizes that hold every activation at once (the tests
    differentiate it in every leaf)."""
    w = _widen(weights)
    x = _stack(w, tokens, s, quant)
    targets, masks = head_targets_and_masks(tokens, mask, s["J"])
    loss, head_loss = heads_loss(x, w["final_norm"], w["lm_head"], targets,
                                 masks, s, quant)
    return loss, {"head_loss": head_loss}


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes (the items of
    ``s``): a layer forward and backward, the heads forward and backward."""
    s = dict(sizes)
    forward = jax.jit(lambda x, w: layer(x, _widen(w), s, quant))

    @jax.jit
    def backward(x, w, gx):
        _, vjp = jax.vjp(lambda x, w: layer(x, w, s, quant), x, _widen(w))
        gx, gw = vjp(gx)
        return gx, {n: gw[n] for n in JUDGED}

    @jax.jit
    def heads(x, final_norm, lm_head, tokens, mask):
        targets, masks = head_targets_and_masks(tokens, mask, s["J"])
        (loss, head_loss), vjp = jax.vjp(
            lambda x, n: heads_loss(x, n, lm_head.astype(F32), targets, masks,
                                    s, quant), x, final_norm.astype(F32))
        gx, g_final = vjp((jnp.ones((), F32), jnp.zeros_like(head_loss)))
        return loss, head_loss, gx, g_final

    return forward, backward, heads


def loss_and_judged_grads(weights, tokens, mask, s, quant=None):
    """(loss, {"head_loss": [J]}, the gradient in the judged weights) for
    tokens [B, S]: ``final_norm [E]`` and, under ``blocks`` with a leading
    layer axis, ``attn_norm``, ``mlp_norm [L, E]``, ``eva_mu``,
    ``eva_phi [L, H, D]``.

    The forward walk keeps every layer's input; the backward walk goes back
    through the layers, one ``jax.vjp`` of ``layer`` at a time.  A Python
    loop over jitted pieces: call it outside ``jax.jit``."""
    forward, backward, heads = _programs(tuple(sorted(s.items())), quant)
    at = lambda i: jax.tree.map(lambda a: a[i], weights["blocks"])
    x, inputs = weights["embed"].astype(F32)[tokens], []
    for i in range(s["L"]):
        inputs.append(x)
        x = forward(x, at(i))
    loss, head_loss, gx, g_final = heads(
        x, weights["final_norm"], weights["lm_head"], tokens, mask)
    del x
    g_layers = [None] * s["L"]
    for i in reversed(range(s["L"])):
        gx, g_layers[i] = backward(inputs.pop(), at(i), gx)
    return loss, {"head_loss": head_loss}, {
        "final_norm": g_final,
        "blocks": jax.tree.map(lambda *a: jnp.stack(a), *g_layers)}


def logits(weights, tokens, s, quant=None):
    """tokens [B, S] -> logits [B, S, J, V] float32."""
    w = _widen(weights)
    E, J, V = w["lm_head"].shape
    y = norm(_stack(w, tokens, s, quant), w["final_norm"], s["eps"])
    return _linear(y, w["lm_head"].reshape(E, J * V), quant).reshape(
        *tokens.shape, J, V)
