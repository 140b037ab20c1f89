"""The plain reference of the ``ouro`` looped stack (Ouro-2.6B): forward,
the exit-weighed loss, the passes' own losses and exit shares, and the
gradient in every RMSNorm weight and in the exit gate, in float32
``jax.numpy`` at the highest matmul precision.  Nothing here comes from
``ray_tpu``; the elementary pieces (RMSNorm, rotary embedding on split
halves, causal attention, a linear layer with its int8 control, the
distance) are ``reference.py``'s.

The equations (sizes from Ouro-2.6B's ``config.json``; the lines marked
(assumed) are from the family's description and published modelling code and
are listed in ``configs/ouro-2.6b.json`` under ``assumed``).  N is RMSNorm
with a weight, eps 1e-6.

    h^0 = Embed[tokens]
    for t = 1..T:                         # T = total_ut_steps, one set of weights
        x = h^{t-1}
        for l = 1..L:
            a = x + N2_l( Attn_l( N1_l(x) ) )                (assumed: N2, N4)
            x = a + N4_l( W_down( silu(W_gate N3_l(a)) * (W_up N3_l(a)) ) )
        h^t = N_f(x)                      # (assumed) after every pass; the
                                          # normed state starts the next
        nll^t[pos] = -log softmax(h^t W_head)[target]         # one untied head
        lam^t[pos] = sigmoid(h^t . w_g + b_g)                 # (assumed: b_g)
    q^1 = lam^1;  q^t = lam^t prod_{j<t} (1 - lam^j);  q^T = prod_{j<T} (1 - lam^j)
    loss = mean over masked positions of  sum_t q^t nll^t - beta H(q)   (assumed)

``Attn``: q, k, v = N1(x) Wq, Wk, Wv with no bias, rotary embedding on
split halves (theta 1e6), causal softmax(q k^T / sqrt(128)) v in float32,
no window (``use_sliding_window`` false), ``Wo``.  ``early_exit_threshold``
is a serving key and does nothing here.

Departures: none in the mathematics.  The exit distribution is formed as
the products above and its entropy as ``-sum q log q`` directly (the program
forms both in logarithms).  For the size of a check row the walk below runs a
layer, and a pass's head, at a time (``loss_and_judged_grads``); the whole
function in one piece is ``loss_and_report``, and the tests hold the two
together.

``quant="int8"`` is the control, as in ``reference.py``: the inputs of every
linear layer (the head's and the gate's too) rounded to 8-bit integers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import (F32, _attention, _linear, _rms_norm, _rope,
                                 relative_distance)  # noqa: F401

NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


def layer(x, w, s, quant=None):
    """One layer, four norms.  x [B, S, E] float32; w one layer's weights."""
    B, S, E = x.shape
    H, K, D, eps = s["H"], s["Hkv"], s["D"], s["eps"]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _linear(h, w["wq"].reshape(E, H * D), quant).reshape(B, S, H, D)
    k = _linear(h, w["wk"].reshape(E, K * D), quant).reshape(B, S, K, D)
    v = _linear(h, w["wv"].reshape(E, K * D), quant).reshape(B, S, K, D)
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    k, v = (jnp.repeat(t, H // K, axis=2) for t in (k, v))
    attn = _linear(_attention(q, k, v).reshape(B, S, H * D),
                   w["wo"].reshape(H * D, E), quant)
    a = x + _rms_norm(attn, w["attn_post_norm"], eps)
    h = _rms_norm(a, w["mlp_norm"], eps)
    mlp = _linear(jax.nn.silu(_linear(h, w["w_gate"], quant))
                  * _linear(h, w["w_up"], quant), w["w_down"], quant)
    return a + _rms_norm(mlp, w["mlp_post_norm"], eps)


def head(h, lm_head, gate, targets, quant=None):
    """(nll [B, S], the gate's logit [B, S]) of one pass's state h."""
    lg = _linear(h, lm_head, quant)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, targets[..., None], -1)[..., 0]
    return nll, _linear(h, gate["w"][:, None], quant)[..., 0] + gate["b"]


def objective(nll, z, mask, beta):
    """nll, z [T, B, S] -> (loss, {loop_loss [T], loop_exit_share [T],
    loop_exit_entropy})."""
    lam = jax.nn.sigmoid(z)
    stay = jnp.cumprod(1.0 - lam, axis=0)            # prod_{j<=t} (1 - lam^j)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    q = jnp.concatenate([lam[:-1] * before[:-1], before[-1:]], 0)
    entropy = -jnp.sum(q * jnp.log(q), axis=0)
    mean = lambda a: jnp.sum(a * mask, axis=(-2, -1)) / jnp.sum(mask)
    return mean(jnp.sum(q * nll, axis=0) - beta * entropy), {
        "loop_loss": mean(nll), "loop_exit_share": mean(q),
        "loop_exit_entropy": mean(entropy)}


def _widen(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _targets(tokens):
    return jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)


def loss_and_report(weights, tokens, mask, s, quant=None):
    """The whole function in one piece: (loss, report) for tokens [B, S],
    a Python loop over passes and layers.  For sizes that hold every
    activation at once (the tests differentiate it in every leaf)."""
    w = _widen(weights)
    x, nll, z = w["embed"][tokens], [], []
    for _ in range(s["T"]):
        for i in range(s["L"]):
            x = layer(x, jax.tree.map(lambda a: a[i], w["blocks"]), s, quant)
        x = _rms_norm(x, w["final_norm"], s["eps"])
        n, g = head(x, w["lm_head"], w["exit_gate"], _targets(tokens), quant)
        nll.append(n)
        z.append(g)
    return objective(jnp.stack(nll), jnp.stack(z), mask.astype(F32),
                     s["beta"])


@functools.lru_cache(maxsize=None)
def _programs(sizes, quant):
    """The jitted pieces of the walk for one set of sizes (the items of
    ``s``): a layer forward and backward, the norm that closes a pass, a
    pass's head forward and backward, the objective."""
    s = dict(sizes)
    forward = jax.jit(lambda x, w: layer(x, _widen(w), s, quant))

    @jax.jit
    def backward(x, w, gx):
        _, vjp = jax.vjp(lambda x, w: layer(x, w, s, quant), x, _widen(w))
        gx, gw = vjp(gx)
        return gx, {n: gw[n] for n in NORMS}

    close = jax.jit(lambda x, n: _rms_norm(x, n.astype(F32), s["eps"]))

    @jax.jit
    def close_back(x, n, gh):
        _, vjp = jax.vjp(lambda x, n: _rms_norm(x, n, s["eps"]), x,
                         n.astype(F32))
        return vjp(gh)

    head_of = jax.jit(lambda h, lm_head, gate, targets: head(
        h, lm_head.astype(F32), _widen(gate), targets, quant))

    @jax.jit
    def head_back(h, lm_head, gate, targets, g_nll, g_z):
        _, vjp = jax.vjp(lambda h, gate: head(h, lm_head.astype(F32), gate,
                                              targets, quant),
                         h, _widen(gate))
        return vjp((g_nll, g_z))

    weigh = jax.jit(jax.value_and_grad(
        lambda nll, z, mask: objective(nll, z, mask, s["beta"]),
        argnums=(0, 1), has_aux=True))
    return forward, backward, close, close_back, head_of, head_back, weigh


def loss_and_judged_grads(weights, tokens, mask, s, quant=None):
    """(loss, report, the gradient in the judged weights) for tokens [B, S]:
    the report is ``loop_loss`` [T], ``loop_exit_share`` [T] and
    ``loop_exit_entropy``; the gradient tree is ``final_norm [E]``, under
    ``blocks`` the four norms of ``NORMS`` with a leading layer axis, and
    ``exit_gate`` (``w [E]``, ``b``).  Each is the sum over the T uses of
    the weight.

    The forward walk keeps every layer's input of every pass; the backward
    walk goes back through the passes and their layers, one ``jax.vjp`` of
    ``layer`` at a time, and hands what reaches a pass's input on to the
    state the pass before it produced, beside what that state's own head and
    gate gave.  A Python loop over jitted pieces, so compiling it does not
    grow with depth or passes: call it outside ``jax.jit``."""
    (forward, backward, close, close_back, head_of, head_back,
     weigh) = _programs(tuple(sorted(s.items())), quant)
    T, L = s["T"], s["L"]
    at = lambda i: jax.tree.map(lambda a: a[i], weights["blocks"])
    targets, mask = _targets(tokens), mask.astype(F32)
    lm_head, gate, final = (weights["lm_head"], weights["exit_gate"],
                            weights["final_norm"])
    x, inputs, outs, hs, nll, z = weights["embed"].astype(F32)[tokens], \
        [], [], [], [], []
    for _ in range(T):
        for i in range(L):
            inputs.append(x)
            x = forward(x, at(i))
        outs.append(x)
        x = close(x, final)
        hs.append(x)
        n, g = head_of(x, lm_head, gate, targets)
        nll.append(n)
        z.append(g)
    (loss, report), (g_nll, g_z) = weigh(jnp.stack(nll), jnp.stack(z), mask)

    zero = lambda tree: jax.tree.map(lambda a: jnp.zeros(a.shape, F32), tree)
    g_gate, g_final = zero(gate), zero(final)
    g_layers = [None] * L
    gx = None                   # what the next pass's input hands back
    for t in reversed(range(T)):
        gh, gg = head_back(hs[t], lm_head, gate, targets, g_nll[t], g_z[t])
        g_gate = jax.tree.map(jnp.add, g_gate, gg)
        gx, gn = close_back(outs[t], final, gh if gx is None else gh + gx)
        g_final = g_final + gn
        for i in reversed(range(L)):
            gx, g = backward(inputs[t * L + i], at(i), gx)
            g_layers[i] = g if g_layers[i] is None else jax.tree.map(
                jnp.add, g_layers[i], g)
    return loss, report, {
        "final_norm": g_final,
        "blocks": jax.tree.map(lambda *a: jnp.stack(a), *g_layers),
        "exit_gate": g_gate}


def logits(weights, tokens, s, quant=None):
    """tokens [B, S] -> the last pass's logits [B, S, V] float32."""
    w = _widen(weights)
    x = w["embed"][tokens]
    for _ in range(s["T"]):
        for i in range(s["L"]):
            x = layer(x, jax.tree.map(lambda a: a[i], w["blocks"]), s, quant)
        x = _rms_norm(x, w["final_norm"], s["eps"])
    return _linear(x, w["lm_head"], quant)
