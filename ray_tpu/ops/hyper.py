"""Hyper-connections: a residual stream of ``n`` lanes, mixed by maps that are
made from the stream itself (arXiv:2409.19606), the lane-to-lane map held
doubly stochastic by Sinkhorn-Knopp iterations (manifold-constrained
hyper-connections, arXiv:2512.24880).

A token's state is ``X`` [n, C].  A sublayer ``F`` (attention, a feed-forward
part) has its own ``phi`` [n*C, 2n + n*n], ``b`` [2n + n*n] and three gains
``alpha``; with ``m = vec(X) / rms(vec(X)) . phi``:

    H_pre  = sigmoid(alpha[0] m[:n] + b[:n])                     [n]
    H_post = 2 sigmoid(alpha[1] m[n:2n] + b[n:2n])               [n]
    H_res  = sinkhorn(exp(clamp(alpha[2] mat(m[2n:]) + mat(b[2n:]))))  [n, n]
    u = sum_j H_pre[j] X[j];   X <- H_res X + H_post (x) F(N(u))

The stream is laid out [B, n, S, C]: a lane is a [S, C] plane, tiled as any
activation is, and no dimension of 4 stands where the TPU pads to 8 or 16.
The maps carry the tokens along the lanes: H_pre and H_post [B, n, S],
H_res [B, n, n, S], float32, so that Sinkhorn's twenty passes over a 4 x 4
matrix a token are elementwise work on [S]-long rows.

Every pass here is bound by the stream's bytes: ``hc_maps`` and
``hc_collect`` read it once each, ``hc_deposit`` reads it and writes it.
They are ``jnp``, said so that XLA fuses each into reductions over the lanes
that read the stream in place; in a compiled train step at [1, 4, 8192,
3584] they move it at 28 % of HBM pace (PERF.md, PR 40, has the forms tried:
the write-back as sixteen sliced products ran the step 4.5 % slower and held
2 % more of the chip).  The compiler keeps this stream with the sequence
minor, as the maps carry the tokens along the lanes; pinning it row-major,
which PR 39 found for a float32 stream, gained 0.6 % under the sliced
write-back and was not tried under this one.
Which path a call took is counted in
``ray_tpu_hc_path_total`` (``xla``; a Pallas pair would count ``kernel``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..util import telemetry


def sinkhorn(R, iters: int, eps: float):
    """``exp(R)`` made doubly stochastic: ``iters`` times, every column
    divided by its sum + ``eps``, then every row by its.  R: [..., n, n, S]
    float32 (rows, columns, tokens), already clamped.  Unrolled: autodiff's
    backward through twenty elementwise passes over 16 numbers a token is
    not worth a rule of its own (PERF.md, PR 40)."""
    M = jnp.exp(R)
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=-3, keepdims=True) + eps)
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)
    return M


def sinkhorn_residual(H_res):
    """Largest |row sum - 1| and |column sum - 1| of H_res [..., n, n, S]."""
    rows = jnp.max(jnp.abs(jnp.sum(H_res, axis=-2) - 1.0))
    cols = jnp.max(jnp.abs(jnp.sum(H_res, axis=-3) - 1.0))
    return jnp.maximum(rows, cols)


def hc_maps(X, phi, b, alpha, iters: int, eps: float,
            clamp: Tuple[float, float], norm_eps: float = 1e-6):
    """The three maps of a sublayer from the stream X [B, n, S, C]: (H_pre
    [B, n, S], H_post [B, n, S], H_res [B, n, n, S]), float32.  ``phi``
    [n*C, 2n + n*n], ``b`` [2n + n*n], ``alpha`` [3] (pre, post, res).

    ``vec(X) / rms . phi`` is formed as ``(vec(X) . phi) / rms``: the thin
    product takes the stream as it is held, accumulates in float32, and
    the sum of squares rides the same pass; no normed copy of 4 C numbers
    a token exists."""
    B, n, S, C = X.shape
    telemetry.inc("ray_tpu_hc_path_total",
                  tags={"path": "xla", "lanes": str(n)})
    with jax.named_scope("block/hc/maps"):
        # Token-major out of the product, as X lies; the 24 numbers a token
        # are then turned once, so that what follows is elementwise work on
        # [S]-long rows.
        raw = jnp.swapaxes(jnp.einsum(
            "bjsc,jcm->bsm", X, phi.astype(X.dtype).reshape(n, C, -1),
            preferred_element_type=jnp.float32), 1, 2)
        square = jnp.sum(jnp.square(X.astype(jnp.float32)), axis=(1, 3))
        m = raw * jax.lax.rsqrt(square / (n * C) + norm_eps)[:, None, :]
        a = alpha.astype(jnp.float32)
        b = b.astype(jnp.float32)[None, :, None]
        H_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:, :n])
        H_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[:, n:2 * n])
        R = jnp.clip(a[2] * m[:, 2 * n:] + b[:, 2 * n:], *clamp)
        H_res = sinkhorn(R.reshape(B, n, n, S), iters, eps)
        return H_pre, H_post, H_res


def hc_collect(X, H_pre):
    """What a sublayer reads: ``sum_j H_pre[j] X[j]``, [B, S, C] in the
    stream's dtype.  X [B, n, S, C], H_pre [B, n, S]."""
    with jax.named_scope("block/hc/collect"):
        u = jnp.sum(H_pre[..., None] * X.astype(jnp.float32), axis=1)
        return u.astype(X.dtype)


def hc_deposit(X, H_res, H_post, y):
    """The write-back ``X <- H_res X + H_post (x) y``: lane i gets ``sum_j
    H_res[i, j] X[j] + H_post[i] y``.  X [B, n, S, C], H_res [B, n, n, S],
    H_post [B, n, S], y [B, S, C]; float32 inside, X's dtype out."""
    with jax.named_scope("block/hc/deposit"):
        # A lane written is one reduction over the lanes read, as
        # ``hc_collect`` is, with the sublayer's part as its epilogue.
        x32, y32 = X.astype(jnp.float32), y.astype(jnp.float32)
        lanes = [jnp.sum(H_res[:, i, :, :, None] * x32, axis=1)
                 + H_post[:, i, :, None] * y32 for i in range(X.shape[1])]
        return jnp.stack(lanes, axis=1).astype(X.dtype)
