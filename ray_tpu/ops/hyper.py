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

Every pass over the stream is bound by its bytes, and a model calls two
entries that choose who makes them:

- ``collect`` -> (the stream, ``u``, H_post, H_res) and ``deposit`` -> the
  stream written back.  Where the backend is a TPU (or ``interpret``, for
  the tests), C is a multiple of 128 and S of every kernel's tile, Pallas
  kernels touch the stream and nothing else does: ``hc_collect`` reads a
  tile [n, rows, C] once for the thin product (MXU, bf16 operands, float32
  accumulator), the sum of squares, H_pre and ``u``; ``hc_deposit`` reads it
  once and writes it once, float32 in VMEM only.  Both have
  ``jax.custom_vjp`` rules whose backward is three kernels more
  (``hc_deposit_bwd``: dy, the write-back's part of dX, dH_res, dH_post;
  ``hc_pre_bwd``: dH_pre from the sublayer's own du; ``hc_collect_bwd``: dX
  summed in float32 and rounded once, d phi accumulated over the token
  grid).  In lane-sets (one = the stream's bytes once): 1 1/4 + 2 1/4
  forward, 3 1/2 + 1 1/4 + 3 1/4 = 8 backward.  The 24 numbers a token
  (``_maps_of``: the three maps, Sinkhorn's unrolled passes, their
  autodiff) stay ``jnp`` on [S]-long float32 rows.  Each rule's forward and
  backward is one ``jax.jit``, so that a step's twelve sublayers share one
  trace a signature, and every operation of a rule enters
  ``block/hc/maps``, ``/collect`` or ``/deposit`` inside it.  A tile is the
  most rows that fit Mosaic's default scoped VMEM (``_tile``): in a compiled
  train step at [1, 4, 8192, 3584] the five kernels move the stream at 61 %
  of HBM pace (PERF.md, PR 42).
- Elsewhere ``hc_maps``, ``hc_collect`` and ``hc_deposit``: ``jnp``, the
  definition the kernels are held to and the CPU's path.  XLA moves the
  stream through them at 28 % of HBM pace (a float32 copy first, four
  reductions, autodiff's re-reads, the stream kept sequence-minor;
  PERF.md, PR 40).

Which path a sublayer took is counted in ``ray_tpu_hc_path_total``
(``kernel`` or ``xla``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..util import telemetry

#: what a kernel's double-buffered blocks may take of VMEM: Mosaic's default
#: scoped limit is 16 MiB, and no call asks for more.  A kernel that does
#: (40 MiB as 100) runs alone and hangs the cell's compiled step in its first
#: call, as flash's 1,024 x 1,024 blocks at 192 / 128 did (PERF.md, PR 42:
#: XLA keeps up to 64 MiB of its own in VMEM across the step's custom calls).
_VMEM_TILES = 12 * 2 ** 20
#: a tile's rows at most, and what a kernel's inner loop takes between a load
#: and a store: [_LOOP_ROWS, up to _LOOP_LANES] (a packed bf16 tile's
#: sublanes; of the columns as many as trace fast: at 128 the five bodies
#: take 2 s more to trace a step and run no faster, PERF.md, PR 42)
_MAX_ROWS = 512
_LOOP_ROWS = 16
_LOOP_LANES = 512
#: [rows, C] planes a kernel's step holds (the widest: G' or g, X and dX,
#: then y and dy, or du and the float32 ``back``), and whether phi [n, W, C]
#: stays beside them, and its float32 cotangent
_HELD = {"collect": (lambda n: n + 1, True, False),
         "pre_bwd": (lambda n: n + 1, False, False),
         "deposit": (lambda n: 2 * n + 1, False, False),
         "deposit_bwd": (lambda n: 3 * n + 2, False, False),
         "collect_bwd": (lambda n: 3 * n + 2, True, True)}


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


def _maps_of(raw, square, b, alpha, n: int, C: int, iters: int, eps: float,
             clamp: Tuple[float, float], norm_eps: float):
    """The three maps from the 2n + n*n thin products ``raw`` [B, 2n + n*n,
    S] and the sum of squares ``square`` [B, S] of a token's n*C numbers,
    float32: elementwise work on [S]-long rows."""
    B, _, S = raw.shape
    m = raw * jax.lax.rsqrt(square / (n * C) + norm_eps)[:, None, :]
    a = alpha.astype(jnp.float32)
    b = b.astype(jnp.float32)[None, :, None]
    H_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:, :n])
    H_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[:, n:2 * n])
    R = jnp.clip(a[2] * m[:, 2 * n:] + b[:, 2 * n:], *clamp)
    return H_pre, H_post, sinkhorn(R.reshape(B, n, n, S), iters, eps)


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
        return _maps_of(raw, square, b, alpha, n, C, iters, eps, clamp,
                        norm_eps)


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


# ------------------------------------------------------------ the kernels
#
# A kernel's grid is (rows of the batch, tiles of the sequence); a step holds
# a tile [n, rows, C] of every stream it reads or writes and [rows, C] of u,
# y or their cotangents.  The numbers a token go in and out token-major,
# float32 [B, S, W] (W = ``_width``: a tile's few lanes), each kernel's
# columns as its docstring says; ``jnp`` turns them to and from the maps'
# [B, ., S] outside.

def _width(n: int) -> int:
    """Columns of a token-major array of the numbers a token: the 2n + n*n
    products, the sum of squares and H_pre beside them, in 16s."""
    return -(-(3 * n + n * n + 1) // 16) * 16


def _tile(X, kernel: str) -> Optional[int]:
    """Rows of ``kernel``'s tile of the stream X [B, n, S, C], from the
    call's shapes alone: the most that divide S, are whole inner-loop turns,
    and whose planes, with the blocks that stay, fit ``_VMEM_TILES``
    double-buffered.  None where nothing does."""
    _, n, S, C = X.shape
    planes, phi, dphi = _HELD[kernel]
    size = X.dtype.itemsize
    stays = n * _width(n) * C * (phi * size + dphi * 4)
    fits = [d for d in range(_LOOP_ROWS, min(S, _MAX_ROWS) + 1, _LOOP_ROWS)
            if S % d == 0
            and 2 * (d * C * size * planes(n) + stays) <= _VMEM_TILES]
    return max(fits, default=None)


def _loop(rows: int, body):
    """``body(at)`` for every ``_LOOP_ROWS`` rows of a tile of ``rows``."""
    from jax.experimental import pallas as pl

    def turn(r, carry):
        body(pl.ds(pl.multiple_of(r * _LOOP_ROWS, _LOOP_ROWS), _LOOP_ROWS))
        return carry

    jax.lax.fori_loop(0, rows // _LOOP_ROWS, turn, 0)


def _chunks(C: int):
    """C's columns in slices of one width, the most 128s up to
    ``_LOOP_LANES`` that divide it: (the width, the slices)."""
    width = max(w for w in range(128, min(C, _LOOP_LANES) + 1, 128)
                if C % w == 0)
    return width, [slice(c, c + width) for c in range(0, C, width)]


def _column(values, k: int):
    """Column k of a [rows, W] value, [rows, 1]: broadcasts along C."""
    return values[:, k:k + 1]


def _set_columns(width: int, columns):
    """[rows, width] float32 whose column k is ``columns[k]`` [rows, 1] and
    whose others are 0."""
    rows = columns[0].shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = jnp.zeros((rows, width), jnp.float32)
    for k, c in enumerate(columns):
        out = jnp.where(at == k, c, out)
    return out


def _lane_sum(acc):
    """[rows, lanes] -> [rows, 1]."""
    return jnp.sum(acc, axis=1, keepdims=True)


def _collect_kernel(x_ref, phi_ref, ab_ref, u_ref, t_ref, *, norm_eps):
    """x [n, rows, C], phi [n, W, C] (rows past 2n + n*n zero), ab [2, W]
    (each column's gain, then its b) -> u [rows, C]; t [rows, W]: the thin
    products, then the sum of squares in column 2n + n*n."""
    n, rows, C = x_ref.shape
    M, f32 = 2 * n + n * n, jnp.float32
    raw = sum(jax.lax.dot_general(
        x_ref[j], phi_ref[j], (((1,), (1,)), ((), ())),
        preferred_element_type=f32) for j in range(n))
    t_ref[...] = raw
    gain, bias = ab_ref[0:1, :], ab_ref[1:2, :]
    width, chunks = _chunks(C)

    def body(at):
        acc = jnp.zeros((_LOOP_ROWS, width), f32)
        for c in chunks:
            for j in range(n):
                x = x_ref[j, at, c].astype(f32)
                acc = acc + x * x
        square = _lane_sum(acc)
        raw = t_ref[at, :]
        at_col = jax.lax.broadcasted_iota(jnp.int32, raw.shape, 1)
        t_ref[at, :] = jnp.where(at_col == M, square, raw)
        m = raw * jax.lax.rsqrt(square / (n * C) + norm_eps)
        H_pre = 1.0 / (1.0 + jnp.exp(-(gain * m + bias)))
        for c in chunks:
            u = sum(_column(H_pre, j) * x_ref[j, at, c].astype(f32)
                    for j in range(n))
            u_ref[at, c] = u.astype(u_ref.dtype)

    _loop(rows, body)


def _deposit_kernel(x_ref, y_ref, h_ref, o_ref):
    """x [n, rows, C], y [rows, C], h [rows, W] (column i*n + j H_res[i, j],
    column n*n + i H_post[i]) -> o [n, rows, C]."""
    n, rows, C = x_ref.shape
    f32 = jnp.float32

    def body(at):
        h = h_ref[at, :]
        for c in _chunks(C)[1]:
            x = [x_ref[j, at, c].astype(f32) for j in range(n)]
            y = y_ref[at, c].astype(f32)
            for i in range(n):
                lane = _column(h, n * n + i) * y
                for j in range(n):
                    lane = lane + _column(h, i * n + j) * x[j]
                o_ref[i, at, c] = lane.astype(o_ref.dtype)

    _loop(rows, body)


def _deposit_bwd_kernel(g_ref, x_ref, y_ref, h_ref, dx_ref, dy_ref, dh_ref):
    """g = dX' [n, rows, C], x, y, h as the forward's -> dx [n, rows, C]
    (``H_res^T g``), dy [rows, C] (``sum_i H_post[i] g[i]``), dh [rows, W]
    (column i*n + j ``sum_c g[i] x[j]``, column n*n + i ``sum_c g[i] y``).
    Two sweeps over a loop turn's rows, so that a sweep's sums fit the
    registers: the elementwise results with dH_post, then dH_res a pair of
    written lanes at a time."""
    n, rows, C = x_ref.shape
    f32 = jnp.float32
    width, chunks = _chunks(C)
    zero = lambda: jnp.zeros((_LOOP_ROWS, width), f32)

    def body(at):
        h = h_ref[at, :]
        post = [zero() for _ in range(n)]
        for c in chunks:
            g = [g_ref[i, at, c].astype(f32) for i in range(n)]
            y = y_ref[at, c].astype(f32)
            dy = sum(_column(h, n * n + i) * g[i] for i in range(n))
            dy_ref[at, c] = dy.astype(dy_ref.dtype)
            for j in range(n):
                dx = sum(_column(h, i * n + j) * g[i] for i in range(n))
                dx_ref[j, at, c] = dx.astype(dx_ref.dtype)
            post = [post[i] + g[i] * y for i in range(n)]
        columns = {n * n + i: _lane_sum(post[i]) for i in range(n)}
        for pair in range(0, n, 2):
            written = range(pair, min(pair + 2, n))
            res = {(i, j): zero() for i in written for j in range(n)}
            for c in chunks:
                x = [x_ref[j, at, c].astype(f32) for j in range(n)]
                for i in written:
                    g = g_ref[i, at, c].astype(f32)
                    for j in range(n):
                        res[i, j] = res[i, j] + g * x[j]
            columns |= {i * n + j: _lane_sum(v) for (i, j), v in res.items()}
        dh_ref[at, :] = _set_columns(
            dh_ref.shape[1], [columns[k] for k in range(n * n + n)])

    _loop(rows, body)


def _pre_bwd_kernel(du_ref, x_ref, dh_ref):
    """du [rows, C], x [n, rows, C] -> dh [rows, W]: column j
    ``sum_c du x[j]``, dH_pre."""
    n, rows, C = x_ref.shape
    f32 = jnp.float32

    width, chunks = _chunks(C)

    def body(at):
        pre = [jnp.zeros((_LOOP_ROWS, width), f32) for _ in range(n)]
        for c in chunks:
            du = du_ref[at, c].astype(f32)
            pre = [pre[j] + du * x_ref[j, at, c].astype(f32)
                   for j in range(n)]
        dh_ref[at, :] = _set_columns(dh_ref.shape[1],
                                     [_lane_sum(p) for p in pre])

    _loop(rows, body)


def _collect_bwd_kernel(x_ref, g_ref, du_ref, t_ref, phi_ref, dx_ref,
                        dphi_ref, back_ref):
    """x [n, rows, C], g [n, rows, C] (the cotangent of the stream handed
    on), du [rows, C], t [rows, W] (d raw, then column 2n + n*n twice the
    statistic's cotangent, then H_pre), phi [n, W, C] -> dx [n, rows, C] =
    ``g + H_pre (x) du + d raw . phi + 2 d square x``, summed in float32;
    dphi [n, W, C] float32 += ``d raw^T x`` over the whole grid.  ``back``
    [rows, C] float32 is a lane's ``d raw . phi`` between the MXU and the
    loop."""
    from jax.experimental import pallas as pl
    n, rows, C = x_ref.shape
    M, f32 = 2 * n + n * n, jnp.float32

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    # The columns past d raw carry other numbers: not into the products.
    t = t_ref[...]
    at_col = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    d_raw = jnp.where(at_col < M, t, 0.0).astype(x_ref.dtype)
    for j in range(n):
        dphi_ref[j] += jax.lax.dot_general(
            d_raw, x_ref[j], (((0,), (0,)), ((), ())),
            preferred_element_type=f32)
        back_ref[...] = jnp.dot(d_raw, phi_ref[j], preferred_element_type=f32)

        def body(at, j=j):
            t = t_ref[at, :]
            for c in _chunks(C)[1]:
                dx = (back_ref[at, c] + g_ref[j, at, c].astype(f32)
                      + _column(t, M + 1 + j) * du_ref[at, c].astype(f32)
                      + _column(t, M) * x_ref[j, at, c].astype(f32))
                dx_ref[j, at, c] = dx.astype(dx_ref.dtype)

        _loop(rows, body)


def _call(kernel, name: str, grid, in_specs, out_specs, out_shape, interpret,
          scratch=()):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch),
        interpret=interpret, name=name,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid))}))


def _specs(X, rows: int):
    """(grid, a stream's tile, a [B, S, .] array's tile of the same rows,
    one block that stays) for a kernel over X [B, n, S, C]."""
    from jax.experimental import pallas as pl
    B, n, S, C = X.shape
    stream = pl.BlockSpec((None, n, rows, C), lambda b, s: (b, 0, s, 0))
    plane = lambda width: pl.BlockSpec((None, rows, width),
                                       lambda b, s: (b, s, 0))
    whole = lambda *shape: pl.BlockSpec(shape, lambda b, s: (0,) * len(shape))
    return (B, S // rows), stream, plane, whole


def _token_major(parts, width: int):
    """[B, k, S] arrays stacked along k and turned: [B, S, width] float32,
    the columns past them zero."""
    t = jnp.concatenate(parts, axis=1)
    t = jnp.pad(t, ((0, 0), (0, width - t.shape[1]), (0, 0)))
    return jnp.swapaxes(t, 1, 2)


def _phi_by_lane(phi, n: int, C: int, dtype):
    """phi [n*C, M] as the kernels hold it: [n, W, C], rows past M zero."""
    by_lane = jnp.swapaxes(phi.astype(dtype).reshape(n, C, -1), 1, 2)
    return jnp.pad(by_lane,
                   ((0, 0), (0, _width(n) - by_lane.shape[1]), (0, 0)))


_MAPS = ("iters", "eps", "clamp", "norm_eps")


@functools.partial(jax.jit, static_argnames=_MAPS + ("interpret",))
def _collect_fwd(X, phi, b, alpha, *, iters, eps, clamp, norm_eps,
                 interpret):
    """-> (u, H_post, H_res, (raw, square)): one kernel over the stream,
    then the maps from its 2n + n*n + 1 numbers a token."""
    B, n, S, C = X.shape
    M, W, f32 = 2 * n + n * n, _width(n), jnp.float32
    with jax.named_scope("block/hc/collect"):
        gain = jnp.repeat(alpha.astype(f32), np.array([n, n, n * n]))
        ab = jnp.pad(jnp.stack([gain, b.astype(f32)]), ((0, 0), (0, W - M)))
        grid, stream, plane, whole = _specs(X, _tile(X, "collect"))
        u, t = _call(
            functools.partial(_collect_kernel, norm_eps=norm_eps),
            f"hc_collect_n{n}", grid,
            [stream, whole(n, W, C), whole(2, W)], [plane(C), plane(W)],
            [jax.ShapeDtypeStruct((B, S, C), X.dtype),
             jax.ShapeDtypeStruct((B, S, W), f32)], interpret)(
            X, _phi_by_lane(phi, n, C, X.dtype), ab)
    with jax.named_scope("block/hc/maps"):
        raw, square = jnp.swapaxes(t[..., :M], 1, 2), t[..., M]
        _, H_post, H_res = _maps_of(raw, square, b, alpha, n, C, iters, eps,
                                    clamp, norm_eps)
    return u, H_post, H_res, (raw, square)


@functools.partial(jax.jit, static_argnames=_MAPS + ("interpret",))
def _collect_bwd(X, phi, b, alpha, raw, square, dX, du, dH_post, dH_res, *,
                 iters, eps, clamp, norm_eps, interpret):
    """The cotangents of (X, phi, b, alpha) from those of (the stream handed
    on, u, H_post, H_res): dH_pre by one kernel, the maps' own backward by
    ``jax.vjp`` on their rows, dX and d phi by one kernel more."""
    B, n, S, C = X.shape
    M, W, f32 = 2 * n + n * n, _width(n), jnp.float32
    with jax.named_scope("block/hc/collect"):
        grid, stream, plane, _ = _specs(X, _tile(X, "pre_bwd"))
        dH_pre = jnp.swapaxes(_call(
            _pre_bwd_kernel, f"hc_pre_bwd_n{n}", grid, [plane(C), stream],
            plane(W), jax.ShapeDtypeStruct((B, S, W), f32), interpret)(
            du, X)[..., :n], 1, 2)
    with jax.named_scope("block/hc/maps"):
        (H_pre, _, _), back = jax.vjp(
            lambda raw, square, b, alpha: _maps_of(
                raw, square, b, alpha, n, C, iters, eps, clamp, norm_eps),
            raw, square, b, alpha)
        d_raw, d_square, db, dalpha = back((dH_pre, dH_post, dH_res))
    with jax.named_scope("block/hc/collect"):
        from jax.experimental.pallas import tpu as pltpu
        rows = _tile(X, "collect_bwd")
        grid, stream, plane, whole = _specs(X, rows)
        dX, dphi = _call(
            _collect_bwd_kernel, f"hc_collect_bwd_n{n}", grid,
            [stream, stream, plane(C), plane(W), whole(n, W, C)],
            [stream, whole(n, W, C)],
            [jax.ShapeDtypeStruct(X.shape, X.dtype),
             jax.ShapeDtypeStruct((n, W, C), f32)], interpret,
            scratch=[pltpu.VMEM((rows, C), f32)])(
            X, dX, du,
            _token_major([d_raw, 2.0 * d_square[:, None], H_pre], W),
            _phi_by_lane(phi, n, C, X.dtype))
        dphi = jnp.swapaxes(dphi[:, :M], 1, 2).reshape(n * C, M)
    return dX, dphi.astype(phi.dtype), db, dalpha


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _collect(X, phi, b, alpha, static):
    u, H_post, H_res, _ = _collect_fwd(X, phi, b, alpha, **dict(static))
    return X, u, H_post, H_res


def _collect_rule_fwd(X, phi, b, alpha, static):
    u, H_post, H_res, saved = _collect_fwd(X, phi, b, alpha, **dict(static))
    return (X, u, H_post, H_res), (X, phi, b, alpha) + saved


def _collect_rule_bwd(static, saved, cotangents):
    return _collect_bwd(*saved, *cotangents, **dict(static))


_collect.defvjp(_collect_rule_fwd, _collect_rule_bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _deposit_fwd(X, H_res, H_post, y, *, interpret):
    B, n, S, C = X.shape
    W = _width(n)
    with jax.named_scope("block/hc/deposit"):
        grid, stream, plane, _ = _specs(X, _tile(X, "deposit"))
        return _call(
            _deposit_kernel, f"hc_deposit_n{n}", grid,
            [stream, plane(C), plane(W)], stream,
            jax.ShapeDtypeStruct(X.shape, X.dtype), interpret)(
            X, y, _token_major([H_res.reshape(B, n * n, S), H_post], W))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _deposit_bwd(X, H_res, H_post, y, G, *, interpret):
    B, n, S, C = X.shape
    W, f32 = _width(n), jnp.float32
    with jax.named_scope("block/hc/deposit"):
        grid, stream, plane, _ = _specs(X, _tile(X, "deposit_bwd"))
        dX, dy, dh = _call(
            _deposit_bwd_kernel, f"hc_deposit_bwd_n{n}", grid,
            [stream, stream, plane(C), plane(W)],
            [stream, plane(C), plane(W)],
            [jax.ShapeDtypeStruct(X.shape, X.dtype),
             jax.ShapeDtypeStruct(y.shape, y.dtype),
             jax.ShapeDtypeStruct((B, S, W), f32)], interpret)(
            G, X, y, _token_major([H_res.reshape(B, n * n, S), H_post], W))
        dh = jnp.swapaxes(dh, 1, 2)
        return (dX, dh[:, :n * n].reshape(H_res.shape),
                dh[:, n * n:n * n + n], dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _deposit(X, H_res, H_post, y, interpret):
    return _deposit_fwd(X, H_res, H_post, y, interpret=interpret)


def _deposit_rule_fwd(X, H_res, H_post, y, interpret):
    return (_deposit_fwd(X, H_res, H_post, y, interpret=interpret),
            (X, H_res, H_post, y))


def _deposit_rule_bwd(interpret, saved, G):
    return _deposit_bwd(*saved, G, interpret=interpret)


_deposit.defvjp(_deposit_rule_fwd, _deposit_rule_bwd)


# ------------------------------------------------------------ the entries

def _kernels(X, interpret: bool) -> bool:
    """Whether the kernels take a stream of X's shape here."""
    from .attention import LANES, _on_tpu   # at the call: tests steer it
    return bool((interpret or _on_tpu()) and X.shape[-1] % LANES == 0
                and all(_tile(X, kernel) for kernel in _HELD))


def collect(X, phi, b, alpha, iters: int, eps: float,
            clamp: Tuple[float, float], norm_eps: float = 1e-6, *,
            interpret: bool = False):
    """What a sublayer reads and the maps of its write-back, from the stream
    X [B, n, S, C]: (the stream to hand to ``deposit``, u [B, S, C], H_post
    [B, n, S], H_res [B, n, n, S]); weights as ``hc_maps`` takes them.  The
    stream handed on is X: through it the write-back's part of X's
    cotangent reaches the kernel that sums all of it, where a second use of
    X would have XLA add the two."""
    if not _kernels(X, interpret):
        H_pre, H_post, H_res = hc_maps(X, phi, b, alpha, iters, eps, clamp,
                                       norm_eps)
        return X, hc_collect(X, H_pre), H_post, H_res
    telemetry.inc("ray_tpu_hc_path_total",
                  tags={"path": "kernel", "lanes": str(X.shape[1])})
    return _collect(X, phi, b, alpha, (
        ("iters", iters), ("eps", eps), ("clamp", tuple(clamp)),
        ("norm_eps", norm_eps), ("interpret", interpret)))


def deposit(X, H_res, H_post, y, *, interpret: bool = False):
    """``hc_deposit`` by the path ``collect`` took for this stream."""
    if not _kernels(X, interpret):
        return hc_deposit(X, H_res, H_post, y)
    return _deposit(X, H_res, H_post, y, interpret)
