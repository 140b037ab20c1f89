"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv 2510.26692): the
gated delta rule with a decay a CHANNEL, in chunks.  What
``models/bailing_hybrid.py``'s linear-attention layers run.

The recurrence, a head on a state ``S`` of ``d_k x d_v`` (float32, zero at
the start of every row), with ``alpha_t = exp(g_t)`` a vector of ``d_k``::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T (q_t * scale)

Unlike ``ops/ssm.ssd_scan``'s, the state is CORRECTED by what it already
holds: with ``u_t = beta_t (v_t - (alpha_t S_{t-1})^T k_t)`` the update is
``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T``, and every ``u_t`` of a chunk
depends on the ones before it.  ``kda`` computes it in chunks of ``chunk``
tokens, equal to the recurrence (the WY form of Gated DeltaNet, arXiv
2412.06464).  With ``G_t`` the running sum of ``g`` inside the chunk, ``S_0``
the state the chunk starts from::

    A[t, s]   = beta_t (k_t e^{G_t}) . (k_s e^{-G_s})      s <  t
    Aqk[t, s] =        (q_t e^{G_t}) . (k_s e^{-G_s})      s <= t
    U = (I + A)^{-1} (beta * (V - (K e^G) S_0))
    O = (Q e^G) S_0 + Aqk U
    S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

``(I + A)^{-1}`` of the unit lower-triangular ``C x C`` matrix is built
block by block (``_tri_inv``; its cotangent is ``-T^T dT T^T``): the inverse
of ``[[L11, 0], [L21, L22]]`` is ``[[T11, 0], [-T22 L21 T11, T22]]``, blocks
of 2 are ``I - A`` with no product, and a round joins blocks of b to blocks
of 2 b, each round exact (no power of ``A`` is formed).  A round multiplies
only what it fills: the blocks of a round lie SIDE BY SIDE as ``[m, C]``
(``_side_by_side``: the sum of the block-diagonal matrix's slabs of m rows),
and ONE product of those rows with the block-diagonal ``[C, C]`` multiplies
every block by its own.  At a chunk of 128 the six rounds' two products
stream 16, 16, 8, 16, 32 and 64 rows (the first two whole sub-blocks: the
rows a round under half a sublane tile fills are no whole tiles); doubling
on masked ``C x C`` products streamed 128 rows twice in each of seven.

``e^{-G_s}`` alone overflows (a chunk of 64 at the gate's bound -5 sums to
-320), so ``A`` and ``Aqk`` are formed a sub-block of ``SUB`` = 16 rows at a
time against ONE reference ``R``, the running sum in the sub-block's
MIDDLE: rows carry ``e^{G_t - R}``, columns ``e^{R - G_s}``, both within
``e^{+-8 x 5}`` inside the sub-block (and the columns of earlier sub-blocks
below 1), far from both ends of float32: with the reference at the
sub-block's start a row's factor reaches ``e^{-80}`` and its product with a
small channel of k is no longer a normal number (3.9e-4 of the output at
the bound, found on the CPU; 1e-7 with the middle).  The caller's gate must
keep ``SUB / 2 * max |g| <= 40`` (``kda_safe_gate`` with
``kda_lower_bound`` -5 does; the exponent is clamped there, so a gate that
breaks the bound gives wrong numbers, not infinities).  That is what lets
the inner products be matrix products and not ``[C, C, d_k]`` masks.

Two forms compute it from ONE chunk function (``_chunk``, two-dimensional
``jnp`` a head).  ``_kda_xla``: a ``lax.scan`` over the chunks of the chunk
function mapped over rows and heads, the backward JAX's; the CPU's path and
the one for shapes that do not tile.  On a TPU, where a head's channels are
whole lane tiles and the chunk whole sublane tiles (``_kernels``), a pair of
Pallas kernels under one ``custom_vjp`` (``kda_fwd_c<chunk>`` /
``kda_bwd_c<chunk>``):
a grid step is one chunk of ``_HEADS_A_STEP`` heads of one row, the chunks
in order with the heads' states ``[d_v, d_k]`` float32 (transposed, so that
the decay runs along the lanes) in VMEM scratch; ``G``, the masks, ``A``,
its inverse and ``U`` never reach HBM.  The forward its rule runs keeps the
state every chunk started from (``[rows, heads, chunks, d_v, d_k]`` float32:
the backward recomputes everything else of a chunk from q, k, v, g, beta and
that state, which costs a forward's products again but no second sequential
pass over the row to rebuild the states).  The backward walks the chunks
last to first, carries the state's cotangent, and takes a chunk's
cotangents as ``jax.vjp`` of the chunk function INSIDE the kernel body: one
definition, three uses.  The state, the running sums, ``A``, ``Aqk`` and
the inverse are float32 at full precision (``Precision.HIGHEST``: six MXU
passes a product; the running sum three, ``_sum_where``: its mask is exact
in bfloat16); the five products with the state and with ``U`` take
their operands in the dtype q, k and v came in (bfloat16 in a training
step: one pass) and add up in float32, forward and backward (``_dot_as``).
At ``[1, 8192, 32, 128]`` on a v5e the pair takes 5.8 ms forward and 11.2
backward a call in chunks of 128 (with float32 operands throughout 1.7e-3 of
the recurrence for 4.6e-3: the output's own bfloat16 rounding and the
operands'), 8.5 % of the floor ``benchmark/roofline_kda.scan_passes``
counts.  It took 9.1 / 17.0 ms with the inverse by doubling on masked
``C x C`` products, the running sum as one six-pass product and ``A`` and
``Aqk`` as two products a sub-block against all ``C`` keys (PR 55); step 0
of PR 56 read each change alone on the chip: the inverse block by block
-1.6 / -1.6 ms, the sum in three passes -0.1 / -0.5, one product a
sub-block -1.0 / -3.0, the keys up to the diagonal only -0.6 / -0.8 (a
float32 product of 16 rows costs about half of one of 128: what a product
loads counts beside what it streams).  The inverse's products in bfloat16
would take another 1.1 ms off the forward and move every error by a
twentieth of itself: read, not shipped (PERF.md sections 6 and 7).  No
kernel states ``vmem_limit_bytes``.

Which path a call took is counted in ``ray_tpu_kda_call_geometry_total``.

Round the recurrence stand a layer's elementwise passes, and on the
kernels' path each is ONE Pallas pass each way under one ``custom_vjp``
(PR 64), on the flat arrays ``_call``'s BlockSpecs address: ``[B, S, H * d]``
with a head's 128 channels one lane tile.  That layout is the contract: the
passes write and read exactly it, so no array between the projections and
the out-projection is written by XLA and none is re-laid (a
``[1, 8192, 32, 128]`` array and its ``[1, 8192, 4096]`` reshape do not
share a tiled layout: in ``jnp`` the step copied the float32 array ten times
a layer-row and wrote a head's factor, broadcast over its lanes, to HBM).

``kda_mixer`` is a layer from its projections' results to the recurrence's.
BEFORE the scan (``kda_in_fwd`` / ``kda_in_bwd``, scope ``kda/conv``): the
causal depthwise convolution of the fused q, k, v projection in float32
(``ssm._gc_shifted``'s sublane rotations, the rows a rotation wraps put
right from the tile before: carried in VMEM over the forward's walk of a
row, read from HBM by the backward, which walks a row last tile first and
carries the cotangent's first rows for the taps read the other way), silu,
the rounding to the stream's dtype where ``ssm.causal_conv`` rounds, q and
k to unit length a head (a lane reduction inside the head's own lane tile),
rounded again, and ``g = bound * sigmoid(exp(A_log) (a + dt_bias))`` written
float32.  The same numbers as ``_inputs_xla`` (``ssm.causal_conv`` and
``jnp``) with its roundings in their places: on the chip and interpreted
the forward is equal to the last bit, the gradients to an accumulation
order.  The backward is written out: it reads ``qkv``, ``a`` (its only
residuals) and the four cotangents, recomputes the sum and the norms in
VMEM, writes d ``qkv`` as ONE array (a grid axis over the three column
ranges; the decay's part beside q's) and adds the taps', ``A_log``'s and
``dt_bias``'s gradients up over the walk in float32.  AFTER the scan
(``gated_head_norm``: ``kda_norm_fwd`` / ``kda_norm_bwd``, scope
``kda/norm``): the head norm of ``o`` times the output gate's sigmoid, in
float32 from its reads to the one rounding of each result (equal to the
last bit to what the TPU compiler makes of the ``jnp`` lines, which keeps
the normed head and the sigmoid in float32 inside its fusion).  At
``[1, 8192, 12288]`` + ``[1, 8192, 4096]`` on a v5e (PR 64, step 0) the pass
before takes 0.96 ms forward and 1.91 backward a call (77 % and 56 % of HBM
pace by its bytes; ``_inputs_xla`` 5.7 and 8.8), the pass after 0.34 and
0.52 (72 % and 78 %; ``jnp`` 2.0 and 2.0).  A call takes them where the
scan's kernels take it and a row is whole tiles of 512 tokens
(``_in_tile``), ``jnp`` elsewhere; counted in
``ray_tpu_kda_pass_path_total``.  No kernel states ``vmem_limit_bytes``
(10.6, 6.6, 3.2 and 6.0 MB of scoped VMEM by the compiler's count).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..util import telemetry

F32 = jnp.float32

#: rows of a sub-block of ``A`` / ``Aqk``, which share one reference decay
SUB = 16

#: the largest exponent a column's factor takes: SUB / 2 tokens at the
#: bound -5
_CLAMP = 40.0

#: heads a grid step of the kernels takes, one after the other
_HEADS_A_STEP = 2

_EXACT = jax.lax.Precision.HIGHEST

def _mm_of(dtype):
    """What the products with the state and with ``U`` round their operands
    to: bfloat16 where the call's q, k and v are, else float32."""
    return jnp.bfloat16 if dtype == jnp.bfloat16 else F32


def _dot(a, b, lhs: int = 1, rhs: int = 0):
    """a . b contracting a's axis ``lhs`` with b's axis ``rhs``, float32 at
    full precision."""
    return jax.lax.dot_general(a, b, (((lhs,), (rhs,)), ((), ())),
                               precision=_EXACT, preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _dot_as(a, b, lhs: int, rhs: int, dtype):
    """``_dot`` with both operands rounded to ``dtype`` (float32: as they
    are, at full precision), adding up in float32; the two cotangents are
    products of the same kind, the incoming one rounded alike: written out,
    because what JAX derives multiplies a float32 cotangent with a rounded
    operand."""
    if dtype == F32:
        return _dot(a, b, lhs, rhs)
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (((lhs,), (rhs,)), ((), ())),
                               preferred_element_type=F32)


def _dot_as_fwd(a, b, lhs, rhs, dtype):
    return _dot_as(a, b, lhs, rhs, dtype), (a, b)


def _dot_as_bwd(lhs, rhs, dtype, saved, ct):
    a, b = saved
    da = _dot_as(ct, b, 1, 1 - rhs, dtype) if lhs == 1 \
        else _dot_as(b, ct, 1 - rhs, 1, dtype)
    db = _dot_as(a, ct, 1 - lhs, 0, dtype) if rhs == 0 \
        else _dot_as(ct, a, 0, 1 - lhs, dtype)
    return da, db


_dot_as.defvjp(_dot_as_fwd, _dot_as_bwd)


def _iotas(C: int):
    t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return t, s


def _side_by_side(X, m: int, lo: int = 0):
    """X [C, C], zero outside its diagonal blocks of m -> rows ``lo`` on of
    the blocks, side by side: [m - lo, C] with block i in columns
    [i m, (i + 1) m)."""
    out = X[lo:m]
    for at in range(m, X.shape[0], m):
        out = out + X[at + lo:at + m]
    return out


def _on_the_diagonal(L, m: int, x):
    """``_side_by_side``'s inverse: L [m - lo, C] -> [C, C] with the blocks
    on the diagonal and rows ``lo`` on of each filled (``x``: row index xor
    column index)."""
    C = L.shape[1]
    if L.shape[0] < m:
        L = jnp.concatenate([jnp.zeros((m - L.shape[0], C), F32), L], axis=0)
    if m == C:
        return L
    return jnp.where(x < m, jnp.concatenate([L] * (C // m), axis=0), 0.0)


@jax.custom_vjp
def _tri_inv(A):
    """``(I + A)^{-1}`` for A [C, C] strictly lower triangular, C a power of
    two: blocks of 2 are ``I - A``; a round joins blocks of b to blocks of
    2 b, ``T21 = -T22 A21 T11``, on the rows it fills alone.  The blocks of
    ``m = max(2 b, SUB)`` lie side by side as [m, C] (``_side_by_side``), so
    that ONE product with the block-diagonal [C, C] multiplies every block
    by its own: a round is two products of ``b`` rows (of SUB under half a
    sub-block, whose rows a round fills are no whole sublane tiles) where
    masked full products are two of C, whatever the round joins."""
    C = A.shape[0]
    t, s = _iotas(C)
    x = t ^ s
    # under the diagonal ``x >> shift == 1`` says: same block of 2 b, t in
    # its lower half, s in its upper
    T = (t == s).astype(F32) - jnp.where(x == 1, A, 0.0)
    shift = 1
    while (1 << shift) < C:
        b = 1 << shift
        m = min(max(2 * b, SUB), C)
        lo = b if m == 2 * b else 0
        A21 = _side_by_side(jnp.where((x >> shift) == 1, A, 0.0), m, lo)
        P = _on_the_diagonal(_dot(A21, T), m, x)               # A21 T11
        T21 = _dot(_side_by_side(T, m, lo), P)                  # T22 (A21 T11)
        T = T - _on_the_diagonal(T21, m, x)
        shift += 1
    return T


def _tri_inv_fwd(A):
    T = _tri_inv(A)
    return T, T


def _tri_inv_bwd(T, dT):
    t, s = _iotas(T.shape[0])
    return (jnp.where(s < t, -_dot(T, _dot(dT, T, 1, 1), 0, 0), 0.0),)


_tri_inv.defvjp(_tri_inv_fwd, _tri_inv_bwd)


def _sum_where(mask, x):
    """``mask . x`` for a 0 / 1 mask [C, C] and x [C, d] float32, at full
    precision in three MXU passes: the mask is exact in bfloat16 and x is
    the sum of three bfloat16 parts, so the three one-pass products add up
    to what six passes would (the other three multiply the mask's zero low
    parts)."""
    total = 0.0
    for _ in range(3):  # ray-tpu: noqa[RT506] (traced once)
        part = x.astype(jnp.bfloat16).astype(F32)
        total = total + _dot_as(mask, part, 1, 0, jnp.bfloat16)
        x = x - part
    return total


@jax.custom_vjp
def _running_sum(g):
    """The sum of g [C, d] over the rows up to each row."""
    t, s = _iotas(g.shape[0])
    return _sum_where(s <= t, g)


def _running_sum_bwd(_, dG):
    t, s = _iotas(dG.shape[0])
    return (_sum_where(t <= s, dG),)


_running_sum.defvjp(lambda g: (_running_sum(g), None), _running_sum_bwd)


def _within(q, k, g, beta):
    """What a chunk is before it meets the carried state, all float32 at
    full precision: q (scaled), k, g [C, dk], beta [C, 1] -> (G [C, dk] the
    running sum of g, A [C, C] under the diagonal, Aqk [C, C] on and under
    it).  A sub-block's rows of both come from ONE product of 2 SUB rows
    against the ``cols`` they share, exponentiated as far as the diagonal
    reaches."""
    C = q.shape[0]
    t, s = _iotas(C)
    G = _running_sum(g)
    kb = k * beta
    both = []
    for lo in range(0, C, SUB):  # ray-tpu: noqa[RT506] (traced once)
        mid = lo + SUB // 2 - 1
        R = G[mid:mid + 1]                                          # [1, dk]
        rows = jnp.exp(G[lo:lo + SUB] - R)
        # the keys up to the sub-block's last row: the later ones' columns
        # are over the diagonal
        hi = lo + SUB
        cols = k[:hi] * jnp.exp(jnp.minimum(R - G[:hi], _CLAMP))
        if hi < C:
            cols = jnp.concatenate([cols, jnp.zeros((C - hi, k.shape[1]),
                                                    F32)], axis=0)  # [C, dk]
        both.append(_dot(jnp.concatenate([kb[lo:lo + SUB] * rows,
                                          q[lo:lo + SUB] * rows], axis=0),
                         cols, 1, 1))                               # [2 SUB, C]
    A = jnp.concatenate([b[:SUB] for b in both], axis=0)
    Aqk = jnp.concatenate([b[SUB:] for b in both], axis=0)
    return G, jnp.where(s < t, A, 0.0), jnp.where(s <= t, Aqk, 0.0)


def _chunk(q, k, v, g, beta, St, scale: float, mm=F32):
    """One chunk of one head: q, k, g [C, dk], v [C, dv], beta [C, 1], all
    float32, ``St`` [dv, dk] the TRANSPOSED state the chunk starts from ->
    (o [C, dv], the transposed state it hands on).  C a multiple of SUB and
    a power of two.  ``mm``: what the operands of the five products with
    the state and with ``U`` are rounded to (the inputs' dtype); the
    running sum, ``A``, ``Aqk`` and the inverse are float32 whatever it
    is."""
    C = q.shape[0]
    q = q * scale
    G, A, Aqk = _within(q, k, g, beta)
    e = jnp.exp(G)
    last = G[C - 1:C]                                               # [1, dk]
    U = _dot_as(_tri_inv(A), beta * (v - _dot_as(k * e, St, 1, 1, mm)),
                1, 0, mm)                                           # [C, dv]
    o = _dot_as(q * e, St, 1, 1, mm) + _dot_as(Aqk, U, 1, 0, mm)
    return o, St * jnp.exp(last) + _dot_as(U, k * jnp.exp(last - G), 0, 0,
                                           mm)


# ---------------------------------------------------------------- jnp form

def _kda_xla(q, k, v, g, beta, C: int, scale: float):
    """The chunked form in ``jnp`` over whole chunks: q, k, g [B, S, H, dk],
    v [B, S, H, dv], beta [B, S, H] with C dividing S -> o float32."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    chunks = lambda a: jnp.moveaxis(
        a.astype(F32).reshape(B, S // C, C, H, -1), (1, 3), (0, 2))
    one = jax.vmap(jax.vmap(functools.partial(_chunk, scale=scale,
                                              mm=_mm_of(q.dtype))))

    def step(St, xs):
        o, St = one(*xs, St)
        return St, o

    _, o = jax.lax.scan(step, jnp.zeros((B, H, dv, dk), F32),
                        tuple(chunks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, S, H, dv)


# ------------------------------------------------------- the Pallas kernels
#
# A grid step is one chunk of ``hb`` heads of one row; q, k, v, g and their
# cotangents are addressed flat, [B, S, H * d], a head's channels whole lane
# tiles; beta and its cotangent as columns [B, H / hb, S, hb].  The loops
# over a step's heads unroll while a kernel is traced, so the lint's RT506
# (op-by-op dispatch in a loop) does not apply to them.

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, hb: int,
                dk: int, dv: int, scale: float):
    """o of a chunk; with a ``s_ref`` among ``rest`` the state each head's
    chunk started from is kept for the backward."""
    from jax.experimental import pallas as pl
    *s_ref, state = rest

    @pl.when(pl.program_id(2) == 0)
    def _row_start():
        state[...] = jnp.zeros(state.shape, F32)

    for j in range(hb):  # ray-tpu: noqa[RT506]
        ak, av = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        St = state[j]
        if s_ref:
            s_ref[0][j] = St
        o, St = _chunk(q_ref[:, ak].astype(F32), k_ref[:, ak].astype(F32),
                       v_ref[:, av].astype(F32), g_ref[:, ak],
                       b_ref[:, j:j + 1], St, scale, _mm_of(q_ref.dtype))
        o_ref[:, av] = o.astype(o_ref.dtype)
        state[j] = St


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, dstate, *, hb: int, dk: int,
                dv: int, scale: float):
    """The cotangents of a chunk, the chunks of a row walked last to first;
    ``dstate`` holds the cotangent of the state the chunk hands on."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _row_end():
        dstate[...] = jnp.zeros(dstate.shape, F32)

    for j in range(hb):  # ray-tpu: noqa[RT506]
        ak, av = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        _, pull = jax.vjp(
            functools.partial(_chunk, scale=scale, mm=_mm_of(q_ref.dtype)),
            q_ref[:, ak].astype(F32), k_ref[:, ak].astype(F32),
            v_ref[:, av].astype(F32), g_ref[:, ak], b_ref[:, j:j + 1],
            s_ref[j])
        dq, dk_, dv_, dg, db, dS = pull((do_ref[:, av].astype(F32),
                                         dstate[j]))
        dq_ref[:, ak] = dq.astype(dq_ref.dtype)
        dk_ref[:, ak] = dk_.astype(dk_ref.dtype)
        dv_ref[:, av] = dv_.astype(dv_ref.dtype)
        dg_ref[:, ak] = dg
        db_ref[:, j:j + 1] = db
        dstate[j] = dS


def _call(kernel, name, dims, reverse, extra_in, outs, scale, interpret):
    """A kernel over the grid (row, heads of a step, chunk): q, k, v, g and
    beta first, then ``extra_in`` / ``outs`` as (spec, array or shape)
    pairs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, H, nc, C, dk, dv, hb = dims
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    tokens = lambda w: pl.BlockSpec((None, C, hb * w),
                                    lambda b, h, c: (b, at(c), h))
    specs = {"k": tokens(dk), "v": tokens(dv),
             "col": pl.BlockSpec((None, None, C, hb),
                                 lambda b, h, c: (b, h, at(c), 0)),
             "state": pl.BlockSpec((None, hb, None, dv, dk),
                                   lambda b, h, c: (b, h, at(c), 0, 0))}
    first = ["k", "k", "v", "k", "col"]
    return pl.pallas_call(
        functools.partial(kernel, hb=hb, dk=dk, dv=dv, scale=scale),
        grid=(B, H // hb, nc),
        in_specs=[specs[n] for n in first + [n for n, _ in extra_in]],
        out_specs=[specs[n] for n, _ in outs],
        out_shape=[shape for _, shape in outs],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), F32)],
        interpret=interpret, name=name,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}))


def _laid_out(q, k, v, g, beta, H: int, C: int):
    """(dims, the five arrays every kernel reads first): q, k, v, g flat,
    [B, S, H * d], as the BlockSpecs address them."""
    B, S, _ = q.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    hb = _HEADS_A_STEP if H % _HEADS_A_STEP == 0 else 1
    col = jnp.moveaxis(beta.astype(F32).reshape(B, S, H // hb, hb), 1, 2)
    return (B, H, S // C, C, dk, dv, hb), (q, k, v, g.astype(F32), col)


def _kernel_forward(q, k, v, g, beta, H, C, scale, interpret, keep: bool):
    dims, ins = _laid_out(q, k, v, g, beta, H, C)
    B, _, nc, _, dk, dv, _ = dims
    outs = [("v", jax.ShapeDtypeStruct(v.shape, v.dtype))]
    if keep:
        outs.append(("state", jax.ShapeDtypeStruct((B, H, nc, dv, dk), F32)))
    return _call(_fwd_kernel, f"kda_fwd_c{C}", dims, False, [], outs, scale,
                 interpret)(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _kda_kernels(q, k, v, g, beta, H, C, scale, interpret):
    """The pair on flat arrays: q, k, g [B, S, H * dk], v [B, S, H * dv],
    beta [B, S, H] -> o [B, S, H * dv]."""
    return _kernel_forward(q, k, v, g, beta, H, C, scale, interpret, False)[0]


def _kda_kernels_fwd(q, k, v, g, beta, H, C, scale, interpret):
    o, states = _kernel_forward(q, k, v, g, beta, H, C, scale, interpret,
                                True)
    return o, (q, k, v, g, beta, states)


def _kda_kernels_bwd(H, C, scale, interpret, saved, do):
    q, k, v, g, beta, states = saved
    dims, ins = _laid_out(q, k, v, g, beta, H, C)
    B, _, _, _, _, _, hb = dims
    S = q.shape[1]
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    dq, dk, dv, dg, db = _call(
        _bwd_kernel, f"kda_bwd_c{C}", dims, True,
        [("state", states), ("v", do)],
        [("k", like(q)), ("k", like(k)), ("v", like(v)), ("k", like(ins[3])),
         ("col", jax.ShapeDtypeStruct((B, H // hb, S, hb), F32))],
        scale, interpret)(*ins, states, do)
    return (dq, dk, dv, dg.astype(g.dtype),
            jnp.moveaxis(db, 1, 2).reshape(beta.shape).astype(beta.dtype))


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def _kernels(dk: int, dv: int, interpret: bool) -> bool:
    """Whether the kernels take a call of these heads here: a head's key
    and value channels whole lane tiles (``_scan`` has checked the chunk)."""
    from .attention import LANES, _on_tpu     # at the call: tests steer it
    return bool((interpret or _on_tpu()) and dk % LANES == 0
                and dv % LANES == 0)


def _refuse_a_mesh() -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "kda on a mesh: heads split over tp, and a row split over sp "
            "handing its state on, are not built (ROADMAP M8)")


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "scale",
                                             "kernel", "interpret"))
def _kda(q, k, v, g, beta, *, heads, chunk, scale, kernel, interpret):
    """One traced body for every call site of a shape (a model's six layers
    trace and lower the kernels once).  q, k, v, g [B, S, heads, d], or flat
    [B, S, heads * d] as the kernels address them (``_mixer_inputs`` writes
    them so; the result is then flat too)."""
    with jax.named_scope("kda/scan"):
        S = q.shape[1]
        pad = -S % chunk
        if pad:
            # Tokens that leave the state as it is (k 0, beta 0, no decay)
            # and that nothing before them sees.
            grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                     * (a.ndim - 2))
            q, k, v, g, beta = (grow(a) for a in (q, k, v, g, beta))
        if kernel:
            flat = lambda a: a.reshape(a.shape[:2] + (-1,))
            o = _kda_kernels(flat(q), flat(k), flat(v), flat(g), beta, heads,
                             chunk, scale, interpret).reshape(v.shape)
        else:
            o = _kda_xla(q, k, v, g, beta, chunk, scale)
        return o[:, :S].astype(v.dtype)


def _scan(q, k, v, g, beta, chunk: int, interpret: bool, flat: bool):
    """``kda``; with ``flat`` q, k, v, g are [B, S, H * d] as the kernels
    address them (the caller has asked ``_kernels``) and so is the result."""
    _refuse_a_mesh()
    if chunk % SUB or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is not a power of two of whole "
                         f"sub-blocks of {SUB}")
    B, S, H = beta.shape
    dk, dv = (q.shape[-1] // H, v.shape[-1] // H) if flat \
        else (q.shape[-1], v.shape[-1])
    kernel = flat or _kernels(dk, dv, interpret)
    telemetry.inc("ray_tpu_kda_call_geometry_total", tags={
        "heads": str(H), "dk": str(dk), "dv": str(dv),
        "chunk": str(chunk), "rows": str(B), "seq": str(S),
        "path": "kernel" if kernel else "xla"})
    return _kda(q, k, v, g.astype(F32), beta, heads=H, chunk=chunk,
                scale=float(dk ** -0.5), kernel=kernel, interpret=interpret)


def kda(q, k, v, g, beta, chunk: int = 64, *, interpret: bool = False):
    """The recurrence above over every row, in chunks of ``chunk`` tokens.

    q, k [B, S, H, dk] (k of unit length a head: the caller normalises), v
    [B, S, H, dv], g [B, S, H, dk] float32 log-decays in ``(-80 / SUB, 0]``
    = (-5, 0], beta [B, S, H] in [0, 1].  q is scaled by ``dk ** -0.5``.
    Returns o [B, S, H, dv] in v's dtype.  Every row starts from a zero
    state; a row whose length ``chunk`` does not divide is padded with
    tokens that leave the state alone.  ``chunk`` is a power of two and a
    multiple of ``SUB``.  On a TPU (or with ``interpret``, for the tests)
    and where the shapes tile (``_kernels``) the Pallas pair computes it,
    elsewhere ``jnp``."""
    return _scan(q, k, v, g, beta, chunk, interpret, False)


def chunk_carry(g, chunk: int):
    """Mean over rows, whole chunks, heads and channels of ``exp(sum of g
    over a chunk)``, the share of a state's row that a whole chunk hands on:
    g [B, S, H, dk] float32.  No gradient."""
    B, S = g.shape[:2]
    n = S // chunk
    if not n:
        return jnp.ones((), F32)
    total = jnp.sum(g[:, :n * chunk].astype(F32).reshape(
        (B, n, chunk) + g.shape[2:]), axis=2)
    return jax.lax.stop_gradient(jnp.mean(jnp.exp(total)))


# ------------------------------ between the projections and the scan
#
# What a KDA layer does to its projections' results before the recurrence:
# the causal convolution of q, k and v with its silu, q and k to unit length
# a head, the decay from its projection.  ``_inputs_xla`` is the definition
# (``ssm.causal_conv`` and ``jnp``); ``_inputs_kernels`` one Pallas pass each
# way that reads the projections' results where they lie and writes q, k, v
# and g flat, as ``_call``'s BlockSpecs read them.

#: tokens of a forward grid step of the pass (the backward's: half, twice the
#: live arrays), and the most lanes of one
_IN_ROWS, _IN_LANES = 512, 512


def _unit(x):
    """x / ||x|| over the last axis, in float32, in x's dtype."""
    x32 = x.astype(F32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                                + 1e-6)).astype(x.dtype)


def _inputs_xla(qkv, a, conv_w, A_log, dt_bias, H: int, bound: float):
    """-> q, k, v [B, S, H, D] in qkv's dtype and g [B, S, H, D] float32.
    The definition: its convolution is ``ssm.causal_conv``'s ``jnp`` form
    whatever the shapes (``impl="xla"``), never that op's own kernels."""
    from . import ssm
    B, S, _ = a.shape
    with jax.named_scope("kda/conv"):
        qkv = ssm.causal_conv(qkv, conv_w,
                              jnp.zeros(conv_w.shape[1:], conv_w.dtype),
                              impl="xla")
    with jax.named_scope("kda/gate"):
        q, k, v = (c.reshape(B, S, H, -1) for c in jnp.split(qkv, 3, axis=-1))
        q, k = _unit(q), _unit(k)
        rate = jnp.repeat(jnp.exp(A_log.astype(F32)), a.shape[-1] // H)
        g = bound * jax.nn.sigmoid(
            rate * (a.astype(F32) + dt_bias.astype(F32)))
        return q, k, v, g.reshape(B, S, H, -1)


def _in_tile(S: int, H: int, D: int, K: int, backward: bool):
    """(tokens, channels) of a grid step of the pass, whole heads side by
    side, or None where the shapes do not tile."""
    from .attention import LANES
    from .ssm import _GC_HEAD
    if S % _IN_ROWS or D % LANES or D > _IN_LANES or K > _GC_HEAD:
        return None
    heads = max(n for n in range(1, _IN_LANES // D + 1) if H % n == 0)
    return _IN_ROWS // 2 if backward else _IN_ROWS, heads * D


def _taps(x, before, w):
    """The convolution's sum before its silu on a tile: x [rows, d] float32,
    ``before`` the ``_GC_HEAD`` rows that precede it, w [K, d] float32 (tap
    K - 1 reads the token itself) -> (the sum, added up oldest tap first as
    ``ssm._conv_taps`` does, and ``ssm._gc_shifted``'s pair a tap, oldest
    first)."""
    from .ssm import _GC_HEAD, _gc_shifted
    K = w.shape[0]
    shifted = [_gc_shifted(x, K - 1 - j, before) for j in range(K - 1)]
    acc = head = 0.0
    for j, (whole, first) in enumerate(shifted):  # ray-tpu: noqa[RT506]
        acc, head = acc + whole * w[j], head + first * w[j]
    acc, head = acc + x * w[K - 1], head + x[:_GC_HEAD] * w[K - 1]
    return jnp.concatenate([head, acc[_GC_HEAD:]], axis=0), shifted


def _decay(a_ref, rate_ref, dtb_ref, at):
    """(exp(A_log) a channel, a + dt_bias, sigmoid of their product) of a
    head's lanes ``at`` of the tile, float32."""
    rate, z = rate_ref[:, at], a_ref[:, at].astype(F32) + dtb_ref[:, at]
    return rate, z, jax.nn.sigmoid(rate * z)


# (the loops over a tile's heads and taps unroll while a kernel is traced)

def _in_fwd_kernel(xq_ref, xk_ref, xv_ref, a_ref, wq_ref, wk_ref, wv_ref,
                   rate_ref, dtb_ref, q_ref, k_ref, v_ref, g_ref, tail, *,
                   D: int, bound: float):
    """A tile of the three column ranges of ``qkv`` and of ``a``; ``tail``
    [3, _GC_HEAD, lanes] carries each range's last rows over the walk of a
    row."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _row_start():
        tail[...] = jnp.zeros_like(tail)

    for i, (x_ref, w_ref, o_ref) in enumerate((  # ray-tpu: noqa[RT506]
            (xq_ref, wq_ref, q_ref), (xk_ref, wk_ref, k_ref),
            (xv_ref, wv_ref, v_ref))):
        w = w_ref[...].astype(F32)
        for lo in range(0, x_ref.shape[1], D):  # ray-tpu: noqa[RT506]
            at = slice(lo, lo + D)
            x = x_ref[:, at].astype(F32)
            acc, _ = _taps(x, tail[i, :, at], w[:, at])
            tail[i, :, at] = x[-tail.shape[1]:]
            y = jax.nn.silu(acc).astype(o_ref.dtype)
            o_ref[:, at] = _unit(y) if i < 2 else y
    for lo in range(0, a_ref.shape[1], D):  # ray-tpu: noqa[RT506]
        at = slice(lo, lo + D)
        g_ref[:, at] = bound * _decay(a_ref, rate_ref, dtb_ref, at)[2]


def _in_bwd_kernel(x_ref, xb_ref, dq_ref, dk_ref, dv_ref, a_ref, dg_ref,
                   w_ref, rate_ref, dtb_ref, dx_ref, da_ref, dw_ref,
                   drate_ref, ddtb_ref, after, *, D: int, bound: float):
    """A tile of ONE column range of ``qkv`` (grid axis 0: q, k, v), the
    tiles of a row walked last to first: ``after`` [_GC_HEAD, lanes] carries
    the first rows of the tile after's cotangent of the convolution's sum,
    for the taps read the other way; the rows before the tile come from HBM
    (``xb_ref``).  The decay's part runs beside q's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .ssm import _GC_HEAD
    K, rows = w_ref.shape[0], x_ref.shape[0]
    p, b, s = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    first = (b == 0) & (s == 0)
    row_start = s == pl.num_programs(3) - 1

    @pl.when(s == 0)
    def _row_end():
        after[...] = jnp.zeros_like(after)

    @pl.when(first)
    def _first_tile():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    sum0 = lambda t: jnp.sum(t, axis=0, keepdims=True)
    sum1 = lambda t: jnp.sum(t, axis=-1, keepdims=True)
    w_all = w_ref[...].astype(F32)
    late = jax.lax.broadcasted_iota(jnp.int32, (_GC_HEAD, D), 0)

    def part(ct_ref, unit: bool):
        for lo in range(0, x_ref.shape[1], D):  # ray-tpu: noqa[RT506]
            at = slice(lo, lo + D)
            x, w = x_ref[:, at].astype(F32), w_all[:, at]
            acc, shifted = _taps(x, jnp.where(
                row_start, 0.0, xb_ref[:, at].astype(F32)), w)
            sig = jax.nn.sigmoid(acc)
            dy = ct_ref[:, at].astype(F32)
            if unit:
                y = jax.nn.silu(acc).astype(ct_ref.dtype).astype(F32)
                r = jax.lax.rsqrt(sum1(y * y) + 1e-6)
                dy = dy * r - y * (r * r * r * sum1(dy * y))
                dy = dy.astype(ct_ref.dtype).astype(F32)
            ga = dy * sig * (1.0 + acc * (1.0 - sig))
            end, then = ga[-_GC_HEAD:], after[:, at]
            dx = dx_end = 0.0
            dw = []
            for j, (whole, head) in enumerate(shifted):  # ray-tpu: noqa[RT506]
                d = K - 1 - j
                # the taps read the other way: token t's input reaches t + d
                dx = dx + pltpu.roll(ga, rows - d, 0) * w[j]
                dx_end = dx_end + jnp.where(
                    late >= _GC_HEAD - d, pltpu.roll(then, _GC_HEAD - d, 0),
                    pltpu.roll(end, _GC_HEAD - d, 0)) * w[j]
                dw.append(sum0(whole * ga) + sum0(
                    (head - whole[:_GC_HEAD]) * ga[:_GC_HEAD]))
            dw.append(sum0(x * ga))
            dx_ref[:, at] = (dx + ga * w[K - 1]).astype(dx_ref.dtype)
            dx_ref[rows - _GC_HEAD:, at] = (dx_end + end * w[K - 1]).astype(
                dx_ref.dtype)
            dw_ref[:, at] += jnp.concatenate(dw, axis=0)
            after[:, at] = ga[:_GC_HEAD]

    for i, ct_ref in enumerate((dq_ref, dk_ref, dv_ref)):  # ray-tpu: noqa[RT506]
        pl.when(p == i)(functools.partial(part, ct_ref, i < 2))

    @pl.when(p == 0)
    def _decay_part():
        @pl.when(first)
        def _first_tile():
            drate_ref[...] = jnp.zeros_like(drate_ref)
            ddtb_ref[...] = jnp.zeros_like(ddtb_ref)

        for lo in range(0, a_ref.shape[1], D):  # ray-tpu: noqa[RT506]
            at = slice(lo, lo + D)
            rate, z, sg = _decay(a_ref, rate_ref, dtb_ref, at)
            dz = dg_ref[:, at] * bound * sg * (1.0 - sg)
            da_ref[:, at] = (dz * rate).astype(da_ref.dtype)
            drate_ref[:, at] += sum0(dz * z)
            ddtb_ref[:, at] += sum0(dz * rate)


def _in_call(backward: bool, qkv, a, conv_w, rate, dt_bias, cts, H, bound,
             interpret):
    """One kernel call: the forward's (q, k, v, g), or with the cotangents
    ``cts`` of those four (d qkv, d a, d conv_w [K, 3 F], d rate [1, F],
    d dt_bias [1, F]: the last three float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .ssm import _GC_HEAD
    B, S, F = a.shape
    K, D = conv_w.shape[0], F // H
    rows, lanes = _in_tile(S, H, D, K, backward)
    nC, nS = F // lanes, S // rows
    like = lambda x, dtype=None: jax.ShapeDtypeStruct(x.shape,
                                                      dtype or x.dtype)
    params = lambda *sem: {} if interpret else {
        "compiler_params": pltpu.CompilerParams(dimension_semantics=sem)}
    if not backward:
        # Channels outermost, the tokens of a row in order.
        tile = lambda part: pl.BlockSpec(
            (None, rows, lanes), lambda c, b, s: (b, s, part * nC + c))
        taps = lambda part: pl.BlockSpec((K, lanes),
                                         lambda c, b, s: (0, part * nC + c))
        lane = pl.BlockSpec((1, lanes), lambda c, b, s: (0, c))
        one = like(a, qkv.dtype)
        return pl.pallas_call(
            functools.partial(_in_fwd_kernel, D=D, bound=bound),
            grid=(nC, B, nS),
            in_specs=[tile(0), tile(1), tile(2), tile(0), taps(0), taps(1),
                      taps(2), lane, lane],
            out_specs=[tile(0)] * 4, out_shape=[one, one, one, like(a, F32)],
            scratch_shapes=[pltpu.VMEM((3, _GC_HEAD, lanes), F32)],
            interpret=interpret, name="kda_in_fwd",
            **params("parallel", "arbitrary", "arbitrary"),
        )(qkv, qkv, qkv, a, conv_w, conv_w, conv_w, rate, dt_bias)
    # A column range of qkv outermost, then its channels: the taps' gradient
    # of a column of channels stays in VMEM while every tile of the column
    # adds to it; a row's tiles last to first.  What only one range reads or
    # writes keeps ONE block index through the others' steps (nothing moves
    # while an index stands): a cotangent its first block, what the decay's
    # part wrote its last.
    step = rows // _GC_HEAD
    col = lambda p, c: p * nC + c
    tile = pl.BlockSpec((None, rows, lanes),
                        lambda p, c, b, s: (b, nS - 1 - s, col(p, c)))
    before = pl.BlockSpec(
        (None, _GC_HEAD, lanes), lambda p, c, b, s: (
            b, jnp.maximum((nS - 1 - s) * step - 1, 0), col(p, c)))
    only = lambda i: pl.BlockSpec(
        (None, rows, lanes), lambda p, c, b, s: (
            jnp.where(p == i, b, 0), jnp.where(p == i, nS - 1 - s, nS - 1),
            jnp.where(p == i, c, 0)))
    wrote = pl.BlockSpec(
        (None, rows, lanes), lambda p, c, b, s: (
            jnp.where(p == 0, b, B - 1), jnp.where(p == 0, nS - 1 - s, 0),
            jnp.where(p == 0, c, nC - 1)))
    taps = pl.BlockSpec((K, lanes), lambda p, c, b, s: (0, col(p, c)))
    lane = pl.BlockSpec((1, lanes), lambda p, c, b, s: (
        0, jnp.where(p == 0, c, nC - 1)))
    dq, dk, dv, dg = cts
    return pl.pallas_call(
        functools.partial(_in_bwd_kernel, D=D, bound=bound),
        grid=(3, nC, B, nS),
        in_specs=[tile, before, only(0), only(1), only(2), only(0), only(0),
                  taps, lane, lane],
        out_specs=[tile, wrote, taps, lane, lane],
        out_shape=[like(qkv), like(a), like(conv_w, F32), like(rate),
                   like(rate)],
        scratch_shapes=[pltpu.VMEM((_GC_HEAD, lanes), F32)],
        interpret=interpret, name="kda_in_bwd",
        **params(*("arbitrary",) * 4),
    )(qkv, qkv, dq, dk, dv, a, dg, conv_w, rate, dt_bias)


def _by_channel(A_log, dt_bias, F: int):
    """(exp(A_log) a channel, dt_bias) as rows [1, F] float32."""
    rate = jnp.repeat(jnp.exp(A_log.astype(F32)), F // A_log.shape[0])
    return rate.reshape(1, F), dt_bias.astype(F32).reshape(1, F)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _inputs_kernels(qkv, a, conv_w, A_log, dt_bias, H, bound, interpret):
    """``_inputs_xla``'s numbers with its roundings in their places, flat:
    q, k, v [B, S, H * D] and g [B, S, H * D] float32."""
    return tuple(_in_call(False, qkv, a, conv_w,
                          *_by_channel(A_log, dt_bias, a.shape[-1]), None, H,
                          bound, interpret))


def _inputs_kernels_fwd(qkv, a, conv_w, A_log, dt_bias, H, bound, interpret):
    return (_inputs_kernels(qkv, a, conv_w, A_log, dt_bias, H, bound,
                            interpret), (qkv, a, conv_w, A_log, dt_bias))


def _inputs_kernels_bwd(H, bound, interpret, saved, cts):
    qkv, a, conv_w, A_log, dt_bias = saved
    dqkv, da, dw, drate, ddtb = _in_call(
        True, qkv, a, conv_w, *_by_channel(A_log, dt_bias, a.shape[-1]), cts,
        H, bound, interpret)
    dA = jnp.exp(A_log.astype(F32)) * jnp.sum(drate.reshape(H, -1), axis=-1)
    return (dqkv, da, dw.astype(conv_w.dtype), dA.astype(A_log.dtype),
            ddtb.reshape(dt_bias.shape).astype(dt_bias.dtype))


_inputs_kernels.defvjp(_inputs_kernels_fwd, _inputs_kernels_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "bound", "interpret"))
def _inputs(qkv, a, conv_w, A_log, dt_bias, *, heads, bound, interpret):
    """One traced body for every call site of a shape, as ``_kda``."""
    with jax.named_scope("kda/conv"):
        return _inputs_kernels(qkv, a, conv_w, A_log, dt_bias, heads, bound,
                               interpret)


def _count_pass(which: str, kernel: bool, B, S, H, D) -> None:
    telemetry.inc("ray_tpu_kda_pass_path_total", tags={
        "pass": which, "path": "kernel" if kernel else "xla",
        "heads": str(H), "d": str(D), "rows": str(B), "seq": str(S)})


def kda_mixer(qkv, a, beta, conv_w, A_log, dt_bias, *, bound: float,
              chunk: int = 64, interpret: bool = False):
    """A KDA layer from its projections' results to the recurrence's:
    qkv [B, S, 3 H D] (q, k and v column ranges side by side), a [B, S, H D]
    the decay's projection, beta [B, S, H], conv_w [K, 3 H D], A_log [H],
    dt_bias [H D] -> (o [B, S, H, D] in qkv's dtype, g float32 as the scan
    read it: ``chunk_carry`` takes either shape).

    q, k and v are ``silu`` of the causal depthwise convolution (float32,
    rounded to qkv's dtype), q and k then of unit length a head; ``g = bound
    * sigmoid(exp(A_log) (a + dt_bias))`` in float32.  Where ``kda``'s
    kernels take the call and a row is whole tiles (``_in_tile``), ONE
    Pallas pass each way computes them (``kda_in_fwd`` / ``kda_in_bwd``,
    under ``kda/conv``) and the scan reads what it wrote; elsewhere
    ``_inputs_xla`` under ``kda/conv`` and ``kda/gate``.  Which, is counted
    in ``ray_tpu_kda_pass_path_total``."""
    B, S, F = a.shape
    H = beta.shape[-1]
    D = F // H
    kernel = bool(_kernels(D, D, interpret)
                  and _in_tile(S, H, D, conv_w.shape[0], False))
    _count_pass("inputs", kernel, B, S, H, D)
    if kernel:
        q, k, v, g = _inputs(qkv, a, conv_w, A_log, dt_bias, heads=H,
                             bound=float(bound), interpret=interpret)
    else:
        q, k, v, g = _inputs_xla(qkv, a, conv_w, A_log, dt_bias, H, bound)
    o = _scan(q, k, v, g, beta, chunk, interpret, kernel)
    return o.reshape(B, S, H, D), g


# ---------------------------------- between the scan and the out-projection
#
# The head norm of the recurrence's result times the sigmoid of the layer's
# output gate: ``_gated_norm_xla`` is the definition, ``_gated_norm_kernels``
# one Pallas pass each way on the flat arrays the scan wrote and the
# out-projection reads (in ``jnp`` the norm's [B, S, H, D] and the gate's
# [B, S, H * D] do not share a tiled layout, and XLA writes the float32
# array between them, and a head's factor broadcast over its lanes, to HBM).
# Float32 from the two reads to the one rounding of each result: the normed
# head and the sigmoid are not rounded before their product, as they are not
# inside the fusion the TPU compiler makes of the ``jnp`` form (on the CPU
# that form rounds both, a step of bfloat16 apart on a third of the entries).

def _gated_norm_xla(o, gate, w, eps: float):
    """o [B, S, H, D], gate [B, S, H * D], w [D] -> [B, S, H * D] in o's
    dtype: ``rms_norm`` a head, rounded, times the rounded sigmoid."""
    from .norms import rms_norm
    with jax.named_scope("kda/norm"):
        n = rms_norm(o, w, eps).reshape(gate.shape)
        return n * jax.nn.sigmoid(gate.astype(F32)).astype(o.dtype)


def _normed(o_ref, gate_ref, at, eps):
    """A head's lanes ``at`` of a tile, float32: (x, 1 / rms(x), x / rms(x),
    the gate's sigmoid)."""
    x = o_ref[:, at].astype(F32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x, r, x * r, jax.nn.sigmoid(gate_ref[:, at].astype(F32))


def _norm_fwd_kernel(o_ref, gate_ref, w_ref, y_ref, *, D: int, eps: float):
    for lo in range(0, o_ref.shape[1], D):  # ray-tpu: noqa[RT506]
        at = slice(lo, lo + D)
        _, _, xr, sg = _normed(o_ref, gate_ref, at, eps)
        y_ref[:, at] = (xr * w_ref[:, at] * sg).astype(y_ref.dtype)


def _norm_bwd_kernel(o_ref, gate_ref, w_ref, dy_ref, do_ref, dgate_ref,
                     dw_ref, *, D: int, eps: float):
    """d o, d gate, and the weight's gradient a lane added up over every
    tile of a column of channels."""
    from jax.experimental import pallas as pl

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _first_tile():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for lo in range(0, o_ref.shape[1], D):  # ray-tpu: noqa[RT506]
        at = slice(lo, lo + D)
        x, r, xr, sg = _normed(o_ref, gate_ref, at, eps)
        dy, w = dy_ref[:, at].astype(F32), w_ref[:, at]
        dgate_ref[:, at] = (dy * (xr * w) * sg * (1.0 - sg)).astype(
            dgate_ref.dtype)
        dn = dy * sg
        dw_ref[:, at] += jnp.sum(dn * xr, axis=0, keepdims=True)
        dxr = dn * w
        back = jnp.mean(dxr * x, axis=-1, keepdims=True) * (r * r * r)
        do_ref[:, at] = (dxr * r - x * back).astype(do_ref.dtype)


def _norm_call(backward: bool, o, gate, w, dy, H, eps, interpret):
    """The forward's result [B, S, F], or (d o, d gate, d w [1, F] float32:
    a head a column)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, S, F = o.shape
    D = F // H
    rows, lanes = _in_tile(S, H, D, 1, False)
    tile = pl.BlockSpec((None, rows, lanes), lambda c, b, s: (b, s, c))
    lane = pl.BlockSpec((1, lanes), lambda c, b, s: (0, c))
    w = jnp.tile(w.astype(F32), H).reshape(1, F)
    like = jax.ShapeDtypeStruct(o.shape, o.dtype)
    kernel, ins, outs, shapes = _norm_fwd_kernel, (o, gate, w), tile, like
    if backward:
        kernel, ins = _norm_bwd_kernel, (o, gate, w, dy)
        outs = [tile, tile, lane]
        shapes = [like, jax.ShapeDtypeStruct(gate.shape, gate.dtype),
                  jax.ShapeDtypeStruct((1, F), F32)]
    return pl.pallas_call(
        functools.partial(kernel, D=D, eps=eps), grid=(F // lanes, B,
                                                       S // rows),
        in_specs=[tile, tile, lane] + ([tile] if backward else []),
        out_specs=outs,
        out_shape=shapes, interpret=interpret,
        name="kda_norm_bwd" if backward else "kda_norm_fwd",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"))}),
    )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gated_norm_kernels(o, gate, w, H, eps, interpret):
    return _norm_call(False, o, gate, w, None, H, eps, interpret)


def _gated_norm_kernels_fwd(o, gate, w, H, eps, interpret):
    return _gated_norm_kernels(o, gate, w, H, eps, interpret), (o, gate, w)


def _gated_norm_kernels_bwd(H, eps, interpret, saved, dy):
    o, gate, w = saved
    do, dgate, dw = _norm_call(True, o, gate, w, dy, H, eps, interpret)
    return do, dgate, jnp.sum(dw.reshape(H, -1), axis=0).astype(w.dtype)


_gated_norm_kernels.defvjp(_gated_norm_kernels_fwd, _gated_norm_kernels_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "interpret"))
def _gated_norm(o, gate, w, *, heads, eps, interpret):
    """One traced body for every call site of a shape, as ``_kda``."""
    with jax.named_scope("kda/norm"):
        return _gated_norm_kernels(o.reshape(gate.shape), gate, w, heads,
                                   eps, interpret)


def gated_head_norm(o, gate, w, eps: float, *, interpret: bool = False):
    """What a KDA layer hands its out-projection: o [B, S, H, D] (the
    recurrence's result) to unit RMS a head times w [D], rounded to o's
    dtype, times the sigmoid (float32, rounded) of gate [B, S, H * D] ->
    [B, S, H * D].  One Pallas pass each way (``kda_norm_fwd`` /
    ``kda_norm_bwd``, under ``kda/norm``) where ``kda_mixer`` takes its own,
    ``jnp`` elsewhere; counted beside it."""
    B, S, H, D = o.shape
    kernel = bool(_kernels(D, D, interpret) and _in_tile(S, H, D, 1, False))
    _count_pass("norm", kernel, B, S, H, D)
    if not kernel:
        return _gated_norm_xla(o, gate, w, eps)
    return _gated_norm(o, gate, w, heads=H, eps=float(eps),
                       interpret=interpret)
