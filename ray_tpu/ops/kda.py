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


def _laid_out(q, k, v, g, beta, C: int):
    """(dims, the five arrays every kernel reads first)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    hb = _HEADS_A_STEP if H % _HEADS_A_STEP == 0 else 1
    flat = lambda a: a.reshape(B, S, -1)
    col = jnp.moveaxis(beta.astype(F32).reshape(B, S, H // hb, hb), 1, 2)
    return (B, H, S // C, C, dk, dv, hb), (
        flat(q), flat(k), flat(v), flat(g.astype(F32)), col)


def _kernel_forward(q, k, v, g, beta, C, scale, interpret, keep: bool):
    dims, ins = _laid_out(q, k, v, g, beta, C)
    B, H, nc, _, dk, dv, _ = dims
    outs = [("v", jax.ShapeDtypeStruct(ins[2].shape, v.dtype))]
    if keep:
        outs.append(("state", jax.ShapeDtypeStruct((B, H, nc, dv, dk), F32)))
    out = _call(_fwd_kernel, f"kda_fwd_c{C}", dims, False, [], outs, scale,
                interpret)(*ins)
    return [out[0].reshape(v.shape)] + list(out[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_kernels(q, k, v, g, beta, C, scale, interpret):
    return _kernel_forward(q, k, v, g, beta, C, scale, interpret, False)[0]


def _kda_kernels_fwd(q, k, v, g, beta, C, scale, interpret):
    o, states = _kernel_forward(q, k, v, g, beta, C, scale, interpret, True)
    return o, (q, k, v, g, beta, states)


def _kda_kernels_bwd(C, scale, interpret, saved, do):
    q, k, v, g, beta, states = saved
    dims, ins = _laid_out(q, k, v, g, beta, C)
    B, H, _, _, _, _, hb = dims
    S = q.shape[1]
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    dq, dk, dv, dg, db = _call(
        _bwd_kernel, f"kda_bwd_c{C}", dims, True,
        [("state", states), ("v", do)],
        [("k", like(ins[0])), ("k", like(ins[1])), ("v", like(ins[2])),
         ("k", like(ins[3])),
         ("col", jax.ShapeDtypeStruct((B, H // hb, S, hb), F32))],
        scale, interpret)(*ins, states, do.reshape(ins[2].shape))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype),
            jnp.moveaxis(db, 1, 2).reshape(beta.shape).astype(beta.dtype))


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def _kernels(q, v, interpret: bool) -> bool:
    """Whether the kernels take a call of these shapes here: a head's key
    and value channels whole lane tiles (``kda`` has checked the chunk)."""
    from .attention import LANES, _on_tpu     # at the call: tests steer it
    return bool((interpret or _on_tpu()) and q.shape[-1] % LANES == 0
                and v.shape[-1] % LANES == 0)


def _refuse_a_mesh() -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "kda on a mesh: heads split over tp, and a row split over sp "
            "handing its state on, are not built (ROADMAP M8)")


@functools.partial(jax.jit, static_argnames=("chunk", "scale", "kernel",
                                             "interpret"))
def _kda(q, k, v, g, beta, *, chunk, scale, kernel, interpret):
    """One traced body for every call site of a shape (a model's six layers
    trace and lower the kernels once)."""
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
            o = _kda_kernels(q, k, v, g, beta, chunk, scale, interpret)
        else:
            o = _kda_xla(q, k, v, g, beta, chunk, scale)
        return o[:, :S].astype(v.dtype)


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
    _refuse_a_mesh()
    if chunk % SUB or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is not a power of two of whole "
                         f"sub-blocks of {SUB}")
    B, S, H, dk = q.shape
    kernel = _kernels(q, v, interpret)
    telemetry.inc("ray_tpu_kda_call_geometry_total", tags={
        "heads": str(H), "dk": str(dk), "dv": str(v.shape[-1]),
        "chunk": str(chunk), "rows": str(B), "seq": str(S),
        "path": "kernel" if kernel else "xla"})
    return _kda(q, k, v, g.astype(F32), beta, chunk=chunk,
                scale=float(dk ** -0.5), kernel=kernel, interpret=interpret)


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
