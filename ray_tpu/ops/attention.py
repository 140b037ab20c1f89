"""Causal (GQA) attention: pallas flash kernels + jnp reference.

FlashAttention-2 on TPU, forward *and* backward as pallas kernels:

- Every kernel walks a grid (B*H, steps) over the (q block, k block) pairs
  that hold a visible element, and no others: ``block_schedule`` lists them
  at trace time from the shapes, the blocks, ``q_offset`` and ``causal``, and
  the table reaches the kernels and their index maps by scalar prefetch.
  Under a causal mask that is the triangle (36 steps of 64 at 4,096 tokens
  and 512 x 512 blocks); ``causal=False`` is the same kernels under the full
  rectangle.  Blocks above the diagonal are not grid steps at all.  Every
  causal step masks its tile, also the *interior* ones the diagonal does not
  cross (there the mask adds 0.0): on the v5e the mask hides behind the MXU,
  and a second, unmasked step body measured no faster (PERF.md, PR 28).
  The table is one packed int32 a step, so SMEM holds it at any length the
  kernels would be asked for (131 KB at 128k tokens).
- The grid is ("parallel", "arbitrary"): only B*H splits across the cores of
  a two-core chip (v4, v5p), so a call with one or two heads a device no
  longer spreads its q blocks over both.  The v5e has one core.
- Forward blocks over BOTH sequence axes, the steps of one Q block in a row
  with K ascending ("arbitrary" semantics) so pallas double-buffers K/V
  block DMAs while the MXU works.  Online softmax state (running max m,
  denominator l, unnormalized accumulator) lives in VMEM scratch carried
  across K blocks; the [Sq, Sk] score matrix never exists in HBM.  The
  log-sum-exp is written out as a residual (broadcast over the 128-lane
  minor dim, the TPU-friendly layout the jax flash kernel also uses).
- Backward is two kernels: dq (the same walk as the forward, accumulating
  dq for a resident Q block) and dk/dv (K-major: the steps of one K/V block
  in a row with Q ascending, accumulating dk/dv for the resident block).
  Both recompute probabilities from the saved LSE — one exp, no second
  softmax pass — with fp32 accumulation and bf16 MXU inputs.
- GQA is native: the K/V index maps collapse query heads onto their shared
  KV head; dk/dv are emitted per query head and group-summed outside only
  when kv_heads < heads.

``q_offset`` shifts query positions for causal masking so sequence-sharded
callers (ring attention) can flash-attend a mid-sequence Q shard.
``window`` narrows the triangle to a band (a key is visible iff
``0 <= t - s < window``): the pairs left of the band leave the grid, the
mask gains the band's lower edge, and the three kernels carry the window in
their names (``flash_fwd_w2048``).  With ``window=None`` nothing changes.

Design provenance (patterns, not code): the reference delegates attention to
engines (SURVEY §2.4 SP/CP row — no in-repo kernel); the block/layout recipe
follows jax.experimental.pallas.ops.tpu.flash_attention (LSE lane broadcast,
dual-axis blocking); the prefetched table of visible blocks is how upstream's
splash attention walks a sparse mask.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128


def reference_attention(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        q_offset: int = 0, window: Optional[int] = None):
    """Plain-jnp attention. q: [B, H, Sq, D]; k/v: [B, Hkv, Sk, D].

    ``q_offset`` shifts query positions for causal masking (used by
    sequence-sharded callers where the local Q block starts mid-sequence).
    ``window`` (with ``causal``) keeps the keys with ``0 <= t - s < window``.
    """
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if Hkv != H:
        group = H // Hkv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Sk)
        mask = qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _bcast_lanes(x128, n):
    """[rows, 128] lane-broadcast value -> [rows, n]."""
    if n == LANES:
        return x128
    if n % LANES == 0:
        return jnp.tile(x128, (1, n // LANES))
    if n < LANES:
        return x128[:, :n]
    raise NotImplementedError(f"n={n} not a multiple of {LANES}")


# Rows of a block schedule, and the kinds of step.
QI, KI, KIND, FIRST, LAST = range(5)
INTERIOR, DIAGONAL, EMPTY = range(3)
# A step as the kernels read it, one int32: qi | ki | run | first | last.
_BLOCK_BITS = 14
_KI_SHIFT, _QI_SHIFT = 3, 3 + _BLOCK_BITS
_BLOCK_MASK = (1 << _BLOCK_BITS) - 1
_LAST_BIT, _FIRST_BIT, _RUN_BIT = 1, 2, 4


def block_schedule(Sq, Sk, block_q, block_k, q_offset=0, causal=True,
                   major="q", window=None):
    """The grid steps of one (batch, head): int32 [5, steps], rows QI, KI,
    KIND, FIRST, LAST.

    One step for every (q block, k block) pair that holds a visible element.
    INTERIOR: every element is visible, no mask is needed.  DIAGONAL: the
    causal diagonal crosses the pair, or the lower edge of the band does
    (``window``: a key is visible iff ``0 <= t - s < window``, so the pairs
    left of the band are no steps either: 70 steps a head at 8,192 / 512 /
    512 and a window of 2,048, where the triangle has 136).  ``major="q"`` walks q blocks with k
    ascending inside each (forward, dq: the q block is resident);
    ``major="k"`` walks k blocks with q ascending (dk/dv).  FIRST and LAST
    mark the steps that open and close a resident block.  A resident block
    that sees nothing (a k block beyond every q row) still gets one EMPTY
    step, so that its output is written (as zeros).

    The kinds are the static count of what a call does (tests and PERF.md
    read them); the kernels only tell EMPTY from the rest."""
    nq, nk = Sq // block_q, Sk // block_k
    steps = []
    for a in range(nq if major == "q" else nk):
        run = []
        for b in range(nk if major == "q" else nq):
            qi, ki = (a, b) if major == "q" else (b, a)
            # The last q row against the first k column, then the reverse.
            if causal and (qi + 1) * block_q - 1 + q_offset < ki * block_k:
                continue
            # The first q row against the last k column: the nearest pair.
            near = qi * block_q + q_offset - ((ki + 1) * block_k - 1)
            far = (qi + 1) * block_q - 1 + q_offset - ki * block_k
            if window is not None and near >= window:
                continue
            whole = (not causal or near >= 0) and (window is None
                                                   or far < window)
            run.append([qi, ki, INTERIOR if whole else DIAGONAL, 0, 0])
        if not run:
            run = [[a, 0, EMPTY, 0, 0] if major == "q"
                   else [nq - 1, a, EMPTY, 0, 0]]
        run[0][FIRST] = run[-1][LAST] = 1
        steps += run
    return np.array(steps, np.int32).T


def _packed_schedule(*args):
    """``block_schedule`` as the scalar-prefetch operand: int32 [steps]."""
    sched = block_schedule(*args)
    if max(sched[QI].max(), sched[KI].max()) > _BLOCK_MASK:
        raise ValueError(f"more than {_BLOCK_MASK + 1} blocks a side")
    return (sched[QI] << _QI_SHIFT | sched[KI] << _KI_SHIFT
            | (sched[KIND] != EMPTY) * _RUN_BIT
            | sched[FIRST] * _FIRST_BIT | sched[LAST] * _LAST_BIT
            ).astype(np.int32)


def _step_qi(step):
    return step >> _QI_SHIFT


def _step_ki(step):
    return (step >> _KI_SHIFT) & _BLOCK_MASK


def _causal_mask_bias(s_shape, qi, bq, ki, bk, q_offset, window=None):
    row = jax.lax.broadcasted_iota(jnp.int32, s_shape, 0) + qi * bq + q_offset
    col = jax.lax.broadcasted_iota(jnp.int32, s_shape, 1) + ki * bk
    visible = col <= row
    if window is not None:
        # A row that sees nothing of its first tile takes MASK_VALUE as its
        # running max there; the first visible score then scales that
        # tile's sums by exp(MASK_VALUE - max) = 0.
        visible &= row - col < window
    return jnp.where(visible, 0.0, MASK_VALUE)


# ---------------------------------------------------------------- forward

def _fwd_kernel(sched_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, causal, scale, block_q, block_k, q_offset,
                window=None):
    # lse_ref is None when the caller doesn't need residuals (inference).
    from jax.experimental import pallas as pl

    step = sched_ref[pl.program_id(1)]
    qi, ki = _step_qi(step), _step_ki(step)

    @pl.when(step & _FIRST_BIT != 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(step & _RUN_BIT != 0)
    def _step():
        q = q_ref[0]                                   # [bq, D]
        k = k_ref[0]                                   # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            s = s + _causal_mask_bias(s.shape, qi, block_q, ki, block_k,
                                      q_offset, window)
        m_prev = m_scr[...]                            # [bq, 128]
        l_prev = l_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _bcast_lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)               # [bq, 128]
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_scr[...] = m_next
        v = v_ref[0]
        pv = jax.lax.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * _bcast_lanes(alpha, acc_scr.shape[1]) \
            + pv

    @pl.when(step & _LAST_BIT != 0)
    def _finish():
        l = l_scr[...]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0] = (acc_scr[...]
                    * _bcast_lanes(l_inv, acc_scr.shape[1])
                    ).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_scr[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))


def _kernel_name(base, window):
    """A windowed call carries its window in its name, so that a device
    trace tells it from a full-causal call (``flash_fwd_w2048``)."""
    return base if window is None else f"{base}_w{window}"


def _flash_forward(q, k, v, causal, scale, block_q, block_k, q_offset,
                   interpret, *, need_lse, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    if H % Hkv:
        raise ValueError(f"H={H} not divisible by Hkv={Hkv}")
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"seq ({Sq},{Sk}) not divisible by blocks ({block_q},{block_k})")
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    sched = _packed_schedule(Sq, Sk, block_q, block_k, q_offset, causal, "q",
                             window)

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * Hkv, Sk, D)
    vr = v.reshape(B * Hkv, Sk, D)

    q_index, kv_index, _ = _index_maps(H, Hkv)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, q_offset=q_offset, window=window)

    out_specs = [pl.BlockSpec((1, block_q, D), q_index)]
    out_shape = [jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((1, block_q, LANES), q_index))
        out_shape.append(
            jax.ShapeDtypeStruct((B * H, Sq, LANES), jnp.float32))
    else:
        # No LSE output at all: skip ~B*H*Sq*128 fp32 of dead HBM writes.
        with_lse = kernel

        def kernel(sched, q, k, v, o, *scratch):
            return with_lse(sched, q, k, v, o, None, *scratch)

    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, sched.size),
            in_specs=[
                pl.BlockSpec((1, block_q, D), q_index),
                pl.BlockSpec((1, block_k, D), kv_index),
                pl.BlockSpec((1, block_k, D), kv_index),
            ],
            out_specs=out_specs,
            scratch_shapes=[
                _vmem((block_q, LANES), jnp.float32),
                _vmem((block_q, LANES), jnp.float32),
                _vmem((block_q, D), jnp.float32),
            ]),
        out_shape=out_shape,
        interpret=interpret,
        name=_kernel_name("flash_fwd", window),
        **_compiler_params(interpret),
    )(sched, qr, kr, vr)
    out = res[0].reshape(B, H, Sq, D)
    if not need_lse:
        return out, None
    return out, res[1][..., 0].reshape(B, H, Sq)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _index_maps(H, Hkv):
    """Index maps (q-shaped, k/v, dk/dv) of a grid (B*H, steps) whose blocks
    come from the prefetched schedule.  Query heads fold onto their shared
    key head; dk/dv are per query head."""
    group = H // Hkv

    def q_index(bh, t, sched):
        return (bh, _step_qi(sched[t]), 0)

    def kv_index(bh, t, sched):
        return ((bh // H) * Hkv + (bh % H) // group, _step_ki(sched[t]), 0)

    def dkv_index(bh, t, sched):
        return (bh, _step_ki(sched[t]), 0)

    return q_index, kv_index, dkv_index


def _compiler_params(interpret):
    from jax.experimental.pallas import tpu as pltpu
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))}


# ---------------------------------------------------------------- backward

def _dq_kernel(sched_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, dq_scr, *, causal, scale, block_q, block_k, q_offset,
               window=None):
    from jax.experimental import pallas as pl

    step = sched_ref[pl.program_id(1)]
    qi, ki = _step_qi(step), _step_ki(step)

    @pl.when(step & _FIRST_BIT != 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(step & _RUN_BIT != 0)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                               # [bq, 128]
        di = di_ref[0]                                 # [bq, 128]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = s + _causal_mask_bias(s.shape, qi, block_q, ki, block_k,
                                      q_offset, window)
        p = jnp.exp(s - _bcast_lanes(lse, s.shape[1]))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _bcast_lanes(di, s.shape[1])) * scale
        dq_scr[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    @pl.when(step & _LAST_BIT != 0)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(sched_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal, scale, block_q, block_k, q_offset,
                window=None):
    from jax.experimental import pallas as pl

    step = sched_ref[pl.program_id(1)]
    qi, ki = _step_qi(step), _step_ki(step)

    @pl.when(step & _FIRST_BIT != 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(step & _RUN_BIT != 0)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        di = di_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            s = s + _causal_mask_bias(s.shape, qi, block_q, ki, block_k,
                                      q_offset, window)
        p = jnp.exp(s - _bcast_lanes(lse, s.shape[1]))
        dv_scr[...] += jax.lax.dot(
            p.T.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _bcast_lanes(di, s.shape[1])) * scale
        dk_scr[...] += jax.lax.dot(
            ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32)

    @pl.when(step & _LAST_BIT != 0)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, dout, causal, scale, block_q, block_k,
                    q_offset, interpret, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)

    # delta_i = rowsum(dO * O): one fused elementwise+reduce pass in XLA.
    di = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * Hkv, Sk, D)
    vr = v.reshape(B * Hkv, Sk, D)
    dor = dout.reshape(B * H, Sq, D)
    # LSE/delta residuals broadcast over the lane dim (layout-friendly).
    lser = jnp.broadcast_to(lse.reshape(B * H, Sq)[..., None],
                            (B * H, Sq, LANES))
    dir_ = jnp.broadcast_to(di.reshape(B * H, Sq)[..., None],
                            (B * H, Sq, LANES))

    q_index, kv_index, dkv_index = _index_maps(H, Hkv)
    in_specs = [
        pl.BlockSpec((1, block_q, D), q_index),
        pl.BlockSpec((1, block_k, D), kv_index),
        pl.BlockSpec((1, block_k, D), kv_index),
        pl.BlockSpec((1, block_q, D), q_index),
        pl.BlockSpec((1, block_q, LANES), q_index),
        pl.BlockSpec((1, block_q, LANES), q_index),
    ]
    static = dict(causal=causal, scale=scale, block_q=block_q,
                  block_k=block_k, q_offset=q_offset, window=window)

    # ---- dq: Q block resident, K/V blocks stream (the forward's walk).
    sched = _packed_schedule(Sq, Sk, block_q, block_k, q_offset, causal, "q",
                             window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, sched.size),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, D), q_index),
            scratch_shapes=[_vmem((block_q, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=interpret,
        name=_kernel_name("flash_dq", window),
        **_compiler_params(interpret),
    )(sched, qr, kr, vr, dor, lser, dir_).reshape(B, H, Sq, D)

    # ---- dk/dv: K/V block resident, Q blocks stream (K-major walk).
    # Emitted per *query* head; group-summed below when GQA.
    sched = _packed_schedule(Sq, Sk, block_q, block_k, q_offset, causal, "k",
                             window)
    dkv_dtype = jnp.float32 if group > 1 else q.dtype
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, sched.size),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_k, D), dkv_index),
                pl.BlockSpec((1, block_k, D), dkv_index),
            ],
            scratch_shapes=[
                _vmem((block_k, D), jnp.float32),
                _vmem((block_k, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sk, D), dkv_dtype),
            jax.ShapeDtypeStruct((B * H, Sk, D), dkv_dtype),
        ],
        interpret=interpret,
        name=_kernel_name("flash_dkv", window),
        **_compiler_params(interpret),
    )(sched, qr, kr, vr, dor, lser, dir_)

    dk = dk.reshape(B, H, Sk, D)
    dv = dv.reshape(B, H, Sk, D)
    if group > 1:
        dk = dk.reshape(B, Hkv, group, Sk, D).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(B, Hkv, group, Sk, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------- wrapper

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, q_offset, interpret,
           window):
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                            q_offset, interpret, need_lse=False,
                            window=window)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, q_offset, interpret,
               window):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              q_offset, interpret, need_lse=True,
                              window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, q_offset, interpret, window,
               res, dout):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, dout, causal, scale, block_q,
                           block_k, q_offset, interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 512, q_offset: int = 0,
                    interpret: bool = False, window: Optional[int] = None):
    """Pallas flash attention (fwd + bwd kernels) with custom VJP.
    q: [B, H, Sq, D]; k/v: [B, Hkv, Sk, D].  ``window``: with ``causal``,
    a key is visible iff ``0 <= t - s < window``."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash(q, k, v, causal, scale, block_q, block_k, q_offset,
                  interpret, window)


def _on_tpu() -> bool:
    """The platform JAX reports.  A backend that fails to initialize
    raises here: a broken chip must not read as "not on TPU"."""
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
              impl: Optional[str] = None, mesh=None,
              window: Optional[int] = None):
    """Dispatching entry point: pallas flash on TPU, reference elsewhere.

    ``mesh`` is the SPMD mesh q/k/v are laid out on inside a GSPMD
    program.  A Mosaic kernel cannot be partitioned automatically, so on
    a mesh of more than one device the flash kernel runs as a shard_map
    island: batch over (dp, fsdp), heads over tp, the sequence whole."""
    if impl is None:
        impl = "flash" if _on_tpu() else "reference"
    if impl == "reference":
        return reference_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)
    fn = functools.partial(flash_attention, causal=causal, scale=scale,
                           interpret=impl == "flash_interpret",
                           window=window)
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import (AXIS_DATA, AXIS_FSDP, AXIS_SEQ,
                                     AXIS_TENSOR)
        if mesh.shape.get(AXIS_SEQ, 1) > 1:
            raise ValueError(
                "flash attention needs the whole sequence on each device; "
                "use attention_impl='ring' or 'ulysses' on an sp mesh")
        spec = P((AXIS_DATA, AXIS_FSDP), AXIS_TENSOR, None, None)
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    return fn(q, k, v)
