"""Causal (GQA) attention: pallas flash kernels + jnp reference.

FlashAttention-2 on TPU, forward *and* backward as pallas kernels:

- Forward blocks over BOTH sequence axes — grid (B*H, Sq/bq, Sk/bk) with the
  K/V axis innermost ("arbitrary" semantics) so pallas double-buffers K/V
  block DMAs while the MXU works.  Online softmax state (running max m,
  denominator l, unnormalized accumulator) lives in VMEM scratch carried
  across K blocks; the [Sq, Sk] score matrix never exists in HBM.  The
  log-sum-exp is written out as a residual (broadcast over the 128-lane
  minor dim, the TPU-friendly layout the jax flash kernel also uses).
- Backward is two kernels: dq (grid over K blocks innermost, accumulating
  dq for a resident Q block) and dk/dv (grid over Q blocks innermost,
  accumulating dk/dv for a resident K/V block).  Both recompute probabilities
  from the saved LSE — one exp, no second softmax pass — with fp32
  accumulation and bf16 MXU inputs.
- Causal block-skipping: blocks strictly above the diagonal are predicated
  out with pl.when and their K/V DMAs are redirected to block 0 (the next
  useful block), so the skipped half of the grid costs neither FLOPs nor
  bandwidth.
- GQA is native: the K/V index maps collapse query heads onto their shared
  KV head; dk/dv are emitted per query head and group-summed outside only
  when kv_heads < heads.

``q_offset`` shifts query positions for causal masking so sequence-sharded
callers (ring attention) can flash-attend a mid-sequence Q shard.

Design provenance (patterns, not code): the reference delegates attention to
engines (SURVEY §2.4 SP/CP row — no in-repo kernel); the block/layout recipe
follows jax.experimental.pallas.ops.tpu.flash_attention (LSE lane broadcast,
dual-axis grid, prefetch-redirect on skipped causal blocks).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128


def reference_attention(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        q_offset: int = 0):
    """Plain-jnp attention. q: [B, H, Sq, D]; k/v: [B, Hkv, Sk, D].

    ``q_offset`` shifts query positions for causal masking (used by
    sequence-sharded callers where the local Q block starts mid-sequence).
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


def _visible(qi, bq, ki, bk, q_offset):
    """Causal: does q block qi see any of k block ki?"""
    return (qi + 1) * bq - 1 + q_offset >= ki * bk


def _causal_mask_bias(s_shape, qi, bq, ki, bk, q_offset):
    row = jax.lax.broadcasted_iota(jnp.int32, s_shape, 0) + qi * bq + q_offset
    col = jax.lax.broadcasted_iota(jnp.int32, s_shape, 1) + ki * bk
    return jnp.where(col <= row, 0.0, MASK_VALUE)


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, nk, q_offset):
    # lse_ref is None when the caller doesn't need residuals (inference).
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    run = True if not causal else _visible(qi, block_q, ki, block_k, q_offset)

    @pl.when(run)
    def _step():
        q = q_ref[0]                                   # [bq, D]
        k = k_ref[0]                                   # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            s = s + _causal_mask_bias(s.shape, qi, block_q, ki, block_k,
                                      q_offset)
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

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0] = (acc_scr[...]
                    * _bcast_lanes(l_inv, acc_scr.shape[1])
                    ).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_scr[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))


def _flash_forward(q, k, v, causal, scale, block_q, block_k, q_offset,
                   interpret, *, need_lse):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    if H % Hkv:
        raise ValueError(f"H={H} not divisible by Hkv={Hkv}")
    group = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"seq ({Sq},{Sk}) not divisible by blocks ({block_q},{block_k})")
    nq, nk = Sq // block_q, Sk // block_k

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * Hkv, Sk, D)
    vr = v.reshape(B * Hkv, Sk, D)

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        row = (bh // H) * Hkv + (bh % H) // group
        if causal:
            ki = jnp.where(
                _visible(qi, block_q, ki, block_k, q_offset), ki, 0)
        return (row, ki, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, nk=nk, q_offset=q_offset)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    out_specs = [pl.BlockSpec((1, block_q, D), q_index)]
    out_shape = [jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((1, block_q, LANES), q_index))
        out_shape.append(
            jax.ShapeDtypeStruct((B * H, Sq, LANES), jnp.float32))
    else:
        # No LSE output at all: skip ~B*H*Sq*128 fp32 of dead HBM writes.
        kernel = functools.partial(
            lambda q, k, v, o, m, l, a, *, _k: _k(q, k, v, o, None, m, l, a),
            _k=kernel)

    res = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            _vmem((block_q, LANES), jnp.float32),
            _vmem((block_q, LANES), jnp.float32),
            _vmem((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **params,
    )(qr, kr, vr)
    out = res[0].reshape(B, H, Sq, D)
    if not need_lse:
        return out, None
    return out, res[1][..., 0].reshape(B, H, Sq)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr,
               *, scale, causal, block_q, block_k, nk, q_offset):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    run = True if not causal else _visible(qi, block_q, ki, block_k, q_offset)

    @pl.when(run)
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
                                      q_offset)
        p = jnp.exp(s - _bcast_lanes(lse, s.shape[1]))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _bcast_lanes(di, s.shape[1])) * scale
        dq_scr[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, block_q, block_k, nq, q_offset):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    run = True if not causal else _visible(qi, block_q, ki, block_k, q_offset)

    @pl.when(run)
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
                                      q_offset)
        p = jnp.exp(s - _bcast_lanes(lse, s.shape[1]))
        dv_scr[...] += jax.lax.dot(
            p.T.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _bcast_lanes(di, s.shape[1])) * scale
        dk_scr[...] += jax.lax.dot(
            ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, dout, causal, scale, block_q, block_k,
                    q_offset, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = Sq // block_q, Sk // block_k

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

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    def kv_row(bh):
        return (bh // H) * Hkv + (bh % H) // group

    # ---- dq: Q block resident, K/V blocks stream (ki innermost).
    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index_dq(bh, qi, ki):
        if causal:
            ki = jnp.where(
                _visible(qi, block_q, ki, block_k, q_offset), ki, 0)
        return (kv_row(bh), ki, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          q_offset=q_offset),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index_dq),
            pl.BlockSpec((1, block_k, D), kv_index_dq),
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_q, LANES), q_index),
            pl.BlockSpec((1, block_q, LANES), q_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_index),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[_vmem((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
        **params,
    )(qr, kr, vr, dor, lser, dir_).reshape(B, H, Sq, D)

    # ---- dk/dv: K/V block resident, Q blocks stream (qi innermost).
    # Emitted per *query* head; group-summed below when GQA.
    def kv_index(bh, ki, qi):
        return (kv_row(bh), ki, 0)

    def q_index_dkv(bh, ki, qi):
        if causal:
            # Skipped q blocks (above diagonal) redirect their DMA to the
            # next diagonal block to avoid wasted bandwidth.
            qi = jnp.where(
                _visible(qi, block_q, ki, block_k, q_offset), qi,
                jnp.minimum((ki * block_k) // block_q, nq - 1))
        return (bh, qi, 0)

    def dkv_index(bh, ki, qi):
        return (bh, ki, 0)

    dkv_dtype = jnp.float32 if group > 1 else q.dtype
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          q_offset=q_offset),
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index_dkv),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_q, D), q_index_dkv),
            pl.BlockSpec((1, block_q, LANES), q_index_dkv),
            pl.BlockSpec((1, block_q, LANES), q_index_dkv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), dkv_index),
            pl.BlockSpec((1, block_k, D), dkv_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sk, D), dkv_dtype),
            jax.ShapeDtypeStruct((B * H, Sk, D), dkv_dtype),
        ],
        scratch_shapes=[
            _vmem((block_k, D), jnp.float32),
            _vmem((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
        **params,
    )(qr, kr, vr, dor, lser, dir_)

    dk = dk.reshape(B, H, Sk, D)
    dv = dv.reshape(B, H, Sk, D)
    if group > 1:
        dk = dk.reshape(B, Hkv, group, Sk, D).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(B, Hkv, group, Sk, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------- wrapper

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, q_offset, interpret):
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                            q_offset, interpret, need_lse=False)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, q_offset, interpret):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              q_offset, interpret, need_lse=True)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, q_offset, interpret, res,
               dout):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, dout, causal, scale, block_q,
                           block_k, q_offset, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 512, q_offset: int = 0,
                    interpret: bool = False):
    """Pallas flash attention (fwd + bwd kernels) with custom VJP.
    q: [B, H, Sq, D]; k/v: [B, Hkv, Sk, D]."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash(q, k, v, causal, scale, block_q, block_k, q_offset,
                  interpret)


def _on_tpu() -> bool:
    """The platform JAX reports.  A backend that fails to initialize
    raises here: a broken chip must not read as "not on TPU"."""
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
              impl: Optional[str] = None, mesh=None):
    """Dispatching entry point: pallas flash on TPU, reference elsewhere.

    ``mesh`` is the SPMD mesh q/k/v are laid out on inside a GSPMD
    program.  A Mosaic kernel cannot be partitioned automatically, so on
    a mesh of more than one device the flash kernel runs as a shard_map
    island: batch over (dp, fsdp), heads over tp, the sequence whole."""
    if impl is None:
        impl = "flash" if _on_tpu() else "reference"
    if impl == "reference":
        return reference_attention(q, k, v, causal=causal, scale=scale)
    fn = functools.partial(flash_attention, causal=causal, scale=scale,
                           interpret=impl == "flash_interpret")
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
