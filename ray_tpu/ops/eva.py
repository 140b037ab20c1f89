"""EVA: chunked linearized attention (EvaByte), pooling and attention as
pallas kernels with a custom VJP, and their jnp references.

A query at position ``t`` in window ``w = t // window`` attends, under ONE
softmax, to two kinds of key:

- the tokens of its own window up to itself (causal, exact), and
- one *summary* of every ``chunk`` tokens of every earlier window: a
  summary key ``k~`` and a summary value ``v~`` a chunk, each a learned
  softmax pooling of the chunk (``eva_summaries``).

``eva_summaries(k, v, mu, phi, chunk)``: per head and chunk,
``a = softmax_i(scale * k_i . mu)``, ``k~ = sum_i a_i k_i``;
``b = softmax_i(scale * k_i . phi)``, ``v~ = sum_i b_i v_i`` (both poolings
read the key).  One pass over k and v and a transpose pass back
(``eva_pool_fwd_c16`` / ``eva_pool_bwd_c16``): a grid step holds
``_POOL_ROWS`` tokens of a head as ``[chunks, chunk, D]`` in float32.

``eva_attention(q, k, v, k_sum, v_sum, window, chunk)``: four kernels, all
on ``ops/attention.py``'s prefetched table of block pairs, its packing, its
``_tiles`` and its specs (a change to those is judged on this too):

- ``eva_fwd`` and ``eva_dq`` walk, for a resident q block of window ``w``,
  first the summary blocks of the windows before ``w`` and then the token
  blocks of ``w`` up to the diagonal: the online softmax state (m, l, the
  accumulator) stays in VMEM across both operands and one ``lse`` leaves.
  Which operand a step takes is a second word a step in the same table;
  the operand a step does not take keeps the block index it had or will
  have next, so nothing is fetched for it.  A summary block is
  ``_SUMMARY_WINDOWS`` windows' summaries (512 at 2,048 / 16); the last of
  a run may reach past ``w`` and is masked by its count (a [1, block]
  row; every row of a q block shares it, as ``block_q`` divides the
  window).  A step of one window's 128 summaries would carry no mask and
  cost more: the state's upkeep is as large as the tile then.
- ``eva_dkv`` is ``attention._dkv_kernel`` itself on a K-major table of
  the triangles inside the windows; ``eva_dsum`` is the same kernel,
  unmasked, on a K-major table of one window's summaries (128 rows)
  against the whole q windows after it (2,048 columns a step).

No score matrix reaches HBM and k, v are never concatenated with their
summaries.  ``window >= S`` is plain causal attention (no summary is
visible); ``chunk == 1`` with the identity pooling is causal attention over
the whole row.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..util import telemetry
from .attention import (_BLOCK_MASK, _FIRST_BIT, _KI_SHIFT, _LAST_BIT,
                        _QI_SHIFT, _RUN_BIT, DIAGONAL, EMPTY, FIRST,
                        INTERIOR, KI, KIND, LANES, LAST, MASK_VALUE, NEG_INF,
                        QI, Dims, Tiles, _bcast_lanes, _causal_mask_bias,
                        _compiler_params, _dkv_kernel, _rows, _specs,
                        _step_ki, _step_qi, _tiles, _vmem, block_schedule)

#: windows whose summaries make one block of the forward's and dq's walk,
#: at most (fewer where the row has fewer windows or they do not divide)
_SUMMARY_WINDOWS = 4
#: tokens of a head a pooling step holds (2,048: 128 chunks of 16)
_POOL_ROWS = 2048


def _on_tpu() -> bool:
    from .attention import _on_tpu      # at the call: tests steer it
    return _on_tpu()


# ------------------------------------------------------------- references

def reference_summaries(k, v, mu, phi, chunk: int,
                        scale: Optional[float] = None):
    """Plain jnp.  k, v: [B, H, S, D]; mu, phi: [H, D] -> (k_sum, v_sum)
    [B, H, S / chunk, D] in k's dtype, float32 inside."""
    B, H, S, D = k.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    k4 = k.astype(jnp.float32).reshape(B, H, S // chunk, chunk, D)
    v4 = v.astype(jnp.float32).reshape(B, H, S // chunk, chunk, D)

    def pooled(vec, x4):
        logits = jnp.einsum("bhncd,hd->bhnc", k4,
                            vec.astype(jnp.float32)) * scale
        return jnp.einsum("bhnc,bhncd->bhnd", jax.nn.softmax(logits, -1), x4)

    return pooled(mu, k4).astype(k.dtype), pooled(phi, v4).astype(v.dtype)


def reference_eva_attention(q, k, v, k_sum, v_sum, window: int, chunk: int,
                            scale: Optional[float] = None):
    """Plain jnp, a window's scores at once.  q, k, v: [B, H, S, D];
    k_sum, v_sum: [B, H, S / chunk, D]."""
    B, H, S, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    window = min(window, S)
    n, per = _windows(S, window, chunk)
    qw, kw, vw = (a.reshape(B, H, n, window, D) for a in (q, k, v))
    local = jnp.einsum("bhnqd,bhnkd->bhnqk", qw, kw,
                       preferred_element_type=jnp.float32) * scale
    t = jnp.arange(window)
    local = jnp.where(t[:, None] >= t[None, :], local, NEG_INF)
    remote = jnp.einsum("bhnqd,bhcd->bhnqc", qw, k_sum,
                        preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(k_sum.shape[2])[None, :] < per * jnp.arange(n)[:, None]
    remote = jnp.where(seen[:, None, :], remote, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([remote, local], axis=-1), axis=-1)
    p_remote, p_local = p[..., :k_sum.shape[2]], p[..., k_sum.shape[2]:]
    out = jnp.einsum("bhnqc,bhcd->bhnqd", p_remote.astype(v.dtype), v_sum) \
        + jnp.einsum("bhnqk,bhnkd->bhnqd", p_local.astype(v.dtype), vw)
    return out.reshape(B, H, S, D).astype(q.dtype)


def _windows(S: int, window: int, chunk: int):
    """(windows in a row, summaries a window)."""
    if S % window or window % chunk:
        raise ValueError(f"a row of {S} is not whole windows of {window}, "
                         f"or a window not whole chunks of {chunk}")
    return S // window, window // chunk


# --------------------------------------------------------------- pooling

def _pool_weights(k3, vec, scale):
    """softmax over a chunk of ``scale * k . vec``: k3 [N, chunk, D],
    vec [1, D] -> [N, chunk, 1]."""
    logits = jnp.sum(k3 * vec, axis=-1, keepdims=True) * scale
    e = jnp.exp(logits - jnp.max(logits, axis=1, keepdims=True))
    return e / jnp.sum(e, axis=1, keepdims=True)


def _pool_fwd_kernel(k_ref, v_ref, mu_ref, phi_ref, ks_ref, vs_ref, *, chunk,
                     scale):
    rows, D = k_ref.shape
    k3 = k_ref[...].astype(jnp.float32).reshape(rows // chunk, chunk, D)
    v3 = v_ref[...].astype(jnp.float32).reshape(rows // chunk, chunk, D)
    ks_ref[...] = jnp.sum(_pool_weights(k3, mu_ref[...], scale) * k3,
                          axis=1).astype(ks_ref.dtype)
    vs_ref[...] = jnp.sum(_pool_weights(k3, phi_ref[...], scale) * v3,
                          axis=1).astype(vs_ref.dtype)


def _pool_bwd_kernel(k_ref, v_ref, mu_ref, phi_ref, dks_ref, dvs_ref, dk_ref,
                     dv_ref, dmu_ref, dphi_ref, *, chunk, scale):
    """The transpose of the pass above, its weights formed again: with
    ``a`` the weights of a pooled ``x`` under ``vec`` and ``g`` the
    pooled row's gradient, ``dx = a g``, ``da = x . g``,
    ``dl = a (da - sum_chunk a da)``, ``dk += scale dl vec``,
    ``dvec += scale sum dl k``."""
    rows, D = k_ref.shape
    shape = (rows // chunk, chunk, D)
    k3 = k_ref[...].astype(jnp.float32).reshape(shape)
    v3 = v_ref[...].astype(jnp.float32).reshape(shape)

    def back(vec, x3, g_ref):
        a = _pool_weights(k3, vec, scale)
        g = g_ref[...].astype(jnp.float32)[:, None, :]
        da = jnp.sum(x3 * g, axis=-1, keepdims=True)
        dl = a * (da - jnp.sum(a * da, axis=1, keepdims=True)) * scale
        dvec = jnp.sum(jnp.sum(dl * k3, axis=0), axis=0, keepdims=True)
        return a * g, dl, dvec

    dk3, dl_mu, dmu = back(mu_ref[...], k3, dks_ref)
    dv3, dl_phi, dphi = back(phi_ref[...], v3, dvs_ref)
    dk3 = dk3 + dl_mu * mu_ref[...] + dl_phi * phi_ref[...]
    dk_ref[...] = dk3.reshape(rows, D).astype(dk_ref.dtype)
    dv_ref[...] = dv3.reshape(rows, D).astype(dv_ref.dtype)
    dmu_ref[...] = dmu
    dphi_ref[...] = dphi


def _pool_call(backward, k, v, mu, phi, grads, chunk, scale, interpret):
    """One pooling kernel over [B * H, S, D]: the forward's (k_sum, v_sum),
    or the backward's (dk, dv, dmu, dphi) given ``grads`` = (dk_sum,
    dv_sum)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = k.shape
    rows = min(_POOL_ROWS, S)
    if S % rows or rows % chunk:
        raise ValueError(f"a row of {S} is not whole pooling steps of "
                         f"{rows}, or a step not whole chunks of {chunk}")
    n, steps, per = B * H, S // rows, rows // chunk
    name = f"eva_pool_{'bwd' if backward else 'fwd'}_c{chunk}"
    telemetry.inc("ray_tpu_eva_step_geometry_total", tags={
        "kernel": name, "block_q": str(rows), "block_k": str(per),
        "block_s": "0", "summary_steps": "0", "token_steps": str(steps)})
    tokens = pl.BlockSpec((None, rows, D), lambda r, j: (r, j, 0))
    pooled = pl.BlockSpec((None, per, D), lambda r, j: (r, j, 0))
    vec = pl.BlockSpec((None, 1, D), lambda r, j: (r % H, 0, 0))
    part = pl.BlockSpec((None, None, 1, D), lambda r, j: (r, j, 0, 0))
    flat = lambda a: a.reshape(n, a.shape[2], D)
    vecs = [a.astype(jnp.float32).reshape(H, 1, D) for a in (mu, phi)]
    sds = jax.ShapeDtypeStruct
    if not backward:
        in_specs, args = [tokens, tokens, vec, vec], []
        out_specs = [pooled, pooled]
        out_shape = [sds((n, S // chunk, D), k.dtype),
                     sds((n, S // chunk, D), v.dtype)]
        kernel = _pool_fwd_kernel
    else:
        in_specs = [tokens, tokens, vec, vec, pooled, pooled]
        args = [flat(g) for g in grads]
        out_specs = [tokens, tokens, part, part]
        out_shape = [sds((n, S, D), k.dtype), sds((n, S, D), v.dtype),
                     sds((n, steps, 1, D), jnp.float32),
                     sds((n, steps, 1, D), jnp.float32)]
        kernel = _pool_bwd_kernel
    out = pl.pallas_call(
        functools.partial(kernel, chunk=chunk, scale=scale),
        grid=(n, steps), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name=name,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 2 ** 20)}),
    )(flat(k), flat(v), *vecs, *args)
    if not backward:
        return tuple(a.reshape(B, H, S // chunk, D) for a in out)
    dk, dv, dmu, dphi = out
    total = lambda a: a.reshape(B, H, steps, D).sum(axis=(0, 2))
    return (dk.reshape(k.shape), dv.reshape(v.shape),
            total(dmu).astype(mu.dtype), total(dphi).astype(phi.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _pool(k, v, mu, phi, chunk, scale, interpret):
    return _pool_call(False, k, v, mu, phi, None, chunk, scale, interpret)


def _pool_fwd(k, v, mu, phi, chunk, scale, interpret):
    return _pool_call(False, k, v, mu, phi, None, chunk, scale,
                      interpret), (k, v, mu, phi)


def _pool_bwd(chunk, scale, interpret, res, grads):
    return _pool_call(True, *res, grads, chunk, scale, interpret)


_pool.defvjp(_pool_fwd, _pool_bwd)


def eva_summaries(k, v, mu, phi, chunk: int, *, scale: Optional[float] = None,
                  impl: Optional[str] = None):
    """One summary key and one summary value a chunk of ``chunk`` tokens.
    k, v: [B, H, S, D]; mu, phi: [H, D] -> (k_sum, v_sum)
    [B, H, S / chunk, D].  ``impl`` as ``ops.attention.attention``'s:
    the kernels on a TPU (``flash``; ``flash_interpret`` for the tests),
    ``reference`` elsewhere."""
    scale = scale if scale is not None else 1.0 / math.sqrt(k.shape[-1])
    if impl is None:
        impl = "flash" if _on_tpu() else "reference"
    if impl == "reference":
        return reference_summaries(k, v, mu, phi, chunk, scale)
    return _pool(k, v, mu, phi, chunk, scale, impl == "flash_interpret")


# ------------------------------------------------------------- the tables

def _flagged(runs):
    """Runs of [qi, ki, kind, 0, 0] steps -> int32 [5, steps], the first
    and last step of each run marked."""
    for run in runs:
        run[0][FIRST] = run[-1][LAST] = 1
    return np.array([step for run in runs for step in run], np.int32).T


def eva_schedule(S, window, chunk, block_q, block_k, block_s, major="q"):
    """The grid steps of one (batch, head), as ``block_schedule``'s rows
    (QI, KI, KIND, FIRST, LAST), and for ``major="q"`` a second array
    [2, steps]: (the summary block, whether the step takes it).

    ``major="q"`` (forward, dq): for each q block of window ``w`` the
    summary blocks of ``block_s`` summaries that hold one of a window
    before ``w`` (DIAGONAL where the block reaches past ``w``), then
    ``block_schedule``'s run of the causal triangle inside the window.  On
    a summary step KI holds the first token block of the run, on a token
    step the second array holds the run's last summary block: what the
    operand not taken is indexed by.
    ``major="k"`` (dk/dv): the K-major triangles of the windows one after
    another.  ``major="s"`` (the summaries' gradients): for each window's
    summaries (``block_s`` = one window's) the q blocks (``block_q`` = a
    window) of every later window; the last window's summaries see none
    and get one EMPTY step."""
    n, per = _windows(S, window, chunk)
    if window % block_q or window % block_k:
        raise ValueError(f"blocks ({block_q}, {block_k}) do not divide the "
                         f"window {window}")
    qpw, kpw = window // block_q, window // block_k
    if major == "s":
        if (block_q, block_s) != (window, per):
            raise ValueError("the summaries' gradients walk whole windows")
        return _flagged([
            [[qw, sw, INTERIOR, 0, 0] for qw in range(sw + 1, n)]
            or [[n - 1, sw, EMPTY, 0, 0]] for sw in range(n)]), None
    local = block_schedule(window, window, block_q, block_k, 0, True,
                           "k" if major == "k" else "q")
    if major == "k":
        runs = []
        for w in range(n):
            for b in range(kpw):
                runs.append([[w * qpw + qi, w * kpw + b, kind, 0, 0]
                             for qi, ki, kind in local[:KIND + 1].T
                             if ki == b])
        return _flagged(runs), None
    if (n * per) % block_s or block_s % per:
        raise ValueError(f"summary blocks of {block_s} do not divide the "
                         f"row's {n * per}, or hold no whole window's {per}")
    runs, taken = [], []
    for w in range(n):
        blocks = -(-w * per // block_s)
        for a in range(qpw):
            tokens = [[w * qpw + a, w * kpw + ki, kind, 0, 0]
                      for qi, ki, kind in local[:KIND + 1].T if qi == a]
            summaries = [[w * qpw + a, tokens[0][KI], INTERIOR if
                          (si + 1) * block_s <= w * per else DIAGONAL, 0, 0]
                         for si in range(blocks)]
            runs.append(summaries + tokens)
            taken += [[si, 1] for si in range(blocks)] \
                + [[max(blocks - 1, 0), 0]] * len(tokens)
    return _flagged(runs), np.array(taken, np.int32).T


def _pack(sched, taken=None):
    """The scalar-prefetch operand: ``attention._packed_schedule``'s word a
    step, and behind them (q-major) a second word a step,
    ``summary block << 1 | takes it``."""
    if max(sched[QI].max(), sched[KI].max()) > _BLOCK_MASK:
        raise ValueError(f"more than {_BLOCK_MASK + 1} blocks a side")
    words = (sched[QI] << _QI_SHIFT | sched[KI] << _KI_SHIFT
             | (sched[KIND] != EMPTY) * _RUN_BIT
             | sched[FIRST] * _FIRST_BIT | sched[LAST] * _LAST_BIT)
    if taken is not None:
        words = np.concatenate([words, taken[0] << 1 | taken[1]])
    return words.astype(np.int32)


def _name(kind, window, chunk):
    return f"eva_{kind}_w{window}c{chunk}"


def _geometry(kind, S, D, window, chunk, block_q=None, block_k=None,
              block_s=None):
    """(Tiles, block_s, table, second words) of kernel ``kind``, counted.
    Forward, dq and dk/dv take ``_tiles``' answer for a causal call of one
    window; the summaries' gradients a window against a window's
    summaries."""
    n, per = _windows(S, window, chunk)
    if kind == "dsum":
        t, block_s, major = Tiles(window, per, 1, "kq"), per, "s"
    else:
        t = _tiles(kind, window, window, D, 1)
        t = t._replace(block_q=min(block_q or t.block_q, window),
                       block_k=min(block_k or t.block_k, window))
        major = "k" if kind == "dkv" else "q"
        block_s = block_s or per * max(
            m for m in range(1, min(n, _SUMMARY_WINDOWS) + 1) if n % m == 0)
    sched, taken = eva_schedule(S, window, chunk, t.block_q, t.block_k,
                                block_s, major)
    steps = int((sched[KIND] != EMPTY).sum())
    summary = int(taken[1].sum()) if taken is not None \
        else steps * (kind == "dsum")
    telemetry.inc("ray_tpu_eva_step_geometry_total", tags={
        "kernel": _name(kind, window, chunk), "block_q": str(t.block_q),
        "block_k": str(t.block_k), "block_s": str(block_s),
        "summary_steps": str(summary), "token_steps": str(steps - summary)})
    return t, block_s, sched, taken


# ---------------------------------------------------- forward and dq walk

def _summary_bias(step_qi, block, block_q, block_s, window, per):
    """0.0 where a summary of this block lies in a window before the q
    block's, MASK_VALUE elsewhere: [1, block_s]."""
    seen = (step_qi * block_q // window) * per - block * block_s
    col = jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    return jnp.where(col < seen, 0.0, MASK_VALUE)


def _walk(sched_ref, steps, body, *, block_q, block_k, block_s, window, per,
          k_ref, v_ref, ks_ref, vs_ref):
    """Run ``body(k block, v block, the step's mask)`` for the operand this
    grid step takes."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    step, second = sched_ref[i], sched_ref[steps + i]
    qi, ki = _step_qi(step), _step_ki(step)
    running = step & _RUN_BIT != 0

    @pl.when(running & (second & 1 == 1))
    def _summaries():
        body(ks_ref[0], vs_ref[0], _summary_bias(
            qi, second >> 1, block_q, block_s, window, per))

    @pl.when(running & (second & 1 == 0))
    def _tokens():
        body(k_ref[0], v_ref[0], _causal_mask_bias(
            block_q, block_k, qi, ki, block_q, block_k, 0))


def _fwd_kernel(sched_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, scale, steps, **walk):
    from jax.experimental import pallas as pl

    step = sched_ref[pl.program_id(1)]

    @pl.when(step & _FIRST_BIT != 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def body(k, v, bias):
        s = jax.lax.dot_general(
            _rows(q_ref), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + bias
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _bcast_lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        m_scr[...] = m_next
        acc_scr[...] = acc_scr[...] * _bcast_lanes(alpha, acc_scr.shape[1]) \
            + jax.lax.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    _walk(sched_ref, steps, body, k_ref=k_ref, v_ref=v_ref, ks_ref=ks_ref,
          vs_ref=vs_ref, **walk)

    @pl.when(step & _LAST_BIT != 0)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] * _bcast_lanes(1.0 / l, acc_scr.shape[1])
                    ).astype(o_ref.dtype).reshape(o_ref.shape[1:])
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l)).T[:1]


def _dq_kernel(sched_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, do_ref,
               lse_ref, di_ref, dq_ref, dq_scr, lse_scr, di_scr, *, scale,
               steps, **walk):
    from jax.experimental import pallas as pl

    step = sched_ref[pl.program_id(1)]
    block_q = walk["block_q"]

    @pl.when(step & _FIRST_BIT != 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
        for row_ref, col_scr in ((lse_ref, lse_scr), (di_ref, di_scr)):
            col_scr[...] = jnp.broadcast_to(row_ref[0, 0],
                                            (LANES, block_q)).T

    def body(k, v, bias):
        s = jax.lax.dot_general(
            _rows(q_ref), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + bias
        p = jnp.exp(s - _bcast_lanes(lse_scr[...], s.shape[1]))
        dp = jax.lax.dot_general(
            _rows(do_ref), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _bcast_lanes(di_scr[...], s.shape[1])) * scale
        dq_scr[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    _walk(sched_ref, steps, body, k_ref=k_ref, v_ref=v_ref, ks_ref=ks_ref,
          vs_ref=vs_ref, **walk)

    @pl.when(step & _LAST_BIT != 0)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype).reshape(
            dq_ref.shape[1:])


def _two_operand_call(kind, kernel, q, k, v, ks, vs, extra, results,
                      scratch, window, chunk, scale, blocks, interpret):
    """``eva_fwd`` or ``eva_dq``: a grid (B * H, steps) over the q-major
    table.  ``extra`` are further inputs, q-shaped [B, H, S, D] or rows
    [B, H, S]; ``results`` the dtypes of the q-shaped (ndim 4) or row
    (ndim 3) results, as (ndim, dtype)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    n = B * H
    t, block_s, sched, taken = _geometry(kind, S, D, window, chunk, *blocks)
    steps = sched.shape[1]
    sp = _specs(t, Dims(n, 1, 1, S, S, D, D))
    sum_spec = pl.BlockSpec(
        (1, block_s, D), lambda r, s, sched: (r, sched[steps + s] >> 1, 0))
    spec = {4: sp.q, 3: sp.row}
    shape = {4: (n, 1, S, D), 3: (n, 1, 1, S)}
    flat = lambda a: a.reshape(n, a.shape[2], D)
    return pl.pallas_call(
        functools.partial(
            kernel, scale=scale, steps=steps, block_q=t.block_q,
            block_k=t.block_k, block_s=block_s, window=window,
            per=window // chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n, steps),
            in_specs=[sp.q, sp.k, sp.k, sum_spec, sum_spec]
            + [spec[a.ndim] for a in extra],
            out_specs=[spec[ndim] for ndim, _ in results],
            scratch_shapes=scratch(t.block_q)),
        out_shape=[jax.ShapeDtypeStruct(shape[ndim], dtype)
                   for ndim, dtype in results],
        interpret=interpret, name=_name(kind, window, chunk),
        **_compiler_params(interpret, t.block_q, max(t.block_k, block_s)),
    )(_pack(sched, taken), q.reshape(shape[4]), flat(k), flat(v), flat(ks),
      flat(vs), *[a.reshape(shape[a.ndim]) for a in extra])


def _eva_forward(q, k, v, ks, vs, window, chunk, scale, blocks, interpret):
    B, H, S, D = q.shape
    out, lse = _two_operand_call(
        "fwd", _fwd_kernel, q, k, v, ks, vs, [],
        [(4, q.dtype), (3, jnp.float32)],
        lambda rows: [_vmem((rows, LANES), jnp.float32),
                      _vmem((rows, LANES), jnp.float32),
                      _vmem((rows, D), jnp.float32)],
        window, chunk, scale, blocks, interpret)
    return out.reshape(q.shape), lse.reshape(B, H, S)


def _eva_backward(q, k, v, ks, vs, out, lse, dout, window, chunk, scale,
                  blocks, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    n = B * H
    di = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dq, = _two_operand_call(
        "dq", _dq_kernel, q, k, v, ks, vs, [dout, lse, di], [(4, q.dtype)],
        lambda rows: [_vmem((rows, D), jnp.float32),
                      _vmem((rows, LANES), jnp.float32),
                      _vmem((rows, LANES), jnp.float32)],
        window, chunk, scale, blocks, interpret)

    def k_major(kind, keys, values, causal):
        """``attention._dkv_kernel`` on this kind's K-major table: the
        gradients of ``keys`` and ``values`` [B, H, rows, D]."""
        t, _, sched, _ = _geometry(kind, S, D, window, chunk, *blocks)
        sp = _specs(t, Dims(n, 1, 1, S, S, D, D))
        flat = lambda a: a.reshape(n, a.shape[2], D)
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, causal=causal, scale=scale,
                              block_q=t.block_q, block_k=t.block_k,
                              q_offset=0),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n, sched.shape[1]),
                in_specs=[sp.q, sp.k, sp.k, sp.q, sp.row,
                          sp.row],
                out_specs=[sp.dk] * 2,
                scratch_shapes=[_vmem((t.block_k, D), jnp.float32)] * 2),
            out_shape=[jax.ShapeDtypeStruct(flat(keys).shape, keys.dtype),
                       jax.ShapeDtypeStruct(flat(values).shape,
                                            values.dtype)],
            interpret=interpret, name=_name(kind, window, chunk),
            **_compiler_params(interpret, t.block_q, t.block_k),
        )(_pack(sched), q.reshape(n, 1, S, D), flat(keys), flat(values),
          dout.reshape(n, 1, S, D), lse.reshape(n, 1, 1, S),
          di.reshape(n, 1, 1, S))
        return dk.reshape(keys.shape), dv.reshape(values.shape)

    dk, dv = k_major("dkv", k, v, True)
    dks, dvs = k_major("dsum", ks, vs, False)
    return dq.reshape(q.shape), dk, dv, dks, dvs


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _eva(q, k, v, ks, vs, window, chunk, scale, blocks, interpret):
    return _eva_forward(q, k, v, ks, vs, window, chunk, scale, blocks,
                        interpret)[0]


def _eva_fwd(q, k, v, ks, vs, window, chunk, scale, blocks, interpret):
    out, lse = _eva_forward(q, k, v, ks, vs, window, chunk, scale, blocks,
                            interpret)
    return out, (q, k, v, ks, vs, out, lse)


def _eva_bwd(window, chunk, scale, blocks, interpret, res, dout):
    return _eva_backward(*res, dout, window, chunk, scale, blocks, interpret)


_eva.defvjp(_eva_fwd, _eva_bwd)


def eva_attention(q, k, v, k_sum, v_sum, window: int, chunk: int, *,
                  scale: Optional[float] = None, impl: Optional[str] = None,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None,
                  block_s: Optional[int] = None, mesh=None):
    """Attention of every query over the tokens of its own window up to
    itself and the summaries of every earlier window, under one softmax.
    q, k, v: [B, H, S, D] (no grouped heads); k_sum, v_sum:
    [B, H, S / chunk, D] (``eva_summaries``).  ``impl`` as
    ``ops.attention.attention``'s.  The blocks default to what
    ``attention._tiles`` picks for a causal call of one window, and to
    ``_SUMMARY_WINDOWS`` windows' summaries a block."""
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"EVA has a key head a query head: q {q.shape}, "
                         f"k {k.shape}, v {v.shape}")
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the EVA kernels on a mesh of more than one device (ROADMAP)")
    S = q.shape[2]
    window = min(window, S)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if impl is None:
        impl = "flash" if _on_tpu() else "reference"
    if impl == "reference":
        return reference_eva_attention(q, k, v, k_sum, v_sum, window, chunk,
                                       scale)
    return _eva(q, k, v, k_sum, v_sum, window, chunk, scale,
                (block_q, block_k, block_s), impl == "flash_interpret")
