"""Causal (GQA) attention: pallas flash kernels + jnp reference.

FlashAttention-2 on TPU, forward *and* backward as pallas kernels:

- Every kernel walks a grid (rows, steps) over the (q block, k block) pairs
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
  kernels would be asked for (131 KB at 128k tokens and 512 x 512).
- **The geometry of a step follows the call's shapes** (``_tiles``; PERF.md,
  PR 33 has the chip's table behind each choice).  A step is bound by how
  it feeds the MXU and by what a grid step costs beside its products, and
  both are paid once a step, so a step should hold as many rows behind each
  128 x 128 tile of the other operand as the shapes offer.  Under
  grouped-query attention a grid row is a *key* head and a step holds the
  ``block_q`` rows of all its query heads (up to 8), stacked: K / V blocks
  are fetched once a group, the group shares one mask, and the pair's
  dk / dv add up inside the kernel and leave per key head in the inputs'
  dtype (the one pass, below, takes a query head a row).  With no
  group to stack (and no window) the blocks are 1,024 x 1,024.  Anything
  else keeps 512 x 512 and a head a row.  Each traced kernel counts its
  geometry in ``ray_tpu_flash_step_geometry_total``.
- **q / k and v / o may have head sizes of their own** (latent attention:
  scores over 192 = 128 without position + 64 rotary, values of 128).  The
  scores, dq and dk are formed over q's head size; the forward's
  accumulator, ``o``, ``do``, ``dv`` and delta over v's.  What crosses HBM
  is as wide as the call's operands, never padded: a 192-wide block is one
  VMEM tile of a lane group and a half, and the MXU takes its contraction
  as it is.  Such a call carries both sizes in its kernels' names
  (``flash_fwd_d192v128``) and in the geometry counter's tags; with equal
  sizes names and tags are as they were.
- **v and the result are read and written where the projections hold
  them** (``rows``).  The kernels' home layout is head-major,
  [B, H, S, D]: what the rotary pair writes for q and k
  (``ops.rope.rotate_heads``).  A projection writes [B, S, H * D], and v,
  ``out``, ``do`` and ``dv`` need not be turned for the kernels' sake: with
  ``rows`` a call hands v as [B, S, Hkv, Dv] and takes the result as
  [B, S, H, Dv]; the block of such an operand is ``(1, block, heads * Dv)``
  at the lane-block of the grid row's heads, and a kernel takes a head as
  the lane slice ``[:, h*Dv:(h+1)*Dv]`` (``_head``, ``_rows``,
  ``_write_rows``: all a kernel body knows of the layout; ``_specs`` is the
  rest).  ``dv`` leaves and ``do`` arrives so, and the residuals are kept
  so.  A head must be whole lane tiles, so the specs apply where both head
  sizes are multiples of 128; any other ``rows`` call is turned at
  ``flash_attention``'s edge.  The geometry counter says which kernels a
  trace took so (PERF.md, PR 49: five q-sized copies a layer call fewer in
  Yi's and Ouro's steps).
- **A call in parts** hands q and k each in the two parts latent
  attention's projections write, and the kernels form the scores as
  ``q_n k_n^T + q_r k_r^T`` in float32 before the scale, the mask and the
  softmax: the 192-wide contraction as the MXU makes it anyway, 128 + 64,
  on the same bf16 operands.  ``q = (q_n [B, Sq, H, Dn], q_r [B, H, Sq,
  Dr])``: the lanes without position as rows, the rotary ones head-major as
  ``rotate_heads`` writes them.  ``k = (kv [B, Sk, H, Dn + Dv], k_r [B, 1,
  Sk, Dr])`` and ``v = None``: a head's key without position and its value
  lie side by side in the one product's result, one rows block of ``Dn +
  Dv`` lanes a step, and the ONE rotary key head is every query head's
  (its block's index map ignores the head: nothing is laid out H times in
  HBM).  The result and its cotangent are rows; dq leaves in q's two
  parts, dk and dv side by side as ``kv`` came, and the rotary head's
  gradient as a grid row's (a head's) share, summed over the heads outside.
  So nothing is concatenated, broadcast, sliced or turned round the
  kernels; ``_specs`` and a few ``if parts`` in each kernel body are all
  there is to it, the geometry is ``_tiles``' for the whole width, and the
  names and the roofline's operations are a 192 / 128 call's.  A one-part
  call traces the kernels it traced before.  The parts apply where ``Dn``
  and ``Dv`` are multiples of 128 on one device; any other call in parts
  (the reference path, a mesh's island) is put together at the edge
  (``_one_part``).  The geometry counter tags such kernels ``parts="128+64"``,
  ``rows="qkvo"`` (PERF.md, PR 50: the kernels alone cost 3.7 % more so,
  and Xing4.0's step is 58 ms of 1,177 shorter for what left it round them).
  ``kv`` may hold fewer key heads than q has heads, [B, Sk, Hkv, Dn + Dv]
  with ``H % Hkv == 0`` (PR 57: latent keys decompressed into 16 key heads
  under 80 query heads): query head ``h`` reads key head ``h // (H //
  Hkv)`` through the same index maps a grouped one-part call uses, dk/dv
  add up a group's query heads in float32 (inside the kernel where a step
  stacks the group, else as float32 shares summed outside) and the rotary
  head's gradient all the heads; with ``H == Hkv`` a call traces what it
  traced before.
- **A grid step may hold several tiles** (``Tiles.tiles``; a head size
  over 128, latent attention's 192 / 128: eight).  The pipeline fetches a
  *major block* of the streamed operands, ``tiles`` blocks long (keys and
  values in forward and dq; q, ``do``, LSE and delta in dk/dv), the table
  lists the (resident block, major block) pairs that hold a visible
  element, and the body walks the major block's 512 x 512 tiles in a loop,
  doing for each what a grid step of one tile does: the same products, mask
  and order of accumulation on the same scratch, so the results are the
  one-tile walk's bit for bit.  The walk stops at the last tile the step's
  q rows see (``_visible_tiles``), so nothing above the diagonal is
  computed, as larger blocks would; the score tile stays 512 x 512, so the
  kernels stay inside Mosaic's default 16 MiB of scoped VMEM (dk/dv 9.5
  MiB, the one pass 13.1 at its 4 tiles) and state no limit.  What it
  buys is what a grid step costs beside its tiles (block DMAs issued and
  waited for, the table's word, the index maps, scratch read and written
  back): at [1, 32, 8192] and 128 + 64 / 128 the three kernels read
  26.69 ms a call at 136 steps a head, 25.42 at 72 (2 tiles), 24.79 at 40
  (4), 24.60 at 24 (8) and 24.12 at 16 (the whole side, where dk/dv holds
  15.5 of the 16 MiB: not shipped); a loop and the tiles unrolled read the
  same (PERF.md, PR 52).  The geometry counter tags such kernels
  ``tiles_a_step``; with one tile a step every kernel traces what it
  traced before.
- **What the roofline's count can reach on a 128-deep MXU.**  A
  contraction over 192 is two passes of the array, the second half empty,
  and a 64-wide result (dq's and dk's rotary parts) a 128-wide pass half
  used: in 128-units a 192 / 128 step makes 3 / 5 / 6 passes (forward / dq
  / dk/dv) where the roofline counts 2.5 / 4 / 5 (``benchmark/
  roofline_mla.flash_call``), so its reading's ceiling is 83 / 80 / 83 %,
  not 100; at a head size of 64 every product fills half of a pass and the
  ceiling is 50 (PERF.md section 3, PR 52 has the kernel-alone reading).
- The grid is ("parallel", "arbitrary"): only the grid rows split across
  the cores of a two-core chip (v4, v5p): B * H of them without a group,
  B * Hkv with one, so a call with one or two key heads a device no longer
  spreads over both.  The v5e has one core.  A step of 4,096 stacked rows
  asks for up to 96 MiB of scoped VMEM (the v5e has 128).
- Forward blocks over BOTH sequence axes, the steps of one Q block in a row
  with K ascending ("arbitrary" semantics) so pallas double-buffers K/V
  block DMAs while the MXU works.  Online softmax state (running max m,
  denominator l, unnormalized accumulator) lives in VMEM scratch carried
  across K blocks; the [Sq, Sk] score matrix never exists in HBM.  The
  state is kept broadcast over the 128-lane minor dim (the TPU-friendly
  layout the jax flash kernel also uses); the log-sum-exp leaves the kernel
  as a residual in rows along the lanes, [.., 1, Sq]: a q block's last step
  transposes its column once, and nothing 128 times its size reaches HBM.
- Backward has two forms, and ``_tiles`` picks one from the call's shapes.
  **The pair**: dq (the same walk as the forward, accumulating dq for a
  resident Q block) and dk/dv (K-major: the steps of one K/V block in a
  row with Q ascending, accumulating dk/dv for the resident block).  Both
  recompute probabilities from the saved LSE — one exp, no second softmax
  pass — with fp32 accumulation and bf16 MXU inputs.  dk/dv forms its
  scores transposed (``k q^T``, as upstream's splash kernel does), so
  dv = p^T do and dk = ds^T q are plain products with no transpose in the
  kernel, and reads ``lse`` / ``di`` as rows along the lanes.  dq needs
  them as columns and makes those in VMEM, once a resident q block, from
  the same rows: no lane-broadcast copy of either is written outside.
  **The one pass** (``flash_bwd``, PERF.md, PR 54): the pair forms every
  tile's scores, probabilities, ``dP = dO V^T`` and ``dS`` twice, seven
  products a tile (11 passes of a 128-deep MXU at 192 / 128) where five
  (8) make all three gradients.  Where a grid row is one query head, the
  K-major kernel also adds ``dS^T`` (one turn of the tile through the
  XLU) ``k`` into a float32 scratch that holds the row's whole dq, and the
  dq kernel is not called: q tile ``i`` has seen its last k block at k
  block ``i``'s last step and leaves then, so nothing of dq crosses HBM
  but the result (upstream's splash dk/dv kernel carries an optional dq
  too, as per-k-block partials in HBM that are summed outside).  It
  serves the causal square (``Sq == Sk``, no offset) with ``block_q ==
  block_k``, which is where a q tile's last k block is its own.  Under a
  group a grid row is one query head all the same (the stacked group's dq
  would be 8 - 32 MiB a row): it reads its key head's blocks, its share
  of dk / dv leaves in float32 and the shares are summed outside, the sum
  the pair makes in its float32 accumulator (PERF.md, PR 60: 20 - 23 %
  off the backward at the four grouped cells' shapes, 12 % under
  Trinity-Mini's window).  A ring shard's offset or rectangle,
  ``causal=False``, a row whose dq does not fit and a window with no group
  or at a head size over 128 keep the pair, and trace what they traced.

``q_offset`` shifts query positions for causal masking so sequence-sharded
callers (ring attention) can flash-attend a mid-sequence Q shard.
``window`` narrows the triangle to a band (a key is visible iff
``0 <= t - s < window``): the pairs left of the band leave the grid, the
mask gains the band's lower edge, and the three kernels carry the window in
their names (``flash_fwd_w2048``).  With ``window=None`` nothing changes.
``sink`` ([H] float32, a learned attention sink: MiMo-V2-Flash's window
layers) gives every row of query head ``h`` one more column, of score ``b_h``
and value zero: ``p_ts = exp(s_ts) / (exp(b_h) + sum_s' exp(s_ts'))``, mass
that goes nowhere.  The forward's online softmax starts such a row at ``m =
b_h, l = 1`` with an empty accumulator, where it starts every other at ``m =
-inf, l = 0``: the state after the sink's column, had it been a key block of
its own; a step's heads read theirs from one [heads, 1, 128] block.  Nothing
else of the forward changes (no second softmax pass, no concatenated column
in HBM), and its kernel says so in its name (``flash_fwd_d192v128_w128_sink``).
The saved log-sum-exp then holds the sink, so the backward kernels need no
change: their ``p = exp(s - lse)`` and ``ds = p (dp - delta)`` are the
softmax's over keys and sink as they stand, and run under the names they
have.  The column's value is zero, so its ``dp`` is 0 and ``db_h = - sum_t
exp(b_h - lse_t) delta_t``, ``delta_t = o_t . do_t``: formed beside the
kernels (``_flash_sink_bwd``, under the scope ``attn/sink_grad``) from the
LSE and the delta they read anyway.  Such a call is a ``custom_vjp`` of its
own that hands out (result, LSE), the LSE differentiable (its cotangent is
taken off delta once): ``exp(b_h - lse_t)`` is the share of the row's mass
the sink took, which a model reports.  With ``sink=None`` a call traces what
it traced.

``segment_ids`` ([B, S] integers: a PACKED row, several documents end to
end, a run of equal ids a document; PR 65) keeps a query to the keys of its
own document, under the causal mask: the causal square with no window, sink,
offset or parts.  The ids reach the three kernels as rows along the lanes,
the mask is the triangle's and ``same document`` in one ``where``, and a
pair of blocks whose documents cannot meet computes and fetches nothing (two
more scalar-prefetch operands say which; the note on documents above
``_documents``).  Such a call is a ``custom_vjp`` of its own
(``_flash_seg``), its kernels are named ``flash_seg_fwd_d64`` and so on, so
that a reader that counts the whole triangle by a kernel's name never counts
them, and the geometry counter tags them so.  With ``segment_ids=None`` a
call traces what it traced: a row is one document.

Design provenance (patterns, not code): the reference delegates attention to
engines (SURVEY §2.4 SP/CP row — no in-repo kernel); the block/layout recipe
follows jax.experimental.pallas.ops.tpu.flash_attention (LSE lane broadcast,
dual-axis blocking); the prefetched table of visible blocks and the
transposed scores of dk/dv are how upstream's splash attention walks a
sparse mask.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..util import telemetry

NEG_INF = -1e30
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128


def reference_attention(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        q_offset: int = 0, window: Optional[int] = None,
                        sink=None, lse: bool = False, segment_ids=None):
    """Plain-jnp attention. q: [B, H, Sq, D]; k: [B, Hkv, Sk, D];
    v: [B, Hkv, Sk, Dv] (``Dv`` may differ from ``D``; the default scale is
    ``D ** -0.5``).  Returns [B, H, Sq, Dv].

    ``q_offset`` shifts query positions for causal masking (used by
    sequence-sharded callers where the local Q block starts mid-sequence).
    ``window`` (with ``causal``) keeps the keys with ``0 <= t - s < window``.
    ``sink`` [H] float32: one more column of every row of head ``h``, of
    score ``sink[h]`` (no scale) and value zero, so it takes mass and adds
    nothing.  ``lse``: also return the rows' log-sum-exp [B, H, Sq] float32,
    the sink's column in it.  ``segment_ids`` [B, S] (with ``causal``, Sq ==
    Sk): a query sees the keys of its own document only, a document a run
    of equal ids.
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
        mask = mask[None, None]
        if segment_ids is not None:
            doc = _documents(segment_ids)
            mask = mask & (doc[:, None, :, None] == doc[:, None, None, :])
        scores = jnp.where(mask, scores, NEG_INF)
    if sink is not None:
        scores = jnp.concatenate([scores, jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            (B, H, Sq, 1))], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    if sink is not None:
        probs = probs[..., :Sk]
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)
    return (out, jax.nn.logsumexp(scores, axis=-1)) if lse else out


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


def _causal_mask_bias(q_rows, k_rows, qi, ki, block_q, block_k, q_offset,
                      window=None, transposed=False, same=None):
    """0.0 where a key is visible and MASK_VALUE elsewhere, for one pair of
    blocks: [q_rows, k_rows], or [k_rows, q_rows] when ``transposed``.
    ``q_rows`` may hold several heads' ``block_q`` rows one after another:
    they share the positions.  ``same``: a bool of that shape, where query
    and key are of one document (a call with ``segment_ids``)."""
    shape = (k_rows, q_rows) if transposed else (q_rows, k_rows)
    q_in = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transposed else 0)
    if q_rows != block_q:
        q_in = (q_in & (block_q - 1) if block_q & (block_q - 1) == 0
                else jax.lax.rem(q_in, block_q))
    row = q_in + qi * block_q + q_offset
    col = jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1) + ki * block_k
    visible = col <= row
    if window is not None:
        # A row that sees nothing of its first tile takes MASK_VALUE as its
        # running max there; the first visible score then scales that
        # tile's sums by exp(MASK_VALUE - max) = 0.
        visible &= row - col < window
    if same is not None:
        # In ONE ``where`` with the triangle: two MASK_VALUEs added up are
        # -inf, and a row of them a softmax of NaNs.
        visible &= same
    return jnp.where(visible, 0.0, MASK_VALUE)


# ------------------------------------------------------------- documents
#
# A call with ``segment_ids`` (a packed row: several documents end to end):
# a query sees the keys of its own document only, under the causal mask.
# The ids are made ``documents`` (``ops/ssm.py``: a run of equal ids a
# document, counted from 0, so they never fall along a row) and reach the
# three kernels as ONE float32 array [B, 1, S] through two blocks, rows
# along the lanes as the LSE's are: the streamed side's ids are read as that
# row, the resident side's are turned into lane-broadcast columns in VMEM
# scratch at the resident block's first step (as dq turns the LSE), and the
# mask is the triangle's and ``column == row`` in one ``where``.  Which
# pairs of blocks hold a visible element is data, and the grid is static:
# a second scalar-prefetch operand, ``meet`` [B * steps] int32, says of each
# step of the causal table whether the k block's last document is at least
# the q block's first (ids never fall, so then and only then they share
# one), and a step that does not meet runs nothing.  Nor does it fetch: a
# third, ``fetch`` [B * steps], names the streamed side's block of the last
# step that met (its own, where it meets), and the streamed operands' index
# maps read it, so the pipeline finds the block it holds and starts no copy
# (a skipped step of dk/dv cost 1.7 us with its four heads' q and dO
# fetched, the forward's 0.5; PERF.md, PR 65).  A q block's diagonal block
# always meets, so every row has seen itself when its block is written.
# One tile a grid step.  The kernels carry names of their own
# (``flash_seg_fwd_d64``).

def _documents(segment_ids):
    from .ssm import documents
    return documents(segment_ids)


def _meet(doc, sched, block_q, block_k, streamed):
    """(``meet``, ``fetch``) of a causal table (``block_schedule``'s rows)
    over blocks of ``block_q`` x ``block_k`` whose ``streamed`` side (``QI``
    or ``KI``) changes from step to step: int32 [B * steps] each."""
    first_q, last_k = doc[:, ::block_q], doc[:, block_k - 1::block_k]
    meet = last_k[:, sched[KI]] >= first_q[:, sched[QI]]
    # the last step that met, up to each one (step 0 before any has)
    last = jax.lax.cummax(jnp.where(meet, jnp.arange(sched.shape[1]), 0),
                          axis=1)
    return (meet.astype(jnp.int32).reshape(-1),
            jnp.asarray(sched[streamed])[last].reshape(-1))


def _seg_refs(refs, seg):
    """A kernel's refs of a call with documents, ``seg = (where the ids'
    blocks stand among them, grid rows a batch element)``: (whether the
    step's blocks meet, the q block's ids [1, 1, bq], the k block's, the
    resident side's as columns (scratch, the last), the refs a call without
    documents hands over)."""
    from jax.experimental import pallas as pl
    at, per_b = seg
    meet_ref, _fetch_ref, *refs, col_scr = refs
    meets = meet_ref[pl.program_id(0) // per_b * pl.num_programs(1)
                     + pl.program_id(1)] != 0
    return (meets, refs[at], refs[at + 1], col_scr,
            [*refs[:at], *refs[at + 2:]])


def _runs(step, meets=None):
    """Whether a grid step computes: the table says so, and in a call with
    documents its blocks meet."""
    runs = step & _RUN_BIT != 0
    return runs if meets is None else runs & meets


def _id_columns(row_ref, col_scr, heads=1):
    """The resident block's ids [1, 1, rows] as lane-broadcast columns
    [heads * rows, 128], the heads' rows one after another."""
    rows = row_ref.shape[-1]
    col = jnp.broadcast_to(row_ref[0], (LANES, rows)).T
    for h in range(heads):
        col_scr[h * rows:(h + 1) * rows, :] = col


# --------------------------------------------------------------- geometry

class Tiles(NamedTuple):
    """What one grid step of a kernel holds: ``heads`` query heads of one
    key head, ``block_q`` rows of each, against ``block_k`` keys; and which
    way round the scores are formed (``qk``: [q rows, keys]; ``kq``: [keys,
    q rows]).  ``tiles`` such pairs share a grid step: the pipeline fetches
    a major block of ``tiles`` blocks of the streamed side (keys in forward
    and dq, q rows in dk/dv) and the kernel walks them in order."""
    block_q: int
    block_k: int
    heads: int
    scores: str
    tiles: int = 1

    @property
    def major(self):
        """(q rows, keys) of a grid step's blocks: the streamed side's is a
        major block, ``tiles`` tiles long (q's in dk/dv, k's in forward and
        dq); the resident side's is the tile's."""
        of_q, of_k = (self.tiles, 1) if self.scores == "kq" else (
            1, self.tiles)
        return self.block_q * of_q, self.block_k * of_k


# The fallback, and what the chip's table (PERF.md, PR 33, step 0) says pays
# beside it on a v5e at head dim 128.
_BLOCK = 512            # 512 x 512 pairs, a head a grid row
_BIG_BLOCK = 1024       # no group to stack and no window: 1,024 x 1,024
_MAX_HEADS = 8          # query heads a step stacks: 4,096 rows at 512 each
_FWD_SCORES = 2 ** 20   # the forward's score tile, elements: halves block_k
_WALK = (8, 4, 2, 1)    # head size over 128: 512 x 512 tiles a grid step
# Head size over 128, a window narrower than a block and a group to stack:
# (q rows, keys) of forward's and dq's tile; dk/dv's is the other way round.
_BAND = (128, 256)
# The one pass (``bwd``): the bytes of float32 dq a grid row may keep in VMEM
# beside dk/dv's walk, and the tiles a step of it walks at the most.  From
# the chip's table (PERF.md, PR 54, step 0; ms a call of the backward / the
# scoped VMEM by the compiler's count).  [4, 16, 4096, 128] with ``rows``,
# 2 MiB of dq a row: the pair 6.946 at 1,024 x 1,024 (7.503 at 512 x 512),
# the one pass 4.806 / 11.3 MiB of a stated 56 (5.075 / 5.2 at 512 x 512).
# In parts at [1, 32, 8192], 128 + 64 / 128, 4 + 2 MiB a row: the pair 18.757
# (dk/dv 9.5 MiB at 8 tiles a step), the one pass 13.072 / 11.6 at 2 tiles
# and 12.737 / 13.1 at 4, of the default's 16 and with no limit stated; with
# the rotary part's sums as rows ([Sq, 64], lane-padded 4 MiB) 14.397 / 12.9
# at 1, 13.770 / 13.6 at 2, 13.434 / 15.1 at 4.  Six MiB and four tiles are
# the most that leave the default limit the room PR 52 left it.
_DQ_ROW = 6 * 2 ** 20
_BWD_WALK = 4


def _tiles(kind, Sq, Sk, D, group, window=None, Dr=0):
    """The geometry of a grid step of kernel ``kind`` (``fwd``, ``dq``,
    ``dkv``; ``bwd``: the one pass in place of the last two, or None where
    the call keeps the pair), from the shapes of the call alone; ``D`` is
    the larger of the call's two head sizes, ``Dr`` of them a call in
    parts' rotary lanes.

    - A key head's query heads share one step (the most that divide the
      group, up to 8): forward and dq stack their rows behind each tile of
      K / V, dk/dv adds them into the one resident block.  With 4,096 rows
      stacked the forward takes its keys 256 at a time.
    - With no group to stack, no window and at least four of them a side,
      blocks of 1,024 x 1,024: fewer, larger steps win over the extra work
      on the diagonal (40 units of 512 x 512 for 36 at 4,096 tokens); under
      a window they waste at both edges of the band and lose.
    - dk/dv always forms its scores transposed (``kq``).
    - Anything else (a head size over 128, as latent attention's 192 / 128;
      a length the larger blocks do not divide) keeps 512 x 512 and a head
      a step.  At 192 / 128 alone the three kernels are 5 % faster at
      1,024 x 1,024 (25.97 ms for 27.41 at [1, 32, 8192]); the train step
      that held them did not return from its first call (PERF.md, PR 40).
    - A head size over 128 and no window: a grid step walks the most of 8,
      4, 2, 1 tiles of 512 x 512 that divide the streamed side's blocks
      (k's in forward and dq, q's in dk/dv): 8 at 8,192 tokens, 24 grid
      steps a head for the same 136 tiles.  From the chip's table (PERF.md,
      PR 52, step 0; the call in parts at [1, 32, 8192], ms a call of the
      three kernels / the most scoped VMEM one of them holds): 1 tile
      26.69 / 4.3 MiB, 2 25.42 / 5.0, 4 24.79 / 6.5, 8 24.60 / 9.5, 16
      24.12 / 15.5 of the default's 16.  In Kanana's step 2,747 ms at 1,
      2,631 at 4, 2,617 at 8, 2,591 at 16.  Eight ships: the most that
      leaves the default limit room.  The 128-wide shapes keep one tile a
      step: whether Yi's 1,024 x 1,024 (a quarter of each diagonal block
      computed for nothing) or the stacked groups want the walk is not
      measured.
    - A head size over 128 under a GROUP (latent keys decompressed into
      fewer key heads than query heads: 80 over 16, five a key head), from
      the chip's table (PERF.md, PR 57, step 0; the call in parts at
      [1, 80 / 16, 8192], ms a call of the three kernels / of everything
      round them too / the most scoped VMEM one of them holds).  **No
      window**: a head a grid row as without a group, the forward and dq at
      8 tiles a step reading their key head's blocks, and the one pass with
      each query head's share of dk/dv leaving in float32 and summed
      outside: 46.72 / 51.34 / 13.6 MiB, against the pair 62.22 / 66.93 /
      10.0 and the pair with the group's five heads added up inside dk/dv
      59.51 / 60.93 / 13.1; the forward with the five stacked (128 x 256)
      17.74 for 14.89.  **A window narrower than a block** (128): the band
      inside 512 x 512 tiles computes eight times what it keeps and a head
      a step pays a grid step's costs 31 times a head for it, 16.89 /
      21.60 / 4.7; 256 x 256 15.18 / 19.88, 128 x 128 23.72 / 28.42.  With
      the group's heads one grid step (the most that divide it, up to 8:
      their rows stacked behind each tile of keys in forward and dq, added
      into the one resident block in dk/dv, which then leaves a key head
      in the inputs' dtype) 128 x 128 reads 9.18 / 10.57 / 3.1, 256 x 128
      9.53 / 10.92 / 6.2 and 128 x 256 **8.46 / 9.85 / 3.6**; dk/dv alone
      2.80 at 256 x 128 for 3.27 - 3.30.  So forward and dq take 128 x 256
      and dk/dv 256 x 128, a stacked step's score tile inside the 512 x
      512 elements past which ``_compiler_params`` would state a limit.  A
      window of a block or more, and one with no group to stack, keep 512 x
      512 and a head a step (256 x 256 is 8 % faster there: not shipped,
      no caller).
    - A head size under 128 (LFM2's 64) takes the answers of 128: at
      [4, 32 / 8, 8192, 64] the three kernels read 68.9 ms at 512 x 512 with
      the group's 4 heads stacked, 70.7 - 71.8 with either block at 1,024
      and 74.0 - 74.4 with either at 256 (PERF.md, PR 47).  The same
      operations at [4, 16 / 4, 8192, 128] take 33.4 ms: a score product
      that contracts over 64 fills half of the MXU's 128 x 128 tile.
    - ``bwd``: the K-major walk makes dq too, dk/dv's geometry at
      ``_BWD_WALK`` tiles a step at the most, where a grid row is one query
      head, ``Sq == Sk`` (with the blocks equal, q tile ``i`` is then
      complete at k block ``i``; ``_flash_backward`` adds that the call is
      causal with no offset) and the row's float32 dq fits ``_DQ_ROW``:
      [Sq, D] lane-padded, a call in parts' rotary lanes lying along the
      lanes, [Dr, Sq], for nothing.  Yi's and Ouro's [4, 16, 4096, 128]
      hold 2 MiB, latent attention's [1, 32, 8192] in parts 4 + 2; 128-wide
      rows over 12,288 tokens and a 192-wide call in one part over 6,144
      keep the pair.
    - ``bwd`` under a GROUP at a head size of 128 or under: a grid row is
      ONE query head all the same (the pair stacks the group, whose dq
      would want 8 - 32 MiB a row), 512 x 512 and the most of 4, 2, 1 tiles
      a step, reading its key head's resident K / V block; each query
      head's share of dk/dv leaves in float32 and the shares are summed
      outside, the float32 sum the pair makes inside its kernel.  From the
      chip's table (PERF.md, PR 60, step 0; head-major, ms a call of the
      backward's kernels / with delta and the shares' sum too / the scoped
      VMEM of the default's 16 MiB, no limit stated):

          [1, 32 / 2, 8192, 128]: the pair 11.008 / 11.325; the one pass
            8.920 / 9.570 at 1 tile, 8.584 / 9.234 at 2, 8.396 / 9.046 /
            7.7 MiB at 4; 8.392 / 9.042 / 12.8 at 1,024 x 1,024
          [1, 32 / 4, 8192, 128]: 11.043 / 11.331; 8.920 / 9.601, 8.584 /
            9.266, 8.396 / 9.078 / 7.7; 8.392 / 9.075 / 12.8
          [1, 32 / 8, 4096, 128]: 3.124 / 3.315; 2.395 / 2.671, 2.311 /
            2.587, 2.262 / 2.537 / 5.5; 2.362 / 2.637 / 10.3
          [4, 32 / 8, 8192, 64]: 45.887 / 49.358; 37.109 / 41.905, 35.111 /
            39.905, 34.408 / 39.204 / 9.0; 33.948 / 38.744 / 13.8

      (the pair's dq holds 20.0 of a stated 96 MiB at 8 stacked heads).  So
      -20 % at 8,192 x 128 whatever the group (16 shares or 8), -23 % at
      Mistral's 4,096, -21 % at LFM2's 64; 1,024 x 1,024 is 1 % ahead at 64
      and 4 % behind at 4,096 for half as much VMEM again, and four tiles
      of 512 x 512 ship everywhere.  Two heads a grid row at Mistral's shape
      (their dq 4 MiB; dk/dv's shares halved) read 2.188 / 2.370 / 11.8 at 4
      tiles, 7 % under one head's: not shipped, the kernel body would index
      its dq scratch by head for 0.3 % of one cell's step (ROADMAP S6 (b)).
    - ``bwd`` under a group AND a window (Trinity-Mini's 2,048 at
      [1, 32 / 4, 8192, 128]): a q tile is still complete at its diagonal k
      block, the table drops the pairs left of the band, and the same
      kernel body serves: the pair 5.847 / 6.135, the one pass 4.742 /
      5.427 / 7.0 at ONE tile a step (-12 %), 4.959 / 5.645 at 2 and 5.602
      / 6.288 at 4, whose major blocks reach past the band's lower edge
      and walk tiles that hold nothing.  So one tile a step under a
      window.  A window with no group at 128 and any window over 128 keep
      the pair: not measured, no caller."""
    if kind == "bwd":
        t = _tiles("dkv", Sq, Sk, D, group, window)
        if D <= LANES and t.heads > 1:
            # A group's query heads a grid row each, in dk/dv's blocks.
            t = Tiles(t.block_q, t.block_k, 1, "kq",
                      _walked(Sq // t.block_q, window))
        elif window is not None:
            return None
        if (t.heads > 1 or Sq != Sk
                or Sq * (-(-(D - Dr) // LANES) * LANES + Dr) * 4 > _DQ_ROW):
            return None
        return t._replace(tiles=min(t.tiles, _BWD_WALK))
    scores = "kq" if kind == "dkv" else "qk"
    block_q, block_k, heads = min(_BLOCK, Sq), min(_BLOCK, Sk), 1
    # the most of a key head's query heads that a step could hold
    stacked = max(h for h in range(1, min(group, _MAX_HEADS) + 1)
                  if group % h == 0)
    if D > LANES:
        if window is not None and window < _BLOCK and stacked > 1:
            tall, wide = (_BAND[1], _BAND[0]) if kind == "dkv" else _BAND
            if Sq % tall == 0 and Sk % wide == 0:
                return Tiles(tall, wide, stacked, scores)
        streamed = Sq // block_q if kind == "dkv" else Sk // block_k
        return Tiles(block_q, block_k, heads, scores,
                     _walked(streamed, window))
    heads = stacked
    if heads > 1:
        if (kind == "fwd" and heads * block_q * block_k > _FWD_SCORES
                and block_k % (2 * LANES) == 0):
            block_k //= 2
    elif (window is None and min(Sq, Sk) >= 4 * _BIG_BLOCK
          and Sq % _BIG_BLOCK == 0 and Sk % _BIG_BLOCK == 0):
        block_q = block_k = _BIG_BLOCK
    return Tiles(block_q, block_k, heads, scores)


def _walked(streamed, window):
    """The tiles a grid step walks: the most of ``_WALK`` that divide the
    streamed side's blocks; one under a window, where a major block would
    reach past the band."""
    if window is not None:
        return 1
    return next(n for n in _WALK if streamed % n == 0)


class Dims(NamedTuple):
    """A call's sizes, whatever the layout of its operands.  ``D`` is the
    whole width of a score's contraction; a call in parts has ``Dr`` of it
    in its rotary parts (0: q and k are one operand each)."""
    B: int
    H: int
    Hkv: int
    Sq: int
    Sk: int
    D: int
    Dv: int
    Dr: int = 0


def _dims(q, k, v):
    """``Dims`` of a call: q and k are head-major whatever v is; in parts
    (``flash_attention``) q's and k's first lie as rows, and k's holds v."""
    if _in_parts(q):
        (q_n, q_r), (kv, k_r) = q, k
        (B, Sq, H, Dn), (Sk, Hkv), Dr = q_n.shape, kv.shape[1:3], \
            q_r.shape[-1]
        if (v is not None or q_r.shape != (B, H, Sq, Dr)
                or kv.shape[:2] != (B, Sk) or H % Hkv or kv.shape[3] <= Dn
                or k_r.shape != (B, 1, Sk, Dr)):
            raise ValueError(
                f"a call in parts takes q ([B, Sq, H, Dn], [B, H, Sq, Dr]), "
                f"k ([B, Sk, Hkv, Dn + Dv] with H % Hkv == 0, [B, 1, Sk, "
                f"Dr]) and no v: got "
                f"{[x.shape for x in (q_n, q_r, kv, k_r)]} and v "
                f"{None if v is None else v.shape}")
        return Dims(B, H, Hkv, Sq, Sk, Dn + Dr, kv.shape[3] - Dn, Dr)
    (B, H, Sq, D), (Hkv, Sk) = q.shape, k.shape[1:3]
    if H % Hkv:
        raise ValueError(f"H={H} not divisible by Hkv={Hkv}")
    return Dims(B, H, Hkv, Sq, Sk, D, v.shape[-1])


def _in_parts(q):
    return isinstance(q, tuple)


def _operands(q, k, v):
    """A call's q, k and v as the kernels take them, by ``Specs``' names."""
    if _in_parts(q):
        return {"q": q[0], "q_r": q[1], "kv": k[0], "k_r": k[1]}
    return {"q": q, "k": k, "v": v}


def _geometry(kind, dims, block_q, block_k, window, rows, sink=False,
              seg=False):
    """``_tiles``' answer for this call, an explicit block size winning (and
    walked one tile a grid step), checked against the lengths and counted.
    ``seg``: a call with documents, one tile a grid step."""
    _, H, Hkv, Sq, Sk, D, Dv, Dr = dims
    t = _tiles(kind, Sq, Sk, max(D, Dv), H // Hkv, window, Dr)
    if t is None:               # ``bwd``: this call keeps the pair
        return None
    if seg:
        t = t._replace(tiles=1)
    if block_q or block_k:      # the blocks a call names, one a grid step
        t = t._replace(block_q=min(block_q or t.block_q, Sq),
                       block_k=min(block_k or t.block_k, Sk), tiles=1)
        if kind == "bwd" and not t.block_q == t.block_k <= _BIG_BLOCK:
            return None
    if Sq % t.block_q or Sk % t.block_k:
        raise ValueError(f"seq ({Sq},{Sk}) not divisible by blocks "
                         f"({t.block_q},{t.block_k})")
    # the grid rows whose dk / dv are summed into a key head's outside
    shares = H // Hkv // t.heads if kind in ("dkv", "bwd") else 1
    telemetry.inc("ray_tpu_flash_step_geometry_total", tags={
        "kernel": _kernel_name(f"flash_{'seg_' if seg else ''}{kind}",
                               window, D, Dv, sink),
        "block_q": str(t.block_q), "block_k": str(t.block_k),
        "heads_a_step": str(t.heads), "scores": t.scores,
        **({"tiles_a_step": str(t.tiles)} if t.tiles > 1 else {}),
        **({"shares": str(shares)} if shares > 1 else {}),
        **({"d_qk": str(D), "d_v": str(Dv)} if D != Dv
           else {} if D == LANES else {"d": str(D)}),
        **({"parts": f"{D - Dr}+{Dr}", "rows": "qkvo"} if Dr
           else {"rows": "vo"} if rows else {})})
    return t


# A step's block of ``heads`` query heads is [1, heads, rows, n] of a
# head-major operand, or [1, rows, heads * n] of one that lies as rows (the
# result and its cotangent under ``rows``: [B, S, H * n], what a projection
# writes and reads): a head is then a slice of the lanes, on a tile's edge
# since n % 128 == 0 (``flash_attention`` sees to it).  The three below
# are all a kernel body knows of the layout.  A call in parts has every
# 128-wide operand as rows, and a head's key without position and its value
# side by side in one block of ``kv`` (``_k_and_v``).

def _head(ref, h, heads, as_rows=False, j=None, block=None):
    """Head ``h`` of a step's block: [rows, n]; of tile ``j`` of a streamed
    major block (``_tile``), its ``block`` rows."""
    if not as_rows:
        return _tile(ref, j, block, h)
    n = ref.shape[-1] // heads
    return _tile(ref, j, block, lanes=slice(h * n, (h + 1) * n))


def _rows(ref, heads=None, as_rows=False):
    """A step's block as [heads * rows, n]: the heads one after another."""
    if not as_rows:
        x = ref[0]
        return x.reshape(x.shape[0] * x.shape[1], x.shape[2])
    if heads == 1:
        return ref[0]
    return jnp.concatenate(
        [_head(ref, h, heads, True) for h in range(heads)], axis=0)


def _write_rows(ref, x, heads, as_rows=False):
    """``_rows``' inverse: x [heads * rows, n] into a step's block."""
    x = x.astype(ref.dtype)
    if not as_rows:
        ref[0] = x.reshape(ref.shape[1:])
        return
    rows, n = ref.shape[1], ref.shape[-1] // heads
    for h in range(heads):
        ref[0, :, h * n:(h + 1) * n] = x[h * rows:(h + 1) * rows]


def _k_and_v(kv_ref, Dn):
    """A call in parts: the views of a ``kv`` block (or of its gradient's)
    that are the key without position and the value, [1, bk, Dn] and
    [1, bk, Dv]: lane slices on a tile's edge."""
    return kv_ref.at[:, :, :Dn], kv_ref.at[:, :, Dn:]


def _scores(a, b, a_r=None, b_r=None):
    """``a b^T`` in float32, [rows of a, rows of b]; a call in parts adds
    its rotary parts' product ``a_r b_r^T`` to it there: the contraction
    over 128 + 64 that the MXU makes of a 192-wide one."""
    over_lanes = (((1,), (1,)), ((), ()))
    s = jax.lax.dot_general(a, b, over_lanes,
                            preferred_element_type=jnp.float32)
    if a_r is not None:
        s += jax.lax.dot_general(a_r, b_r, over_lanes,
                                 preferred_element_type=jnp.float32)
    return s


# A grid step of ``tiles`` tiles (``Tiles.tiles``): the streamed operands'
# blocks are a major block, ``tiles`` blocks long, and the body does for each
# tile of it that holds a visible element what a grid step of one tile does,
# in the same order, on the same scratch.  With one tile a step the three
# below hand the kernels what they read before, and trace nothing new.

def _visible_tiles(qi, ki, block_q, block_k, q_offset, causal, scores,
                   tiles):
    """(first, stop) of the tiles of a step's major block that hold a
    visible element.  ``qk`` (forward, dq: q block ``qi`` resident, major
    k block ``ki``): the tiles whose first key is no later than the q
    block's last row.  ``kq`` (dk/dv: k block ``ki`` resident, major q block
    ``qi``): the tiles whose last row is no earlier than the k block's first
    key.  On traced scalars in a kernel and on plain integers alike."""
    if not causal:
        return 0, tiles
    if scores == "qk":
        seen = ((qi + 1) * block_q - 1 + q_offset) // block_k + 1
        return 0, jnp.minimum(seen - ki * tiles, tiles)
    unseen = jnp.maximum(ki * block_k - q_offset, 0) // block_q
    return jnp.maximum(unseen - qi * tiles, 0), tiles


def _walk(tile, tiles, *step):
    """``tile(j)`` for the visible tiles of a step's major block, in order
    (``step``: ``_visible_tiles``' other arguments); a step of one tile is
    ``tile(None)``.  A loop and the tiles unrolled, each under its guard,
    read the same on the chip to 0.1 % (PERF.md, PR 52): the loop is the
    shorter program."""
    if tiles == 1:
        return tile(None)
    jax.lax.fori_loop(*_visible_tiles(*step, tiles),
                      lambda j, _: tile(j), None)


def _tile(ref, j, block, *head, lanes=None, along=0):
    """Tile ``j`` of a streamed block, ``block`` rows of ``ref[0, *head]``
    (``lanes``: that slice of their lanes; ``along=1``: ``block`` lanes of
    its rows, LSE's and delta's); ``j`` None: the block is the tile."""
    if j is None:
        at = ()
    else:
        from jax.experimental import pallas as pl
        at = (slice(None),) * along + (
            pl.ds(pl.multiple_of(j * block, block), block),)
    if lanes is not None:
        at = (at or (slice(None),)) + (lanes,)
    return ref[(0, *head, *at)]


def _tile_index(i, j, tiles):
    """The block index of tile ``j`` of major block ``i``."""
    return i if j is None else i * tiles + j


# ---------------------------------------------------------------- forward

def _fwd_kernel(sched_ref, *refs, causal, scale, block_q, block_k, q_offset,
                window=None, rows=False, parts=False, tiles=1, sink=False,
                seg=None):
    # lse_ref is None when the caller doesn't need residuals (inference).
    from jax.experimental import pallas as pl

    if seg:
        meets, idq_ref, idk_ref, idcol_scr, refs = _seg_refs(refs, seg)
    *_, m_scr, l_scr, acc_scr = refs
    heads = m_scr.shape[0] // block_q
    *ins, o_ref, lse_ref = refs[:-3]
    if sink:        # the step's heads' sinks, [1, heads, 1, 128] along lanes
        *ins, sink_ref = ins
    if parts:
        q_ref, qr_ref, kv_ref, kr_ref = ins
        k_ref, v_ref = _k_and_v(kv_ref, q_ref.shape[-1] // heads)
    else:
        q_ref, k_ref, v_ref = ins
    step = sched_ref[pl.program_id(1)]
    qi, ki = _step_qi(step), _step_ki(step)

    @pl.when(step & _FIRST_BIT != 0)
    def _init():
        if sink:
            # A row starts as if it had seen the sink's column: max b_h,
            # sum 1, and nothing accumulated (the column's value is zero).
            for h in range(heads):
                m_scr[h * block_q:(h + 1) * block_q, :] = jnp.broadcast_to(
                    sink_ref[0, h], (block_q, LANES))
            l_scr[...] = jnp.ones(l_scr.shape, jnp.float32)
        else:
            m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        if seg:
            _id_columns(idq_ref, idcol_scr, heads)

    def tile(j):
        q = _rows(q_ref, heads, parts)                 # [heads * bq, D]
        k = _tile(k_ref, j, block_k)                   # [bk, D]
        q_r, k_r = ((_rows(qr_ref), _tile(kr_ref, j, block_k)) if parts
                    else (None, None))
        s = _scores(q, k, q_r, k_r) * scale            # [heads * bq, bk]
        if causal:
            s = s + _causal_mask_bias(
                s.shape[0], block_k, qi, _tile_index(ki, j, tiles), block_q,
                block_k, q_offset, window, same=_bcast_lanes(
                    idcol_scr[...], block_k) == idk_ref[0] if seg else None)
        m_prev = m_scr[...]                            # [heads * bq, 128]
        l_prev = l_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _bcast_lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_scr[...] = m_next
        v = _tile(v_ref, j, block_k)
        pv = jax.lax.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * _bcast_lanes(alpha, acc_scr.shape[1]) \
            + pv

    @pl.when(_runs(step, meets if seg else None))
    def _step():
        _walk(tile, tiles, qi, ki, block_q, block_k, q_offset, causal,
              "qk")

    @pl.when(step & _LAST_BIT != 0)
    def _finish():
        l = l_scr[...]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        _write_rows(o_ref, acc_scr[...] * _bcast_lanes(
            l_inv, acc_scr.shape[1]), heads, rows)
        if lse_ref is not None:
            # Out as rows along the lanes, [heads, 1, bq]: what the backward
            # kernels read, and 1/128 of the lane-broadcast columns.
            lse = m_scr[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))
            for h in range(heads):
                lse_ref[0, h] = lse[h * block_q:(h + 1) * block_q].T[:1]


def _kernel_name(base, window, D=None, Dv=None, sink=False):
    """A windowed call carries its window in its name, so that a device
    trace tells it from a full-causal call (``flash_fwd_w2048``), and a
    call whose values are not as wide as its keys both head sizes
    (``flash_fwd_d192v128``), one whose one head size is not 128 that size
    (``flash_fwd_d64``); a forward whose rows start at a sink says so last
    (``flash_fwd_d192v128_w128_sink``)."""
    if D != Dv:
        base = f"{base}_d{D}v{Dv}"
    elif D is not None and D != LANES:
        base = f"{base}_d{D}"
    if window is not None:
        base = f"{base}_w{window}"
    return f"{base}_sink" if sink else base


def _flash_forward(q, k, v, causal, scale, block_q, block_k, q_offset,
                   interpret, *, need_lse, window=None, rows=False,
                   sink=None, doc=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dims = _dims(q, k, v)
    B, H, Hkv, Sq, Sk, D, Dv, _ = dims
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    with_sink = sink is not None
    seg = doc is not None
    t = _geometry("fwd", dims, block_q, block_k, window, rows, with_sink,
                  seg)
    sched = _packed_schedule(Sq, Sk, *t.major, q_offset, causal, "q", window)
    n, stacked = B * H // t.heads, t.heads * t.block_q

    sp = _specs(t, dims, rows)
    operands = _operands(q, k, v)
    dtype = operands["q"].dtype

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=t.block_q,
        block_k=t.block_k, q_offset=q_offset, window=window, rows=rows,
        **({"parts": True} if dims.Dr else {}),
        **({"tiles": t.tiles} if t.tiles > 1 else {}),
        **({"sink": True} if with_sink else {}),
        **({"seg": (len(operands), H // t.heads)} if seg else {}))
    in_specs = [getattr(sp, name) for name in operands]
    handed = [x.reshape(sp.shapes[name]) for name, x in operands.items()]
    prefetch, scratch = [sched], []
    if seg:
        ids = doc.astype(jnp.float32).reshape(B, 1, Sq)
        in_specs += [sp.ids_q, sp.ids_k]
        handed += [ids, ids]
        prefetch += _meet(doc, block_schedule(
            Sq, Sk, *t.major, q_offset, causal, "q", window), *t.major, KI)
        scratch.append(_vmem((stacked, LANES), jnp.float32))
    if with_sink:
        # A head's sink along the lanes, a grid row's heads a block: 512 B
        # a head in HBM, and the kernel reads a [1, 128] row as it reads
        # the backward's LSE.
        in_specs.append(pl.BlockSpec((1, t.heads, 1, LANES),
                                     lambda r, s, sched, *_: (r, 0, 0, 0)))
        handed.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            (B, H, 1, LANES)).reshape(n, t.heads, 1, LANES))

    out_specs = [sp.o]
    out_shape = [jax.ShapeDtypeStruct(sp.shapes["o"], dtype)]
    if need_lse:
        out_specs.append(sp.row)
        out_shape.append(
            jax.ShapeDtypeStruct((n, t.heads, 1, Sq), jnp.float32))
    else:
        # No LSE output at all: nothing of it is computed or written.
        with_lse, n_io = kernel, len(handed) + len(prefetch)

        def kernel(sched, *refs):
            return with_lse(sched, *refs[:n_io], None, *refs[n_io:])

    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n, sched.size),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                _vmem((stacked, LANES), jnp.float32),
                _vmem((stacked, LANES), jnp.float32),
                _vmem((stacked, Dv), jnp.float32),
                *scratch]),
        out_shape=out_shape,
        interpret=interpret,
        name=_kernel_name("flash_seg_fwd" if seg else "flash_fwd", window, D,
                          Dv, with_sink),
        **_compiler_params(interpret, stacked, t.block_k),
    )(*prefetch, *handed)
    out = res[0].reshape((B, Sq, H, Dv) if rows else (B, H, Sq, Dv))
    if not need_lse:
        return out, None
    return out, res[1].reshape(B, H, Sq)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


class Specs(NamedTuple):
    """Block specs of a kernel's operands, and the shape each is handed
    over in: a reshape of the caller's array, never a copy.  A call has
    k, v, dk and dv, or in parts q_r, kv, k_r, dkv and dk_r."""
    q: object       # blocks by q block of t.heads query heads (q, dq)
    o: object       # the same at the values' head size (o, do)
    row: object     # rows along the lanes of [n, heads, 1, S] (LSE, delta)
    shapes: dict    # name -> the shape that operand is handed over in
    k: object = None        # blocks by k block of the grid row's key head
    v: object = None        # the same at the values' head size
    dk: object = None       # blocks by k block, a grid row's own
    dv: object = None       # the same at the values' head size
    # A call in parts: q is the part without position, as rows like o.
    q_r: object = None      # the rotary part of q (and of dq), head-major
    kv: object = None       # a head's key without position and its value
    k_r: object = None      # the one rotary key head, whatever the grid row
    dkv: object = None      # kv's gradient, a grid row's own
    dk_r: object = None     # a grid row's own share of k_r's
    # The one pass: q's gradient leaves the K-major walk a tile a k block.
    dq: object = None       # the q tile at the resident k block's index
    dq_r: object = None     # the same of q_r's gradient
    # A call with documents: the ids [B, 1, S], rows along the lanes.
    ids_q: object = None    # the q block's
    ids_k: object = None    # the k block's


def _specs(t, dims, rows=False):
    """Block specs of a grid (n, steps) at geometry ``t``, the blocks coming
    from the prefetched schedule; a grid row is ``t.heads`` query heads of
    one key head.  A head-major operand is [n, heads, S, D] (q, o) or
    [B * Hkv, S, D] (k, v) and a block is whole heads; with ``rows`` the
    operands at the values' head size (v, o, dv) lie as [B, S, H * Dv] and
    a block is the ``block`` rows of the step's heads' lanes, ``heads *
    Dv`` of them at lane-block ``r`` modulo the grid rows of a batch
    element.  In a call in parts (``dims.Dr``) q is the part without
    position and lies as rows too; ``kv`` [B, Sk, H * (Dn + Dv)] is one
    rows operand whose block holds a head's key and value side by side;
    the rotary parts are head-major ``Dr`` wide, and the one rotary key
    head [B, Sk, Dr] is every grid row's: its index map takes the batch
    element of the row and no head, so the head is never laid out 32 times
    in HBM."""
    from jax.experimental import pallas as pl
    B, H, Hkv, Sq, Sk, D, Dv, Dr = dims
    group, n = H // Hkv, B * H // t.heads
    per_b = H // t.heads            # grid rows a batch element
    # The streamed side's blocks are major blocks (dk and dv are their grid
    # row's resident block's).
    block_q, block_k = t.major

    def block_of(side):
        """The index map's block of the ``q`` or the ``k`` side at a step:
        the table's; of the STREAMED side of a call with documents (two
        more scalar-prefetch operands, ``_meet``'s), the block of the last
        step that met."""
        plain = _step_qi if side == "q" else _step_ki
        streamed = (side == "k") == (t.scores == "qk")

        def block(r, s, sched, *docs):
            if docs and streamed:
                return docs[1][r // per_b * sched.shape[0] + s]
            return plain(sched[s])
        return block

    q_block, k_block = block_of("q"), block_of("k")

    def of_q(d, as_rows, block_q=block_q, at=q_block):
        """(spec, shape) of a q-side operand of head size ``d``; the one
        pass's dq is a tile of it ``at`` the resident k block's index."""
        if as_rows:
            return (pl.BlockSpec(
                (1, block_q, t.heads * d), lambda r, s, sched, *docs: (
                    r // per_b, at(r, s, sched, *docs), r % per_b)),
                (B, Sq, H * d))
        return (pl.BlockSpec(
            (1, t.heads, block_q, d), lambda r, s, sched, *docs: (
                r, 0, at(r, s, sched, *docs), 0)),
            (n, t.heads, Sq, d))

    def of_k(d, as_rows, own=False):
        """(spec, shape) of a k-side operand of head size ``d``: the block
        of the grid row's key head, or with ``own`` the grid row's own (dk,
        dv: one a step's heads)."""
        heads = per_b if own else Hkv       # of them a batch element
        head = (lambda r: r) if own else (lambda r: r * t.heads // group)
        if as_rows:
            return (pl.BlockSpec(
                (1, block_k, d), lambda r, s, sched, *docs: (
                    head(r) // heads, k_block(r, s, sched, *docs),
                    head(r) % heads)),
                (B, Sk, heads * d))
        return (pl.BlockSpec(
            (1, block_k, d), lambda r, s, sched, *docs: (
                head(r), k_block(r, s, sched, *docs), 0)),
            (B * heads, Sk, d))

    if Dr:
        made = {"q": of_q(D - Dr, True), "o": of_q(Dv, True),
                "q_r": of_q(Dr, False), "kv": of_k(D - Dr + Dv, True),
                "dkv": of_k(D - Dr + Dv, True, own=True),
                "dk_r": of_k(Dr, False, own=True),
                "k_r": (pl.BlockSpec(
                    (1, block_k, Dr), lambda r, s, sched, *_: (
                        r // per_b, _step_ki(sched[s]), 0)), (B, Sk, Dr)),
                "dq": of_q(D - Dr, True, t.block_q, k_block),
                "dq_r": of_q(Dr, False, t.block_q, k_block)}
    else:
        made = {"q": of_q(D, False), "o": of_q(Dv, rows),
                "k": of_k(D, False), "v": of_k(Dv, rows),
                "dk": of_k(D, False, own=True),
                "dv": of_k(Dv, rows, own=True),
                "dq": of_q(D, False, t.block_q, k_block)}
    return Specs(
        ids_q=pl.BlockSpec((1, 1, block_q), lambda r, s, sched, *docs: (
            r // per_b, 0, q_block(r, s, sched, *docs))),
        ids_k=pl.BlockSpec((1, 1, block_k), lambda r, s, sched, *docs: (
            r // per_b, 0, k_block(r, s, sched, *docs))),
        row=pl.BlockSpec((1, t.heads, 1, block_q), lambda r, s, sched, *docs: (
            r, 0, 0, q_block(r, s, sched, *docs))),
        shapes={name: shape for name, (_, shape) in made.items()},
        **{name: spec for name, (spec, _) in made.items()})


# A step's float32 tiles (scores, probabilities and their like) above which
# the kernel asks for more than Mosaic's default 16 MiB of scoped VMEM.
_VMEM_DEFAULT_TILE = 512 * 512


def _compiler_params(interpret, rows, cols):
    from jax.experimental.pallas import tpu as pltpu
    if interpret:
        return {}
    params = dict(dimension_semantics=("parallel", "arbitrary"))
    if rows * cols > _VMEM_DEFAULT_TILE:
        # About ten live [rows, cols] float32 tiles at the worst.
        params["vmem_limit_bytes"] = min(
            100 * 2 ** 20, 16 * 2 ** 20 + 40 * rows * cols)
    return {"compiler_params": pltpu.CompilerParams(**params)}


# ---------------------------------------------------------------- backward

def _dq_kernel(sched_ref, *refs, causal, scale, block_q, block_k, q_offset,
               window=None, rows=False, parts=False, tiles=1, seg=None):
    """``lse`` and ``di`` arrive as rows along the lanes (as dk/dv reads
    them); the resident q block's first step turns them into the
    lane-broadcast columns [heads * bq, 128] the steps subtract.  In parts,
    dq leaves in q's two."""
    from jax.experimental import pallas as pl

    if seg:
        meets, idq_ref, idk_ref, idcol_scr, refs = _seg_refs(refs, seg)
    if parts:
        (q_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref, di_ref,
         dq_ref, dqr_ref, dq_scr, dqr_scr, lse_scr, di_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr,
         lse_scr, di_scr) = refs
    heads = lse_ref.shape[1]
    if parts:
        k_ref, v_ref = _k_and_v(kv_ref, q_ref.shape[-1] // heads)
    step = sched_ref[pl.program_id(1)]
    qi, ki = _step_qi(step), _step_ki(step)

    @pl.when(step & _FIRST_BIT != 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
        if parts:
            dqr_scr[...] = jnp.zeros(dqr_scr.shape, jnp.float32)
        for h in range(heads):
            of_h = slice(h * block_q, (h + 1) * block_q)
            for row_ref, col_scr in ((lse_ref, lse_scr), (di_ref, di_scr)):
                col_scr[of_h, :] = jnp.broadcast_to(
                    row_ref[0, h], (LANES, block_q)).T
        if seg:
            _id_columns(idq_ref, idcol_scr, heads)

    def tile(j):
        q = _rows(q_ref, heads, parts)                 # [heads * bq, D]
        do = _rows(do_ref, heads, rows)
        k = _tile(k_ref, j, block_k)
        v = _tile(v_ref, j, block_k)
        q_r, k_r = ((_rows(qr_ref), _tile(kr_ref, j, block_k)) if parts
                    else (None, None))
        s = _scores(q, k, q_r, k_r) * scale
        if causal:
            s = s + _causal_mask_bias(
                s.shape[0], block_k, qi, _tile_index(ki, j, tiles), block_q,
                block_k, q_offset, window, same=_bcast_lanes(
                    idcol_scr[...], block_k) == idk_ref[0] if seg else None)
        p = jnp.exp(s - _bcast_lanes(lse_scr[...], s.shape[1]))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _bcast_lanes(di_scr[...], s.shape[1])) * scale
        dq_scr[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)
        if parts:
            dqr_scr[...] += jax.lax.dot(ds.astype(k.dtype), k_r,
                                        preferred_element_type=jnp.float32)

    @pl.when(_runs(step, meets if seg else None))
    def _step():
        _walk(tile, tiles, qi, ki, block_q, block_k, q_offset, causal,
              "qk")

    @pl.when(step & _LAST_BIT != 0)
    def _finish():
        _write_rows(dq_ref, dq_scr[...], heads, parts)
        if parts:
            _write_rows(dqr_ref, dqr_scr[...], heads)


def _dkv_kernel(sched_ref, *refs, causal, scale, block_q, block_k, q_offset,
                window=None, rows=False, parts=False, tiles=1, seg=None):
    """The scores are formed transposed, ``k q^T`` [bk, bq], so that dv =
    p^T do and dk = ds^T q are plain products; ``lse`` and ``di`` are rows
    along the lanes.  The step's heads add into the one resident dk / dv.
    In parts, dk and dv leave side by side as ``kv`` came, and the grid
    row's share of the one rotary head's gradient beside them.

    **The one pass** (``flash_bwd``: a call hands dq's refs too, its
    results after dk / dv's and its scratch before theirs).  A grid row is
    one query head, ``block_q == block_k`` and the call is the causal
    square, so the tile's ``dst`` is all dq needs: ``dq[q tile] += dst^T
    k`` into a float32 scratch that holds the row's whole dq, [Sq, D]
    (in parts [Sq, Dn], and the rotary lanes' ``k_r^T dst`` along the
    lanes, [Dr, Sq]: half the VMEM of 64 lanes padded, and the faster of
    the two on the chip), zeroed at the row's first step.  The table
    visits k blocks in ascending order, so q tile ``i`` is complete at k
    block ``i``'s last step and leaves then, through a block indexed by the
    resident side: the same sum over k blocks in the same order as the dq
    kernel's.  With no dq refs the body traces what it did (``ops/eva.py``
    calls it so)."""
    from jax.experimental import pallas as pl

    if seg:
        meets, idq_ref, idk_ref, idcol_scr, refs = _seg_refs(refs, seg)
    if parts:
        (q_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref, di_ref,
         dkv_ref, dkr_ref, *dq, dk_scr, dkr_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
         *dq, dk_scr, dv_scr) = refs
    dq_refs, dq_scrs = dq[:len(dq) // 2], dq[len(dq) // 2:]
    heads = lse_ref.shape[1]
    if parts:
        k_ref, v_ref = _k_and_v(kv_ref, q_ref.shape[-1] // heads)
        dk_ref, dv_ref = _k_and_v(dkv_ref, q_ref.shape[-1] // heads)
    step = sched_ref[pl.program_id(1)]
    qi, ki = _step_qi(step), _step_ki(step)

    def q_tile(i):
        """The rows of q tile ``i`` in the row's dq."""
        return pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

    if dq:
        @pl.when(pl.program_id(1) == 0)
        def _init_row():
            for scr in dq_scrs:
                scr[...] = jnp.zeros(scr.shape, jnp.float32)

    @pl.when(step & _FIRST_BIT != 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)
        if parts:
            dkr_scr[...] = jnp.zeros(dkr_scr.shape, jnp.float32)
        if seg:
            _id_columns(idk_ref, idcol_scr)

    def tile(j):
        k = k_ref[0]                                   # [bk, D]
        v = v_ref[0]
        if causal:
            bias = _causal_mask_bias(
                block_q, block_k, _tile_index(qi, j, tiles), ki, block_q,
                block_k, q_offset, window, transposed=True,     # [bk, bq]
                same=_bcast_lanes(idcol_scr[...], block_q) == idq_ref[0]
                if seg else None)
        dk = dk_scr[...]
        dv = dv_scr[...]
        k_r, dk_r = (kr_ref[0], dkr_scr[...]) if parts else (None, None)
        for h in range(heads):
            q = _head(q_ref, h, heads, parts, j, block_q)      # [bq, D]
            q_r = _tile(qr_ref, j, block_q, h) if parts else None
            do = _head(do_ref, h, heads, rows, j, block_q)
            st = _scores(k, q, k_r, q_r) * scale       # [bk, bq]
            if causal:
                st = st + bias
            # lse, delta: [1, bq]
            pt = jnp.exp(st - _tile(lse_ref, j, block_q, h, along=1))
            dv += jax.lax.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = pt * (dpt - _tile(di_ref, j, block_q, h, along=1)) * scale
            dk += jax.lax.dot(dst.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)
            if parts:
                dk_r += jax.lax.dot(dst.astype(q.dtype), q_r,
                                    preferred_element_type=jnp.float32)
            if dq:
                at = q_tile(_tile_index(qi, j, tiles))
                dq_scrs[0][at, :] += jax.lax.dot(
                    dst.astype(k.dtype).T, k,                  # [bq, bk]
                    preferred_element_type=jnp.float32)
                if parts:       # k_r^T dst, [Dr, bq]: no second turn
                    dq_scrs[1][:, at] += jax.lax.dot_general(
                        k_r, dst.astype(k.dtype), (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
        dk_scr[...] = dk
        dv_scr[...] = dv
        if parts:
            dkr_scr[...] = dk_r

    @pl.when(_runs(step, meets if seg else None))
    def _step():
        _walk(tile, tiles, qi, ki, block_q, block_k, q_offset, causal,
              "kq")

    @pl.when(step & _LAST_BIT != 0)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        if parts:
            dkr_ref[0] = dkr_scr[...].astype(dkr_ref.dtype)
        if dq:      # q tile ki has seen its last k block
            _write_rows(dq_refs[0], dq_scrs[0][q_tile(ki), :], 1, parts)
            if parts:
                _write_rows(dq_refs[1], dq_scrs[1][:, q_tile(ki)].T, 1)


def _delta(out, dout, rows):
    """delta_i = rowsum(dO * O), float32 [B, H, Sq]: one fused
    elementwise+reduce pass in XLA."""
    di = dout.astype(jnp.float32) * out.astype(jnp.float32)
    if not rows:
        return jnp.sum(di, axis=-1)
    B, Sq, H, Dv = di.shape
    if Sq % 8:
        return jnp.swapaxes(jnp.sum(di, axis=-1), 1, 2)
    # Said with the rows in the groups of eight sublanes they lie in:
    # summed straight to [B, Sq, H] the TPU compiler writes the float32
    # product out and re-tiles it by heads before it sums it, three
    # q-sized float32 passes for this one (PERF.md, PR 49).
    return jnp.moveaxis(di.reshape(B, Sq // 8, 8, H, Dv).sum(axis=-1),
                        3, 1).reshape(B, H, Sq)


def _flash_backward(q, k, v, out, lse, dout, causal, scale, block_q, block_k,
                    q_offset, interpret, window=None, rows=False, di=None,
                    doc=None):
    """``di``: the rows' delta where the caller has formed it already (a
    call with a sink, whose LSE is a result with a cotangent of its own).
    ``doc``: a call with documents (``documents`` of its ids)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dims = _dims(q, k, v)
    B, H, Hkv, Sq, Sk, D, Dv, Dr = dims
    operands = _operands(q, k, v)
    dtype = operands["q"].dtype

    if di is None:
        di = _delta(out, dout, rows)
    seg = doc is not None
    ids = doc.astype(jnp.float32).reshape(B, 1, Sq) if seg else None

    def call(kind, kernel, t, major, outs, scratch, tile):
        """One backward kernel at its geometry ``t``, its results the
        operands named in ``outs`` (name -> dtype); LSE / delta enter as
        rows along the lanes, with no broadcast outside.  ``tile``: the
        step's float32 tile, by which ``_compiler_params`` states a limit
        of scoped VMEM ((0, 0): none)."""
        n = B * H // t.heads
        sp = _specs(t, dims, rows)
        sched = _packed_schedule(Sq, Sk, *t.major, q_offset, causal, major,
                                 window)
        out_specs = [getattr(sp, name) for name in outs]
        out_shape = [jax.ShapeDtypeStruct(sp.shapes[name], dtype)
                     for name, dtype in outs.items()]
        if len(outs) == 1:      # one result, not a list of one, as before
            out_specs, out_shape = out_specs[0], out_shape[0]
        with_docs = {}
        if seg:
            # The resident side's ids as columns: q's heads stacked, or k's.
            resident = t.heads * t.block_q if major == "q" else t.block_k
            with_docs = dict(
                kernel={"seg": (len(operands) + 3, H // t.heads)},
                specs=[sp.ids_q, sp.ids_k], handed=[ids, ids],
                prefetch=_meet(doc, block_schedule(
                    Sq, Sk, *t.major, q_offset, causal, major, window),
                    *t.major, KI if major == "q" else QI),
                scratch=[_vmem((resident, LANES), jnp.float32)])
        return pl.pallas_call(
            functools.partial(kernel, causal=causal, scale=scale,
                              block_q=t.block_q, block_k=t.block_k,
                              q_offset=q_offset, window=window, rows=rows,
                              **({"parts": True} if Dr else {}),
                              **({"tiles": t.tiles} if t.tiles > 1 else {}),
                              **with_docs.get("kernel", {})),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1 + 2 * seg,
                grid=(n, sched.size),
                in_specs=[*(getattr(sp, name) for name in operands),
                          sp.o, sp.row, sp.row, *with_docs.get("specs", [])],
                out_specs=out_specs,
                scratch_shapes=[*scratch, *with_docs.get("scratch", [])]),
            out_shape=out_shape,
            interpret=interpret,
            name=_kernel_name(f"flash_{'seg_' if seg else ''}{kind}", window,
                              D, Dv),
            **_compiler_params(interpret, *tile),
        )(sched, *with_docs.get("prefetch", []),
          *(x.reshape(sp.shapes[name]) for name, x in operands.items()),
          dout.reshape(sp.shapes["o"]),
          lse.reshape(n, t.heads, 1, Sq), di.reshape(n, t.heads, 1, Sq),
          *with_docs.get("handed", []))

    # ---- The one pass, where the shapes admit it (``_tiles``) and the call
    # is the causal square: the K-major walk below makes dq too, in a
    # scratch that holds its grid row's, and no dq kernel is called.
    t = (_geometry("bwd", dims, block_q, block_k, window, rows, seg=seg)
         if causal and not q_offset else None)
    kind, dq_outs, dq_scratch = "bwd", {}, []
    if t is not None:
        dq_outs = {"dq": dtype, **({"dq_r": dtype} if Dr else {})}
        dq_scratch = [_vmem(shape, jnp.float32)
                      for shape in ((Sq, D - Dr), (Dr, Sq)) if all(shape)]
        # It states no limit: a head a step, it holds 11.3 MiB at 1,024 x
        # 1,024 and Yi's length and 14.3 at the longest ``_DQ_ROW`` admits,
        # and a limit over the default on this call of three results
        # crashed XLA's memory-space assignment where it compiled Yi's
        # one-row check program (PERF.md, PR 54).
        tile = (0, 0)
    else:
        # ---- dq: Q block resident, K/V blocks stream (the forward's walk).
        t = _geometry("dq", dims, block_q, block_k, window, rows, seg=seg)
        stacked = t.heads * t.block_q
        dq = call(
            "dq", _dq_kernel, t, "q",
            {"q": dtype, **({"q_r": dtype} if Dr else {})},
            [*(_vmem((stacked, d), jnp.float32) for d in (D - Dr, Dr) if d),
             _vmem((stacked, LANES), jnp.float32),
             _vmem((stacked, LANES), jnp.float32)],
            (stacked, t.block_k))
        kind, t = "dkv", _geometry("dkv", dims, block_q, block_k, window,
                                   rows, seg=seg)
        tile = (t.block_q, t.block_k)

    # ---- dk/dv: K/V block resident, Q blocks stream (K-major walk), the
    # step's query heads adding into it.  Where a step holds the whole
    # group the results leave per key head in the inputs' dtype; else per
    # step's heads in float32, summed over the group below.
    shares = H // Hkv // t.heads
    if Dr:
        # A grid row is ``t.heads`` query heads of one key head.  Its share
        # of the one rotary key head's gradient leaves in the inputs' dtype,
        # as a 192-wide dk's lanes did, and the rows are summed here; where
        # a key head's query heads are several grid rows (a group that no
        # step stacks whole), their shares of ``kv``'s gradient leave in
        # float32 side by side and are summed here too.
        dkv, dk_r, *one = call(
            kind, _dkv_kernel, t, "k",
            {"dkv": jnp.float32 if shares > 1 else dtype, "dk_r": dtype,
             **dq_outs},
            [*dq_scratch,
             *(_vmem((t.block_k, d), jnp.float32) for d in (D - Dr, Dr, Dv))],
            tile)
        dq = one or dq
        dk_r = dk_r.reshape(B, H // t.heads, Sk, Dr).sum(
            axis=1, keepdims=True, dtype=jnp.float32).astype(dtype)
        if shares > 1:
            dkv = dkv.reshape(B, Sk, Hkv, shares, D - Dr + Dv).sum(
                axis=3).astype(dtype)
        return ((dq[0].reshape(q[0].shape), dq[1].reshape(q[1].shape)),
                (dkv.reshape(k[0].shape), dk_r), None)
    dk, dv, *one = call(
        kind, _dkv_kernel, t, "k",
        {**{name: jnp.float32 if shares > 1 else k.dtype
            for name in ("dk", "dv")}, **dq_outs},
        [*dq_scratch, *(_vmem((t.block_k, d), jnp.float32) for d in (D, Dv))],
        tile)
    if one:
        dq, = one
    if shares > 1:
        # Over a key head's shares, which lie side by side.
        dk = dk.reshape(B, Hkv, shares, Sk, D).sum(axis=2).astype(k.dtype)
        dv = (dv.reshape(B, Sk, Hkv, shares, Dv).sum(axis=3) if rows
              else dv.reshape(B, Hkv, shares, Sk, Dv).sum(axis=2)
              ).astype(v.dtype)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------- wrapper

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, scale, block_q, block_k, q_offset, interpret,
           window, rows):
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                            q_offset, interpret, need_lse=False,
                            window=window, rows=rows)
    return out


#: What the forward rule calls its two results (``checkpoint_name``), so
#: that a remat policy can keep them past a layer's recomputation and the
#: forward kernel runs once (``models/_lm.flash_keep``).  Under a policy
#: that does not name them the names are the identity.
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, q_offset, interpret,
               window, rows):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              q_offset, interpret, need_lse=True,
                              window=window, rows=rows)
    out, lse = checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, q_offset, interpret, window,
               rows, res, dout):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, dout, causal, scale, block_q,
                           block_k, q_offset, interpret, window, rows)


_flash.defvjp(_flash_fwd, _flash_bwd)


# A call with documents is a function of its own too: causal, the square,
# no window and no sink, the ids an operand with no gradient.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_seg(q, k, v, segment_ids, scale, block_q, block_k, interpret,
               rows):
    return _flash_forward(q, k, v, True, scale, block_q, block_k, 0,
                          interpret, need_lse=False, rows=rows,
                          doc=_documents(segment_ids))[0]


def _flash_seg_fwd(q, k, v, segment_ids, scale, block_q, block_k, interpret,
                   rows):
    out, lse = _flash_forward(q, k, v, True, scale, block_q, block_k, 0,
                              interpret, need_lse=True, rows=rows,
                              doc=_documents(segment_ids))
    out, lse = checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, segment_ids, out, lse)


def _flash_seg_bwd(scale, block_q, block_k, interpret, rows, res, dout):
    q, k, v, segment_ids, out, lse = res
    return (*_flash_backward(q, k, v, out, lse, dout, True, scale, block_q,
                             block_k, 0, interpret, None, rows,
                             doc=_documents(segment_ids)), None)


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


# A call with a sink is a function of its own, so that a call without one
# traces what it traced: (result, LSE), the LSE a result like the other
# because the sink's share of a row's mass is exp(b_h - lse) and a model
# reports it.
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_sink(q, k, v, sink, causal, scale, block_q, block_k, q_offset,
                interpret, window, rows):
    return _flash_forward(q, k, v, causal, scale, block_q, block_k, q_offset,
                          interpret, need_lse=True, window=window, rows=rows,
                          sink=sink)


def _flash_sink_fwd(q, k, v, sink, causal, scale, block_q, block_k, q_offset,
                    interpret, window, rows):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              q_offset, interpret, need_lse=True,
                              window=window, rows=rows, sink=sink)
    out, lse = checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)
    return (out, lse), (q, k, v, sink, out, lse)


def _flash_sink_bwd(causal, scale, block_q, block_k, q_offset, interpret,
                    window, rows, res, cotangents):
    """The saved LSE holds the sink's column, so the backward kernels'
    ``p = exp(s - lse)`` and ``ds = p (dp - delta)`` are a softmax's over
    the keys AND the sink as they stand.  The LSE's own cotangent reaches
    the scores as ``p dlse`` and the sink as ``p_sink dlse``: it is taken
    off delta once.  The sink's column has value zero, so its ``dp`` is 0
    and ``db_h = - sum_t exp(b_h - lse_t) delta_t``, from what is saved."""
    q, k, v, sink, out, lse = res
    dout, dlse = cotangents
    di = _delta(out, dout, rows) - dlse
    dq, dk, dv = _flash_backward(q, k, v, out, lse, dout, causal, scale,
                                 block_q, block_k, q_offset, interpret,
                                 window, rows, di=di)
    with jax.named_scope("attn/sink_grad"):
        db = -jnp.sum(jnp.exp(sink.astype(jnp.float32)[None, :, None] - lse)
                      * di, axis=(0, 2))
    return dq, dk, dv, db.astype(sink.dtype)


_flash_sink.defvjp(_flash_sink_fwd, _flash_sink_bwd)


def _head_major(fn, q, k, v, rows):
    """``fn``, which takes and returns [B, H, S, D], for a call whose v and
    result lie as [B, S, H, D] under ``rows``."""
    if not rows:
        return fn(q, k, v)
    got = fn(q, k, jnp.swapaxes(v, 1, 2))
    if isinstance(got, tuple):      # (result, LSE [B, H, S]: nothing to turn)
        return (jnp.swapaxes(got[0], 1, 2), *got[1:])
    return jnp.swapaxes(got, 1, 2)


def _one_part(q, k, v=None):
    """A call in parts said in one, for the paths the kernels' parts do not
    serve: (q [B, H, Sq, Dn + Dr], k alike, v [B, Sk, H, Dv]), q and k
    head-major with the one rotary key head laid under every head."""
    _dims(q, k, v)      # the shapes a call in parts takes, or a ValueError
    (q_n, q_r), (kv, k_r) = q, k
    Dn = q_n.shape[-1]
    k_n = jnp.swapaxes(kv[..., :Dn], 1, 2)
    return (jnp.concatenate([jnp.swapaxes(q_n, 1, 2), q_r], axis=-1),
            jnp.concatenate([k_n, jnp.broadcast_to(
                k_r, k_n.shape[:3] + k_r.shape[3:])], axis=-1),
            kv[..., Dn:])


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, q_offset: int = 0,
                    interpret: bool = False, window: Optional[int] = None,
                    rows: bool = False, sink=None, lse: bool = False,
                    segment_ids=None):
    """Pallas flash attention (fwd + bwd kernels) with custom VJP.

    q: [B, H, Sq, D]; k: [B, Hkv, Sk, D], head-major as
    ``ops.rope.rotate_heads`` places them.  v: [B, Hkv, Sk, Dv], with
    ``Dv`` free to differ from ``D``, and the result [B, H, Sq, Dv]; or,
    with ``rows``, v [B, Sk, Hkv, Dv] and the result [B, Sq, H, Dv], as a
    projection leaves and takes them.  A gradient has its operand's layout,
    the result's cotangent the result's.  The kernels read and write rows
    in place (module docstring) where both head sizes are multiples of 128;
    for any other v is turned head-major here and the result back, so a
    call may always say what it holds.  The kernels take a rows operand as
    [B, S, heads * Dv]: hand over a projection's result as the same program
    reshapes it (a bitcast), not an array that entered the jit as
    [B, S, heads, Dv], which lies tiled by (heads, Dv) and is copied on its
    way (PERF.md, PR 49).

    **In parts** (latent attention): ``q = (q_n [B, Sq, H, Dn], q_r [B, H,
    Sq, Dr])``, ``k = (kv [B, Sk, H, Dn + Dv], k_r [B, 1, Sk, Dr])`` and
    ``v = None``, as the projections and the rotary kernel write them: a
    head's key without position and its value side by side in ``kv``, the
    one rotary key head every query head's.  The result is [B, Sq, H, Dv]
    (``rows``, which a call in parts implies).  The gradients come back in
    the same parts, k_r's summed over the heads.  The default scale is
    ``(Dn + Dr) ** -0.5``.  ``kv`` may hold ``Hkv`` key heads with ``H %
    Hkv == 0``: query head h reads key head ``h // (H // Hkv)``, and dk/dv
    come back a key head.  The kernels take the parts as they are where
    ``Dn`` and ``Dv`` are multiples of 128; any other such call is put
    together here (``_one_part``) and goes the way of a 192-wide one.

    ``window``: with ``causal``, a key is visible iff ``0 <= t - s <
    window``.  ``block_q`` / ``block_k`` default to what ``_tiles`` picks
    for each kernel from the shapes.

    ``sink`` [H] float32 (a learned attention sink): every row of query
    head ``h`` has one more column, of score ``sink[h]`` (in the softmax's
    own units: no scale) and value zero, ``p_ts = exp(s_ts) / (exp(b_h) +
    sum_s' exp(s_ts'))``: it takes mass and adds nothing.  The forward
    kernel starts the row's running max at ``b_h`` and its sum at 1 (where
    no sink starts them at ``-inf`` and 0) and is named for it
    (``flash_fwd_.._sink``); no second softmax pass, no column in HBM.  The
    saved log-sum-exp holds the sink, so the backward kernels run as they
    are, and ``db_h = - sum_t exp(b_h - lse_t) delta_t`` is formed beside
    them from what they read (``_flash_sink_bwd``).  With ``lse`` the call
    returns (result, the rows' log-sum-exp [B, H, Sq] float32), itself
    differentiable: ``exp(b_h - lse)`` is the mass the sink took.  With
    ``sink=None`` a call traces what it traced.

    ``segment_ids`` [B, S] integers (a packed row; the causal square with no
    window, sink or parts): a query sees the keys of its own document only,
    a document a run of equal ids; blocks whose documents cannot meet are
    not computed (the module's note on documents), and the kernels are
    named ``flash_seg_*``.  With None a call traces what it traced."""
    if segment_ids is not None:
        if (not causal or q_offset or window is not None or sink is not None
                or _in_parts(q) or q.shape[2] != k.shape[2]
                or segment_ids.shape != (q.shape[0], q.shape[2])):
            raise NotImplementedError(
                "segment_ids [B, S] go with the causal square, one part, no "
                "window, sink or offset (ROADMAP M6)")
    if sink is None and lse:
        raise ValueError("the log-sum-exp is handed out for a call with a "
                         "sink only")
    if _in_parts(q):
        rows = True
        if q[0].shape[-1] % LANES or k[0].shape[-1] % LANES:
            q, k, v = _one_part(q, k, v)
    scale = scale if scale is not None else 1.0 / math.sqrt(
        sum(x.shape[-1] for x in q) if _in_parts(q) else q.shape[-1])
    if rows and not _in_parts(q) and (q.shape[-1] % LANES
                                      or v.shape[-1] % LANES):
        return _head_major(
            functools.partial(flash_attention, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              q_offset=q_offset, interpret=interpret,
                              window=window, segment_ids=segment_ids,
                              **({} if sink is None
                                 else {"sink": sink, "lse": lse})),
            q, k, v, rows)
    if segment_ids is not None:
        return _flash_seg(q, k, v, segment_ids, scale, block_q, block_k,
                          interpret, bool(rows))
    if sink is None:
        return _flash(q, k, v, causal, scale, block_q, block_k, q_offset,
                      interpret, window, bool(rows))
    heads = _dims(q, k, v).H
    if sink.shape != (heads,):
        raise ValueError(f"a sink a query head: {sink.shape} for {heads}")
    got = _flash_sink(q, k, v, sink, causal, scale, block_q, block_k,
                      q_offset, interpret, window, bool(rows))
    return got if lse else got[0]


def _on_tpu() -> bool:
    """The platform JAX reports.  A backend that fails to initialize
    raises here: a broken chip must not read as "not on TPU"."""
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
              impl: Optional[str] = None, mesh=None,
              window: Optional[int] = None, rows: bool = False,
              sink=None, lse: bool = False, segment_ids=None):
    """Dispatching entry point: pallas flash on TPU, reference elsewhere.
    ``sink`` and ``lse`` are ``flash_attention``'s (one device only), and
    so is ``segment_ids`` (a packed row's documents).
    ``rows`` is ``flash_attention``'s: v and the result lie as
    [B, S, H, D]; so are q and k in parts, which only the kernels on one
    device take as they are (the reference and a mesh's island get the
    call put together, ``_one_part``).

    ``mesh`` is the SPMD mesh q/k/v are laid out on inside a GSPMD
    program.  A Mosaic kernel cannot be partitioned automatically, so on
    a mesh of more than one device the flash kernel runs as a shard_map
    island: batch over (dp, fsdp), heads over tp, the sequence whole."""
    if impl is None:
        impl = "flash" if _on_tpu() else "reference"
    island = mesh is not None and mesh.size > 1
    if _in_parts(q):
        rows = True
        if impl == "reference" or island:
            q, k, v = _one_part(q, k, v)
    with_sink = {} if sink is None else {"sink": sink, "lse": lse}
    if segment_ids is not None:
        if island:
            raise NotImplementedError(
                "segment_ids on a mesh: the island's specs know no [B, S] "
                "operand (ROADMAP M6)")
        with_sink = {**with_sink, "segment_ids": segment_ids}
    if impl == "reference":
        return _head_major(
            functools.partial(reference_attention, causal=causal,
                              scale=scale, window=window, **with_sink),
            q, k, v, rows)
    fn = functools.partial(flash_attention, causal=causal, scale=scale,
                           interpret=impl == "flash_interpret",
                           window=window, **with_sink)
    if island:
        if sink is not None:
            raise NotImplementedError(
                "a sink on a mesh: the island's specs know no [H] operand "
                "(ROADMAP M16)")
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import (AXIS_DATA, AXIS_FSDP, AXIS_SEQ,
                                     AXIS_TENSOR)
        if mesh.shape.get(AXIS_SEQ, 1) > 1:
            raise ValueError(
                "flash attention needs the whole sequence on each device; "
                "use attention_impl='ring' or 'ulysses' on an sp mesh")
        spec = P((AXIS_DATA, AXIS_FSDP), AXIS_TENSOR, None, None)
        # The island takes head-major arrays; no caller says ``rows`` on a
        # mesh (models/llama._values_as_rows), one that did is turned here.
        return _head_major(
            jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec, check_vma=False), q, k, v, rows)
    return fn(q, k, v, rows=rows)
