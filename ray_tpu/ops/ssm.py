"""State-space layers (Mamba-2, the SSD form of arXiv:2405.21060): a causal
depthwise convolution, the chunked scan of a recurrence that carries a state
from chunk to chunk, and the gated RMSNorm over groups of channels.  What
``models/nemotron_h.py``'s mixer runs, and ``models/granite_hybrid.py``'s
through it.  Beside the mixer's convolution
(``causal_conv``: a bias and a silu) lives LFM2's, which shares its taps
(``gated_short_conv``: two gates, three operands, no bias and no silu; what
``models/lfm2.py``'s ``conv`` layers run).

The recurrence, a head ``h`` of ``P`` channels on a state of ``P x N`` (in
float32, the state zero at the start of every row, and of every DOCUMENT of a
packed row where a call says ``segment_ids``: below)::

    S_t = exp(dt_t A) S_{t-1} + dt_t X_t (x) B_t
    y_t = S_t C_t + D X_t

with ``B`` and ``C`` shared by the heads of a group.  ``ssd_scan`` computes it
in chunks of ``chunk`` tokens, equal to the recurrence: with ``l_t`` the
running sum of ``dt A`` inside a chunk,

- inside a chunk, ``y_t = sum_{s <= t} exp(l_t - l_s) (C_t . B_s) dt_s X_s``:
  one [Q, Q] product a group for ``C . B``, a decay mask a head, one
  [Q, Q] x [Q, P] product a head;
- a chunk's own state ``sum_s exp(l_Q - l_s) dt_s X_s (x) B_s`` (a
  [heads of a group * P, Q] x [Q, N] product a group);
- the states handed on, ``S_c = exp(l_Q) S_{c-1} + own_c``: a ``lax.scan``
  over the chunks, elementwise on [B, H, P, N] float32;
- ``exp(l_t) C_t . S_{c-1}`` added to a chunk's own result.

Decays, running sums, ``dt`` and the states are float32; the products take
bfloat16 operands (the inputs as they come, the masked ``C . B`` and the
states rounded once) and add up in float32, as upstream's kernels do.  A row
whose length the chunk does not divide is padded with tokens that neither
decay nor add (``dt`` 0), which nothing before them sees.

Two forms compute it.  On a TPU, where a group's channels, the state and the
chunk each fill whole tiles of 128 lanes (``_kernels``; Nemotron-3-Nano's 8
heads of 64 a group, state 128, chunk 128), a pair of Pallas kernels
(``ssd_fwd_q128`` / ``ssd_bwd_q128``, one ``jax.custom_vjp``): a grid step
is one chunk of one group, the chunks of a row in order with the group's
state [N, heads P] float32 in VMEM scratch, so the decayed masks, the
masked ``C . B`` and every float32 partial result never reach HBM; the
backward keeps the state each chunk started from (written by the forward
its rule runs) and walks the chunks last to first.  XLA makes what is small:
``l`` by a product with a triangle of ones, and ``l`` and ``dt`` a head as
columns and as rows (64 numbers a token).  Elsewhere ``_scan_xla``: the same
four products in ``jnp`` with a ``lax.scan`` over the chunk states and the
backward JAX derives, the definition the kernels are held to and the CPU's
path; on the chip its masks and partial results cross HBM at HBM pace: 2.4
ms a row of 8,192 forward and 6.1 forward and backward, against the
kernels' 1.0 and 2.7 (PERF.md, PR 43).

The convolutions and the gated norm are elementwise passes over 6,144 (or
Granite's 4,352), 2,048 (LFM2's) and 4,096 channels a token; each has its
backward written out (``jax.custom_vjp``), because what JAX derives moves
several times the bytes: by XLA's own count for a described v5e, a row of
8,192 forward and backward, the convolution 4.23 -> 0.81 GB and the norm
3.06 -> 1.12 GB (PERF.md, PR 43).  The norm is still ``jnp``.  Both
convolutions are a Pallas pair on a TPU where the shapes tile (LFM2's since
PR 47, the mixer's since PR 66) and ``jnp`` elsewhere, which is their
definition.  In ``jnp`` the mixer's taps are slices of packed rows a token
off the tile, a packed row's documents three ``where`` a tap pass, and the
backward's dw and db five column sums that XLA runs as a pass of their own:
on the chip, one row of 32,768 tokens by 4,352 channels with 24 documents'
ids, 2.21 ms forward and 8.01 backward (32 % and 13 % of HBM pace by the
array read and written once, and read twice and written once; the sums
alone 4.46).  The pair (``ssm_conv_fwd`` / ``ssm_conv_bwd``, ``causal_conv``)
reads 1.07 and 2.04 there (63 % and 51 %), 1.05 and 1.92 without ids, and
0.37 and 0.72 for 0.72 and 2.69 at Nemotron's [1, 8192, 6144]: its forward
equal to ``jnp``'s to the last bit, and no copy between the projection, the
pair and the scan (``jnp``'s result was cut out of the projection's and cut
into X, B and C by copies of their own, 31 ms of Granite's step).  What is
left is the vector units' work, not HBM's: without its silu the forward
reads 12 % less (PERF.md, PR 66).

**A row is one document, or several end to end** (``segment_ids`` [B, S],
a run of equal ids a document; ``documents``).  The convolution's taps read
zero on another document's token, forward and in the written-out backward
(``causal_conv``).  The scan takes ``_DROP`` off the decay's exponent of a
document's first token: ``exp`` of it is zero in float32, so the token
decays the state before it to nothing, which is the recurrence with ``S_{t-1}
= 0`` there, and in the chunked forms the one term does everything a boundary
asks for, in ``_scan_xla`` and in the kernels as they are, forward and
backward (``ssd_scan``).  ``chunk_carry`` then averages over the chunks that
hold no boundary and counts the others.  Without the argument every call
traces the program it traced.

**A group wider than a grid step should hold** (Granite-4.0-H's 64 heads of
64 on ONE B and C: 4,096 channels) goes through the kernels in slices of its
heads, grid rows of their own that read the same B and C block (``_slices``,
``_laid_out``); dB and dC leave a slice's share in float32 and are summed
outside.  Nemotron's eight heads a group are one slice: the program they
were.  On the chip at [1, 32768, 64, 64] the pair reads 2.00 + 5.66 ms a
call at a chunk of 256 (2.65 + 4.80 at 128), with ids and without alike
(PERF.md, PR 65).

Which path a scan took is counted in ``ray_tpu_ssm_path_total`` (``kernel``
or ``xla``; ``segments`` yes / no; the channels of a state group).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..util import telemetry

F32 = jnp.float32


def documents(segment_ids):
    """A row's documents counted from 0, int32 [B, S]: a document is a run
    of equal ids, so a token whose id is not its predecessor's starts one.
    What the ops here, and ``ops.attention``, make of a batch's
    ``segment_ids``: ids that never fall along a row."""
    return jnp.cumsum(_starts(segment_ids), axis=1, dtype=jnp.int32)


def _same(doc, d: int):
    """bool [B, S, 1]: token t - d lies in the row and in token t's document
    (``d`` negative: a token after t)."""
    S = doc.shape[1]
    shifted = jnp.pad(doc, ((0, 0), (max(d, 0), max(-d, 0))),
                      constant_values=-1)
    return (shifted[:, max(-d, 0):max(-d, 0) + S] == doc)[..., None]


def _conv_taps(c, w, b=None, doc=None):
    """The convolution before its silu, float32 [B, S, Ch], and the padded
    input its taps read (in c's dtype: a float32 copy of it would be written
    and read four times).  ``b`` None: no bias.  ``c`` a tuple of arrays:
    the taps of their product, each padded apart in its own dtype and
    multiplied in float32 tap by tap (``gated_short_conv``: the TPU compiler
    then reads each operand where it lies, and no product is ever written:
    a row of 8,192 at 2,048 channels forward, 134 MB for 403 with the
    product padded, by its own count for a described v5e; PERF.md, PR 47).
    ``doc`` (``documents``): a tap on another document's token reads zero."""
    several = isinstance(c, tuple)
    cs = c if several else (c,)
    K, S = w.shape[0], cs[0].shape[1]
    padded = tuple(jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))) for x in cs)
    acc = 0.0 if b is None else b.astype(F32)
    for j in range(K):
        x = padded[0][:, j:j + S].astype(F32)
        for p in padded[1:]:
            x = x * p[:, j:j + S].astype(F32)
        if doc is not None and j < K - 1:       # (tap K - 1 reads t itself)
            x = jnp.where(_same(doc, K - 1 - j), x, 0.0)
        acc = acc + x * w[j].astype(F32)
    return acc, (padded if several else padded[0])


def _ahead(ga, w, doc=None):
    """A convolution's taps read the other way: token t's cotangent ``ga``
    (float32 [B, S, Ch], or a tuple of arrays whose product it is, as
    ``_conv_taps`` takes them) reaches the inputs of tokens t - (K - 1) ..
    t, those of its document (``doc``)."""
    gs = ga if isinstance(ga, tuple) else (ga,)
    K, S = w.shape[0], gs[0].shape[1]
    ahead = tuple(jnp.pad(x, ((0, 0), (0, K - 1), (0, 0))) for x in gs)
    out = 0
    for j in range(K):
        x = ahead[0][:, K - 1 - j:K - 1 - j + S]
        for p in ahead[1:]:
            x = x.astype(F32) * p[:, K - 1 - j:K - 1 - j + S].astype(F32)
        if doc is not None and j < K - 1:
            x = jnp.where(_same(doc, j - (K - 1)), x, 0.0)
        out = out + x * w[j].astype(F32)
    return out


def _conv_xla(c, w, b, segment_ids):
    doc = None if segment_ids is None else documents(segment_ids)
    return jax.nn.silu(_conv_taps(c, w, b, doc)[0]).astype(c.dtype)


def _conv_xla_bwd(c, w, b, segment_ids, g):
    """(dc, dw float32 [K, Ch], db float32 [Ch]), written out: the taps read
    the other way, the weight's gradient K reductions over the same shifted
    reads (what JAX derives moves 2.6 times the bytes by XLA's own count for
    a described v5e)."""
    K, S = w.shape[0], c.shape[1]
    doc = None if segment_ids is None else documents(segment_ids)
    acc, padded = _conv_taps(c, w, b, doc)
    sig = jax.nn.sigmoid(acc)
    ga = g.astype(F32) * sig * (1.0 + acc * (1.0 - sig))
    dc = _ahead(ga, w, doc)
    read = lambda j: padded[:, j:j + S].astype(F32) \
        if doc is None or j == K - 1 else jnp.where(
            _same(doc, K - 1 - j), padded[:, j:j + S].astype(F32), 0.0)
    dw = jnp.stack([jnp.sum(read(j) * ga, axis=(0, 1)) for j in range(K)])
    return dc.astype(c.dtype), dw, jnp.sum(ga, axis=(0, 1))


# LFM2's double-gated short convolution.  In ``jnp`` (``_gconv_xla``) the
# TPU compiler reads every operand where it lies and writes no product, but
# its taps are slices of packed bfloat16 rows a token or two off the tile:
# on the chip a call at [4, 8192, 2048] reads 29 % of HBM pace forward and
# 20 % backward, 41 % of its roofline inside the step (PERF.md, PR 47).  The
# Pallas pair walks a row's tokens in order, a tile of ``_GC_ROWS`` tokens by
# ``_GC_LANES`` channels a grid step: the product ``B * u`` is formed once in
# float32, a tap is a sublane rotation of it (``pltpu.roll``), and the
# ``_GC_HEAD`` rows a rotation wraps round are put right from the last rows
# of the tile before, which the walk carries in VMEM scratch (zeros at a
# row's start); the backward reads the first rows of the tile AFTER from HBM
# (a block of ``_GC_HEAD`` rows), for the taps read the other way, and adds
# the taps' gradient up over every tile of a column of channels.
_GC_ROWS, _GC_LANES, _GC_HEAD = 512, 512, 16


def _gc_tile(S: int, Ch: int, backward: bool):
    """(tokens, channels) of a grid step, or None where the shapes do not
    tile: the backward's tile is half as long (twice the live arrays)."""
    rows = _GC_ROWS // 2 if backward else _GC_ROWS
    lanes = next((n for n in (_GC_LANES, 256, 128) if Ch % n == 0), None)
    return (rows, lanes) if lanes and S % rows == 0 else None


def _gc_shifted(x, d: int, before):
    """x [rows, lanes] float32 a tile's values: its values ``d`` tokens
    earlier, the first ``d`` rows from ``before``, the ``_GC_HEAD`` rows that
    precede the tile.  A rotation, and its first ``_GC_HEAD`` rows again
    with the wrapped ones replaced: (whole tile with wrapped rows, the
    first rows put right)."""
    from jax.experimental.pallas import tpu as pltpu
    head = x[:_GC_HEAD]
    first = jax.lax.broadcasted_iota(jnp.int32, head.shape, 0) < d
    return pltpu.roll(x, d, 0), jnp.where(
        first, pltpu.roll(before, d, 0), pltpu.roll(head, d, 0))


def _gc_fwd_kernel(B_ref, C_ref, u_ref, w_ref, o_ref, tail):
    from jax.experimental import pallas as pl
    K = w_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _row_start():
        tail[...] = jnp.zeros_like(tail)

    w = w_ref[...].astype(F32)
    bu = B_ref[...].astype(F32) * u_ref[...].astype(F32)
    acc, head = bu * w[K - 1], bu[:_GC_HEAD] * w[K - 1]
    for d in range(1, K):
        whole, first = _gc_shifted(bu, d, tail[...])
        acc, head = acc + whole * w[K - 1 - d], head + first * w[K - 1 - d]
    o_ref[...] = (C_ref[...].astype(F32) * acc).astype(o_ref.dtype)
    o_ref[:_GC_HEAD] = (C_ref[:_GC_HEAD].astype(F32) * head
                        ).astype(o_ref.dtype)
    tail[...] = bu[-_GC_HEAD:]


def _gc_bwd_kernel(B_ref, C_ref, u_ref, g_ref, gn_ref, Cn_ref, w_ref,
                   dB_ref, dC_ref, du_ref, dw_ref, tail):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    K, rows = w_ref.shape[0], B_ref.shape[0]
    r, s, last = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(s == 0)
    def _row_start():
        tail[...] = jnp.zeros_like(tail)

    @pl.when((r == 0) & (s == 0))
    def _first_tile():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = w_ref[...].astype(F32)
    Bf, uf, Cf = (x[...].astype(F32) for x in (B_ref, u_ref, C_ref))
    bu, g = Bf * uf, g_ref[...].astype(F32)
    ga = g * Cf                                 # the taps' cotangent
    # What follows the tile: the next tile's first rows, nothing after the
    # row's last token.
    after = jnp.where(s == last, 0.0, gn_ref[...].astype(F32)
                      * Cn_ref[...].astype(F32))
    end = ga[-_GC_HEAD:]
    late = jax.lax.broadcasted_iota(jnp.int32, end.shape, 0)
    taps, taps_head = bu * w[K - 1], bu[:_GC_HEAD] * w[K - 1]
    dbu, dbu_end = ga * w[K - 1], end * w[K - 1]
    dw = [None] * K
    dw[K - 1] = jnp.sum(bu * ga, axis=0, keepdims=True)
    # (the taps unroll while the kernel is traced: no dispatch a turn)
    for d in range(1, K):  # ray-tpu: noqa[RT506]
        whole, first = _gc_shifted(bu, d, tail[...])
        taps = taps + whole * w[K - 1 - d]
        taps_head = taps_head + first * w[K - 1 - d]
        dw[K - 1 - d] = jnp.sum(whole * ga, axis=0, keepdims=True) + jnp.sum(
            (first - whole[:_GC_HEAD]) * ga[:_GC_HEAD], axis=0, keepdims=True)
        # the taps read the other way: token t's input reaches t + d
        dbu = dbu + pltpu.roll(ga, rows - d, 0) * w[K - 1 - d]
        dbu_end = dbu_end + jnp.where(
            late >= _GC_HEAD - d, pltpu.roll(after, _GC_HEAD - d, 0),
            pltpu.roll(end, _GC_HEAD - d, 0)) * w[K - 1 - d]
    dC_ref[...] = (g * taps).astype(dC_ref.dtype)
    dC_ref[:_GC_HEAD] = (g[:_GC_HEAD] * taps_head).astype(dC_ref.dtype)
    dB_ref[...] = (dbu * uf).astype(dB_ref.dtype)
    dB_ref[-_GC_HEAD:] = (dbu_end * uf[-_GC_HEAD:]).astype(dB_ref.dtype)
    du_ref[...] = (dbu * Bf).astype(du_ref.dtype)
    du_ref[-_GC_HEAD:] = (dbu_end * Bf[-_GC_HEAD:]).astype(du_ref.dtype)
    dw_ref[...] += jnp.concatenate(dw, axis=0)
    tail[...] = bu[-_GC_HEAD:]


def _gc_call(backward: bool, operands, w, interpret: bool):
    """One kernel call over [rows, S, Ch] operands: the forward's result, or
    (dB, dC, du, dw float32 [K, Ch])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, S, Ch = operands[0].shape
    K = w.shape[0]
    rows, lanes = _gc_tile(S, Ch, backward)
    # Channels outermost: the taps' gradient of a column of channels stays
    # in VMEM while every tile of the column adds to it.
    grid = (Ch // lanes, R, S // rows)
    tile = pl.BlockSpec((None, rows, lanes), lambda c, r, s: (r, s, c))
    taps = pl.BlockSpec((K, lanes), lambda c, r, s: (0, c))
    like = jax.ShapeDtypeStruct((R, S, Ch), operands[0].dtype)
    if backward:
        step = rows // _GC_HEAD
        after = pl.BlockSpec(
            (None, _GC_HEAD, lanes), lambda c, r, s: (
                r, jnp.minimum((s + 1) * step, S // _GC_HEAD - 1), c))
        g, C = operands[3], operands[1]
        operands = tuple(operands) + (g, C)
        in_specs = [tile] * 4 + [after, after, taps]
        out_specs, out_shape = [tile] * 3 + [taps], [like] * 3 + [
            jax.ShapeDtypeStruct((K, Ch), F32)]
    else:
        in_specs, out_specs, out_shape = [tile] * 3 + [taps], tile, like
    return pl.pallas_call(
        _gc_bwd_kernel if backward else _gc_fwd_kernel, grid=grid,
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_GC_HEAD, lanes), F32)],
        interpret=interpret,
        name="gated_conv_bwd" if backward else "gated_conv_fwd",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"))}),
    )(*operands, w)


def _gconv_xla(B, C, u, w):
    return (C.astype(F32) * _conv_taps((B, u), w)[0]).astype(u.dtype)


def _gconv_xla_bwd(B, C, u, w, g):
    K, S = w.shape[0], u.shape[1]
    taps, (pB, pu) = _conv_taps((B, u), w)
    dbu = _ahead((g, C), w)                     # the taps' cotangent is g C
    ga = g.astype(F32) * C.astype(F32)
    dw = jnp.stack([jnp.sum(pB[:, j:j + S].astype(F32)
                            * pu[:, j:j + S].astype(F32) * ga, axis=(0, 1))
                    for j in range(K)])
    return ((dbu * u.astype(F32)).astype(B.dtype),
            (g.astype(F32) * taps).astype(C.dtype),
            (dbu * B.astype(F32)).astype(u.dtype), dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gconv(B, C, u, w, impl):
    with jax.named_scope("block/conv/gate"):
        if impl == "xla":
            return _gconv_xla(B, C, u, w)
        return _gc_call(False, (B, C, u), w, impl == "kernel_interpret")


def _gconv_fwd(B, C, u, w, impl):
    return _gconv(B, C, u, w, impl), (B, C, u, w)


def _gconv_bwd(impl, res, g):
    B, C, u, w = res
    with jax.named_scope("block/conv/gate"):
        if impl == "xla":
            grads = _gconv_xla_bwd(B, C, u, w, g)
        else:
            grads = _gc_call(True, (B, C, u, g), w,
                             impl == "kernel_interpret")
        return grads[:3] + (grads[3].astype(w.dtype),)


_gconv.defvjp(_gconv_fwd, _gconv_bwd)


def gated_short_conv(B, C, u, w, *, impl=None):
    """LFM2's double-gated short convolution, ``C * sum_j w[j] *
    (B * u)[t - (K - 1) + j]`` with ``(B * u)[s] = 0`` before the row's
    start: B, C, u [rows, S, Ch] in the stream's dtype, w [K, Ch] (tap
    K - 1 reads the token itself) -> [rows, S, Ch] in u's dtype.  No bias,
    no activation.  Depthwise: a channel reads its own past and nothing
    else; a row reads nothing of another row.  The products and the sum are
    float32, rounded once.

    The backward is written out as ``causal_conv``'s: dC from the recomputed
    taps, the taps' cotangent read the other way for ``d(B * u)`` and from
    it dB and du, dw as K reductions.  ``impl``: None (the Pallas pair on a
    TPU where the shapes tile, ``jnp`` elsewhere), ``"xla"``, ``"kernel"``,
    ``"kernel_interpret"`` (the tests).  Which form a traced call took is
    counted in ``ray_tpu_gated_conv_path_total``."""
    from .attention import _on_tpu          # at the call: tests steer it
    _, S, Ch = u.shape
    if impl is None:
        from ..parallel.mesh import get_global_mesh
        mesh = get_global_mesh()
        # (where the forward's tile divides S, the backward's half does)
        impl = "kernel" if (_on_tpu() and _gc_tile(S, Ch, False)
                            and w.shape[0] <= _GC_HEAD
                            and not (mesh is not None and mesh.size > 1)) \
            else "xla"
    telemetry.inc("ray_tpu_gated_conv_path_total", tags={
        "path": "xla" if impl == "xla" else "kernel",
        "taps": str(w.shape[0])})
    return _gconv(B, C, u, w, impl)


# The mixer's convolution (``causal_conv``) as a Pallas pair, ``_gc_*``'s form
# with a bias, a silu and documents.  A grid step is a tile of tokens by a
# column of channels, the columns of a tile innermost, so that what a tile's
# tokens say of their documents is fetched once a tile.  A tile is worked
# through in chunks of ``_CC_CHUNK`` numbers (a loop: what a chunk makes
# stays in registers, where a whole tile's arrays each went to VMEM and
# back); a tap is a sublane rotation of the chunk behind the 8 rows before
# it, which ride the loop and, from tile to tile, VMEM scratch a column
# (zeros at a row's start).  The backward walks a row's tiles, and a tile's
# chunks, LAST TO FIRST as ``ops/kda._in_bwd_kernel`` does: the taps read the
# other way take the first rows of the chunk after's cotangent from the loop
# (or the scratch), the rows before a tile come from HBM (a block of
# ``_GC_HEAD`` rows), and dw and db of every column stay in VMEM (one block,
# the whole array) until the last step.  With ids ONE int32 a token
# (``_since_start``) carries every tap's mask, and a flag a chunk rides in by
# scalar prefetch: a chunk in which no document starts runs without a
# select, as a call without ids does everywhere.

def _since_start(segment_ids, K: int):
    """int32 [B, S]: how many tokens of its document, and of its row, lie
    before a token, clipped at K - 1.  A tap ``d`` tokens back reads its
    token iff this is at least ``d``: documents are runs, so the one number
    a token carries every tap's mask, forward and backward."""
    S = segment_ids.shape[1]
    start = _starts(segment_ids).at[:, 0].set(True)
    pos = jnp.full(segment_ids.shape, K - 1, jnp.int32)
    for d in range(K - 2, -1, -1):      # (the nearest start wins)
        pos = jnp.where(jnp.pad(start, ((0, 0), (d, 0)))[:, :S], d, pos)
    return pos


def _cc_taps(prev, x, w, bias, keep):
    """The convolution's sum before its silu on a chunk: x [n, d] float32
    behind ``prev``, the 8 rows before it; w [K, d] float32 (tap K - 1 reads
    the token itself), ``bias`` [1, d] -> (the sum, added up bias first and
    then oldest tap first as ``_conv_taps`` does, and the chunk seen K - 1
    .. 1 tokens back, oldest first).  ``keep`` (``_cc_keeps``): a tap on
    another document's token reads zero, in the sum and in the list."""
    from jax.experimental.pallas import tpu as pltpu
    K = w.shape[0]
    ext = jnp.concatenate([prev, x], axis=0)
    back = [pltpu.roll(ext, K - 1 - j, 0)[8:] for j in range(K - 1)]
    if keep is not None:
        back = [jnp.where(m, sh, 0.0) for m, sh in zip(keep, back)]
    acc = bias
    for j, sh in enumerate(back):  # ray-tpu: noqa[RT506]
        acc = acc + sh * w[j]
    return acc + x * w[K - 1], back


def _cc_keeps(pos_ref, r0, n: int, lanes: int, K: int):
    """The masks [n, lanes] of a chunk's taps, oldest first: ``_since_start``
    of its tokens (along 128 lanes in ``pos_ref``) at least the tap's
    distance."""
    from jax.experimental import pallas as pl
    from .attention import LANES
    pos = pos_ref[pl.ds(r0, n), :]
    pos = jnp.concatenate([pos] * (lanes // LANES), axis=1)
    return [pos >= K - 1 - j for j in range(K - 1)]


def _cc_fwd_kernel(*refs, ids: bool, sub: int):
    """``tail`` [columns, 8, lanes]: a column's last rows, from tile to
    tile."""
    from jax.experimental import pallas as pl
    cut_ref, refs = (refs[0], refs[1:]) if ids else (None, refs)
    x_ref, w_ref, b_ref, *pos_refs, o_ref, tail = refs
    r, s, c = (pl.program_id(i) for i in range(3))
    K, (rows, lanes) = w_ref.shape[1], x_ref.shape
    n = rows // sub

    @pl.when(s == 0)
    def _row_start():
        tail[c] = jnp.zeros(tail.shape[1:], F32)

    w, bias = w_ref[c], b_ref[c]

    def chunk(i, prev):
        r0 = pl.multiple_of(i * sub, sub)
        x = x_ref[pl.ds(r0, sub), :].astype(F32)

        def run(keep):
            acc, _ = _cc_taps(prev, x, w, bias, keep)
            o_ref[pl.ds(r0, sub), :] = jax.nn.silu(acc).astype(o_ref.dtype)

        if ids:
            jax.lax.cond(
                cut_ref[r, s * n + i] != 0,
                lambda: run(_cc_keeps(pos_refs[0], r0, sub, lanes, K)),
                lambda: run(None))
        else:
            run(None)
        return x[sub - 8:]

    tail[c] = jax.lax.fori_loop(0, n, chunk, tail[c])


def _cc_bwd_kernel(*refs, ids: bool, sub: int):
    """``after`` [columns, K - 1, 8, lanes] carries, a tap, the first rows
    of the cotangent of the convolution's sum of the chunk after, masked as
    that chunk's tokens ask (they are the ones the taps read the other way
    reach)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    cut_ref, refs = (refs[0], refs[1:]) if ids else (None, refs)
    (x_ref, xb_ref, g_ref, w_ref, b_ref, *pos_refs, dx_ref, dw_ref, db_ref,
     after) = refs
    r, s, c = (pl.program_id(i) for i in range(3))
    K, (rows, lanes) = w_ref.shape[1], x_ref.shape
    n = rows // sub
    tile = pl.num_programs(1) - 1 - s

    @pl.when(s == 0)
    def _row_end():
        after[c] = jnp.zeros(after.shape[1:], F32)

    @pl.when((r == 0) & (s == 0))
    def _first_tile():
        dw_ref[c] = jnp.zeros(dw_ref.shape[1:], F32)
        db_ref[c] = jnp.zeros(db_ref.shape[1:], F32)

    sum0 = lambda t: jnp.sum(t, axis=0, keepdims=True)
    w, bias = w_ref[c], b_ref[c]

    def chunk(k, carry):
        i = n - 1 - k
        r0 = pl.multiple_of(i * sub, sub)
        x = x_ref[pl.ds(r0, sub), :].astype(F32)
        g = g_ref[pl.ds(r0, sub), :].astype(F32)
        # the 8 rows before the chunk: the tile's own, the tile before's,
        # nothing at the row's start
        inside = pl.multiple_of(jnp.maximum(r0 - _GC_HEAD, 0), _GC_HEAD)
        prev = jnp.where(i > 0, x_ref[pl.ds(inside, _GC_HEAD), :],
                         xb_ref[...]).astype(F32)[_GC_HEAD - 8:]
        prev = jnp.where((i == 0) & (tile == 0), 0.0, prev)

        def run(keep):
            nxt, dw, db = carry
            acc, back = _cc_taps(prev, x, w, bias, keep)
            sig = jax.nn.sigmoid(acc)
            ga = g * sig * (1.0 + acc * (1.0 - sig))
            dx, mine = 0.0, []
            for j in range(K - 1):  # ray-tpu: noqa[RT506]
                # the taps read the other way: token t's input reaches
                # t + d, where that token lies d tokens into t's document
                d = K - 1 - j
                there = ga if keep is None else jnp.where(keep[j], ga, 0.0)
                dx = dx + pltpu.roll(jnp.concatenate(
                    [there, nxt[j]], axis=0), sub + 8 - d, 0)[:sub] * w[j]
                mine.append(there[:8])
            dx_ref[pl.ds(r0, sub), :] = (dx + ga * w[K - 1]).astype(
                dx_ref.dtype)
            sums = [sum0(sh * ga) for sh in back] + [sum0(x * ga)]
            return (tuple(mine), dw + jnp.concatenate(sums, axis=0),
                    db + sum0(ga))

        if not ids:
            return run(None)
        return jax.lax.cond(
            cut_ref[r, tile * n + i] != 0,
            lambda: run(_cc_keeps(pos_refs[0], r0, sub, lanes, K)),
            lambda: run(None))

    nxt, dw, db = jax.lax.fori_loop(0, n, chunk, (
        tuple(after[c, j] for j in range(K - 1)),
        jnp.zeros((K, lanes), F32), jnp.zeros((1, lanes), F32)))
    for j in range(K - 1):  # ray-tpu: noqa[RT506]
        after[c, j] = nxt[j]
    dw_ref[c] += dw
    db_ref[c] += db


#: tokens of a forward grid step of the pair (the backward's: half): a step
#: costs 0.35 us beside its work
_CC_ROWS = 2048
#: numbers of a chunk, what a tile is worked through at a time (64 tokens of
#: 512 channels: 32 vector registers an array)
_CC_CHUNK = 64 * 512


def _cc_tile(S: int, lo: int, width: int, backward: bool):
    """(tokens, channels) of a grid step on ``width`` channels that start at
    column ``lo`` of their array, or None where the shapes do not tile: a
    row whole tiles of ``_CC_ROWS`` tokens, the channels whole blocks of
    lanes from column 0 on."""
    lanes = next((n for n in (_GC_LANES, 256, 128)
                  if width % n == 0 and lo % n == 0), None)
    if lanes is None or S % _CC_ROWS:
        return None
    return _CC_ROWS // 2 if backward else _CC_ROWS, lanes


@functools.partial(jax.jit, static_argnames=("backward", "lo", "interpret"))
def _cc_call(backward: bool, c, lo: int, w, b, since, g, interpret: bool):
    """One kernel call on the columns ``lo : lo + w.shape[1]`` of c [R, S,
    F], read where they lie: the forward's result [R, S, width], or with
    the cotangent ``g`` of it (dc [R, S, width], dw float32 [K, width], db
    float32 [width]).  ``since``: ``_since_start`` [R, S], or None.  One
    traced body for every call site of a shape: a model's mixers trace and
    lower each kernel once (``ops/kda._kda``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .attention import LANES
    R, S, _ = c.shape
    K, width = w.shape
    fits = _cc_tile(S, lo, width, backward)
    if fits is None:
        raise ValueError(
            f"causal_conv's kernels do not tile {width} channels from column "
            f"{lo} of rows of {S} tokens: whole tiles of {_CC_ROWS} tokens "
            "and of 128 lanes, or impl='xla'")
    rows, lanes = fits
    sub = min(_CC_CHUNK // lanes, rows)
    nC, nS, off, step = width // lanes, S // rows, lo // lanes, \
        rows // _GC_HEAD
    # A column's taps and bias: one block, the whole array, indexed by the
    # column inside the kernel (no copy a grid step).
    by_column = lambda a: jnp.moveaxis(
        a.astype(F32).reshape(-1, nC, lanes), 1, 0)
    held = lambda n: pl.BlockSpec((nC, n, lanes), lambda r, s, c, *_: (0, 0, 0))
    # the backward walks a row's tiles last to first
    at = (lambda s: nS - 1 - s) if backward else (lambda s: s)
    tile = lambda o: pl.BlockSpec((None, rows, lanes),
                                  lambda r, s, c, *_: (r, at(s), o + c))
    like = jax.ShapeDtypeStruct((R, S, width), c.dtype)
    operands, in_specs = [c], [tile(off)]
    if backward:
        before = pl.BlockSpec((None, _GC_HEAD, lanes), lambda r, s, c, *_: (
            r, jnp.maximum(at(s) * step - 1, 0), off + c))
        operands += [c, g]
        in_specs += [before, tile(0)]
    operands += [by_column(w), by_column(b)]
    in_specs += [held(K), held(1)]
    if since is not None:
        # What a tile's tokens say of their documents, along 128 lanes, and
        # (by scalar prefetch) a flag a chunk: whether a document starts in
        # it.
        operands += [jnp.broadcast_to(since[..., None],
                                      since.shape + (LANES,))]
        in_specs += [pl.BlockSpec((None, rows, LANES),
                                  lambda r, s, c, *_: (r, at(s), 0))]
        operands.insert(0, jnp.any(since.reshape(R, S // sub, sub) < K - 1,
                                   axis=2).astype(jnp.int32))
    if backward:
        sums = lambda n: jax.ShapeDtypeStruct((nC, n, lanes), F32)
        out_specs, out_shape = [tile(0), held(K), held(1)], [
            like, sums(K), sums(1)]
    else:
        out_specs, out_shape = tile(0), like
    out = pl.pallas_call(
        functools.partial(_cc_bwd_kernel if backward else _cc_fwd_kernel,
                          ids=since is not None, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(since is not None), grid=(R, nS, nC),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(
                (nC, K - 1, 8, lanes) if backward else (nC, 8, lanes), F32)]),
        out_shape=out_shape, interpret=interpret,
        name="ssm_conv_bwd" if backward else "ssm_conv_fwd",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3)}),
    )(*operands)
    if not backward:
        return out
    flat = lambda a: jnp.moveaxis(a, 0, 1).reshape(-1, width)
    return out[0], flat(out[1]), flat(out[2])[0]


def _columns(start: int, widths):
    """(first column in the array, its columns among the taps') of every
    part of the convolution's channels."""
    out, at = [], 0
    for n in widths:
        out.append((start + at, slice(at, at + n)))
        at += n
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv(c, w, b, segment_ids, start, widths, impl):
    """``causal_conv`` on the columns ``start : start + Ch`` of c, the
    result in parts of ``widths`` channels: a tuple of [B, S, width]."""
    K, Ch = w.shape
    with jax.named_scope("block/ssm/conv"):
        if impl == "xla":
            y = _conv_xla(c[..., start:start + Ch], w, b, segment_ids)
            return tuple(y[..., at] for _, at in _columns(start, widths))
        pos = None if segment_ids is None else _since_start(segment_ids,
                                                             K)
        return tuple(_cc_call(False, c, lo, w[:, at], b[at], pos, None,
                              impl == "kernel_interpret")
                     for lo, at in _columns(start, widths))


def _conv_fwd(c, w, b, segment_ids, start, widths, impl):
    return (_conv(c, w, b, segment_ids, start, widths, impl),
            (c, w, b, segment_ids))


def _conv_bwd(start, widths, impl, res, gs):
    c, w, b, segment_ids = res
    K, Ch = w.shape
    with jax.named_scope("block/ssm/conv"):
        if impl == "xla":
            dc, dw, db = _conv_xla_bwd(
                c[..., start:start + Ch], w, b, segment_ids,
                jnp.concatenate(gs, axis=-1))
        else:
            pos = None if segment_ids is None else _since_start(
                segment_ids, K)
            parts = [_cc_call(True, c, lo, w[:, at], b[at], pos, g,
                              impl == "kernel_interpret")
                     for (lo, at), g in zip(_columns(start, widths), gs)]
            dc, dw, db = (jnp.concatenate(a, axis=-1) for a in zip(*parts))
        if c.shape[-1] > Ch:    # the columns the convolution does not read
            dc = jnp.pad(dc, ((0, 0), (0, 0),
                              (start, c.shape[-1] - start - Ch)))
        return dc, dw.astype(w.dtype), db.astype(b.dtype), None


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv(c, w, b, segment_ids=None, *, start: int = 0, split=None,
                impl=None):
    """``silu(b + sum_j w[j] * c[t - (K - 1) + j])`` with ``c[s] = 0`` before
    the row's start: c [B, S, Ch], w [K, Ch] (tap K - 1 reads the token
    itself), b [Ch] -> [B, S, Ch] in c's dtype.  Depthwise: a channel reads
    its own past and nothing else; a row reads nothing of another row, and
    with ``segment_ids`` [B, S] (``documents``) a token nothing of another
    document: ``c[s] = 0`` before its document's start.  The taps, their
    sum, the silu and the gradients' sums are float32, rounded once.

    ``start``: c is wider than the convolution, which reads its columns
    ``start : start + Ch`` where they lie (a mixer's projection writes the
    gate, these and the time step side by side); ``split`` (widths that add
    up to Ch): the result in that many arrays, as the scan reads them.  The
    backward is written out: dc, dw and db from one pass over c and the
    cotangent.

    ``impl``: None, which is the Pallas pair (kernels ``ssm_conv_fwd`` /
    ``ssm_conv_bwd``, a call a part) on a TPU where a row is whole tiles of
    ``_CC_ROWS`` tokens and every part whole lane tiles from a column that
    is one (``_cc_tile``), and ``jnp`` elsewhere: on the CPU, under a mesh,
    at a row that is not whole tiles (``_conv_xla``, the definition: the
    pair's forward is equal to it to the last bit, its gradients to an
    accumulation order); ``"xla"``, ``"kernel"``, ``"kernel_interpret"``
    (the tests).  A call without ids compiles the kernels without the
    documents' operand and without a select.  Which form a traced call took
    is counted in ``ray_tpu_ssm_conv_path_total`` (``path`` kernel / xla,
    ``taps``, ``segments`` yes / no)."""
    from .attention import _on_tpu          # at the call: tests steer it
    S = c.shape[1]
    K, Ch = w.shape
    widths = (Ch,) if split is None else tuple(split)
    if sum(widths) != Ch or start + Ch > c.shape[-1]:
        raise ValueError(f"parts {widths} at column {start} of "
                         f"{c.shape[-1]} are not the taps' {Ch} channels")
    if impl is None:
        from ..parallel.mesh import get_global_mesh
        mesh = get_global_mesh()
        tiles = 2 <= K <= 8 and all(
            _cc_tile(S, lo, at.stop - at.start, False)
            for lo, at in _columns(start, widths))
        impl = "kernel" if (_on_tpu() and tiles and not (
            mesh is not None and mesh.size > 1)) else "xla"
    telemetry.inc("ray_tpu_ssm_conv_path_total", tags={
        "path": "xla" if impl == "xla" else "kernel", "taps": str(K),
        "segments": "no" if segment_ids is None else "yes"})
    out = _conv(c, w, b, segment_ids, start, widths, impl)
    return out[0] if split is None else out


def chunk_carry(dt, A, chunk: int, segment_ids=None):
    """Mean over rows, chunks and heads of ``exp(l_Q)``, the share of a state
    that a whole chunk hands on: dt [B, S, H] float32, A [H].  Only whole
    chunks count.  No gradient.  With ``segment_ids`` [B, S]: (the mean over
    the chunks in which no document starts, which alone hand a state on;
    the number of chunks in which one does, float32)."""
    B, S, H = dt.shape
    n = S // chunk
    if not n:
        one = jnp.ones((), F32)
        return one if segment_ids is None else (one, jnp.zeros((), F32))
    a = dt[:, :n * chunk].astype(F32).reshape(B, n, chunk, H) * A.astype(F32)
    share = jnp.exp(jnp.sum(a, axis=2))                     # [B, n, H]
    if segment_ids is None:
        return jax.lax.stop_gradient(jnp.mean(share))
    cut = jnp.any(_starts(segment_ids)[:, :n * chunk].reshape(B, n, chunk),
                  axis=2)
    whole = jnp.sum(~cut)
    mean = jnp.sum(jnp.where(cut[..., None], 0.0, share)) / jnp.maximum(
        whole * H, 1)
    return jax.lax.stop_gradient(
        (jnp.where(whole > 0, mean, 1.0).astype(F32),
         jnp.sum(cut).astype(F32)))


#: What a document's first token adds to the exponent of its decay: ``exp``
#: of it is zero in float32 (the least it holds is ``exp(-103.3)``), so the
#: token decays the state before it, and every product that spans it, to
#: nothing, in the chunked forms as in the recurrence.  Every exponent the
#: scan forms is a difference of running sums that is never positive, so one
#: boundary between two tokens is enough, and two add up.  The running sums
#: are float32: n boundaries inside a chunk cost the decays of its last
#: documents ``n * 128 * 2 ** -24`` of absolute precision in the exponent
#: (6e-5 at 8), under the products' bfloat16 operands by two orders.
_DROP = 128.0


def _starts(segment_ids):
    """bool [B, S]: whether a token starts a document inside its row (its id
    is not its predecessor's); the row's first does not."""
    ids = segment_ids
    return jnp.pad(ids[:, 1:] != ids[:, :-1], ((0, 0), (1, 0)))


def _refuse_a_mesh() -> None:
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "ssd_scan on a mesh: heads and groups split over tp, and a row "
            "split over sp handing its state on, are not built (ROADMAP M8)")


def _scan_xla(X, dt, A, B, C, D, Q: int, drop=None):
    """The chunked form in ``jnp`` over whole chunks: X [Bt, S, H, P] with
    ``Q`` dividing S -> y float32 of X's shape.  ``drop`` [Bt, S]: what a
    token takes off its decay's exponent (``_DROP`` where a document
    starts)."""
    Bt, S, H, P = X.shape
    G, N = B.shape[2:]
    R, nc, dtype = H // G, S // Q, X.dtype
    Xc = X.reshape(Bt, nc, Q, G, R, P)
    Bc, Cc = B.reshape(Bt, nc, Q, G, N), C.reshape(Bt, nc, Q, G, N)
    dtc = dt.reshape(Bt, nc, Q, H)
    a = dtc * A.astype(F32)
    if drop is not None:
        a = a - drop.reshape(Bt, nc, Q, 1)
    # [Bt, nc, H, Q]: the running sum of dt A inside a chunk, and dt.
    l = jnp.moveaxis(jnp.cumsum(a, axis=2), 2, 3)
    dth = jnp.moveaxis(dtc, 2, 3)
    total = l[..., -1]                                   # [Bt, nc, H]

    # Inside a chunk: the masked, decayed C . B, a head at a time.
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc, preferred_element_type=F32)
    visible = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(visible, l[..., :, None] - l[..., None, :],
                              -jnp.inf))                 # [Bt, nc, H, Q, Q]
    m = (cb[:, :, :, None] * (decay * dth[..., None, :]).reshape(
        Bt, nc, G, R, Q, Q)).astype(dtype)
    y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", m, Xc,
                   preferred_element_type=F32)

    # A chunk's own state, then the states handed on from chunk to chunk.
    left = (jnp.exp(total[..., None] - l) * dth).reshape(Bt, nc, G, R, Q)
    own = jnp.einsum(
        "bcsgrp,bcsgn->bcgrpn",
        (Xc.astype(F32) * jnp.moveaxis(left, 4, 2)[..., None]).astype(dtype),
        Bc, preferred_element_type=F32)
    keep = jnp.exp(total).reshape(Bt, nc, G, R)

    def hand_on(state, chunk_):
        own_c, keep_c = chunk_
        return keep_c[..., None, None] * state + own_c, state

    _, before = jax.lax.scan(
        hand_on, jnp.zeros((Bt, G, R, P, N), F32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(keep, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                  # [Bt, nc, G, R, P, N]
    carried = jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cc, before.astype(dtype),
                         preferred_element_type=F32)
    y = y + carried * jnp.moveaxis(
        jnp.exp(l).reshape(Bt, nc, G, R, Q), 4, 2)[..., None]
    y = y + Xc.astype(F32) * D.astype(F32).reshape(G, R)[..., None]
    return y.reshape(Bt, S, H, P)


# ------------------------------------------------------- the Pallas kernels
#
# A grid step is one chunk of one group of one row: the group's ``R`` heads
# (R P channels along the lanes) on the one B and C they share; of a group
# wider than ``_STEP_LANES`` channels (Granite-4.0-H's one group of 64 heads,
# 4,096 channels) a slice of its heads, the slices grid rows of their own
# that read the same B and C block, form ``C . B`` each and hand out each
# its share of dB and dC in float32, summed outside (``_laid_out``).  The chunks
# of a row run in order (``arbitrary``) and hand the group's state on in
# VMEM scratch, [N, R P] float32; the backward walks them the other way and
# hands the state's cotangent back.  The decayed masks [Q, Q] a head, the
# masked C . B and every float32 partial result live in VMEM only.  What a
# head needs of ``l`` (the running sum of dt A inside the chunk) and dt as a
# column [Q, 1] and as a row [1, Q] comes in from XLA in both layouts
# ([Bt, G, S, R] and [Bt, G, R, S]; 64 numbers a token); their cotangents
# go back as columns.  Heads of 64 channels are taken two at a time, a tile
# of 128 lanes, each product with the other head's lanes zeroed: nothing is
# cut or joined inside a tile (0.61 ms a row forward for 0.86).  The loops
# over a group's heads unroll while a kernel is traced, so the lint's RT506
# (op-by-op dispatch in a loop) does not apply to them.


def _nt(a, b):
    """a [m, k] . b [n, k]^T -> [m, n] float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _tn(a, b):
    """a [k, m]^T . b [k, n] -> [m, n] float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)


def _mm(a, b):
    return jnp.dot(a, b, preferred_element_type=F32)


def _causal(Q: int):
    """[Q, Q] float32, 0 where s <= q and -inf elsewhere: added to an
    exponent, it masks what a token may not see."""
    q = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return jnp.where(s <= q, 0.0, -jnp.inf).astype(F32)


def _heads_a_tile(P: int):
    """(heads whose channels share a lane tile, the tile's channels): heads
    of 64 channels go through the kernels in pairs, so that nothing is cut
    or joined inside a tile of 128 lanes."""
    from .attention import LANES
    per = LANES // P if P < LANES else 1
    return per, per * P


def _of_head(a, k: int, P: int):
    """a [rows, w] with the lanes of every head but the tile's k-th zero."""
    if a.shape[1] == P:
        return a
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, a.shape[1]), 1)
    return jnp.where(lane // P == k, a, jnp.zeros_like(a))


def _by_head(columns, P: int):
    """Per-head columns [rows, 1] (a tile's heads in order) along the lanes
    of their heads: [rows, heads * P]."""
    w = len(columns) * P
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    out = columns[-1]
    for k in range(len(columns) - 2, -1, -1):
        out = jnp.where(lane // P == k, columns[k], out)
    return jnp.broadcast_to(out, (columns[0].shape[0], w))


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, lcol_ref, dtcol_ref, lrow_ref,
                    dtrow_ref, d_ref, y_ref, *rest, R: int, P: int):
    """y of a chunk [Q, R P]; with a ``s_ref`` among ``rest``, the state the
    chunk started from is kept for the backward."""
    from jax.experimental import pallas as pl
    *s_ref, state = rest

    @pl.when(pl.program_id(2) == 0)
    def _row_start():
        state[...] = jnp.zeros(state.shape, F32)

    Bm, Cm = b_ref[...], c_ref[...]
    dtype, Q = Bm.dtype, Bm.shape[0]
    lcol, dtcol = lcol_ref[...], dtcol_ref[...]          # [Q, R]
    lrow, dtrow = lrow_ref[...], dtrow_ref[...]          # [R, Q]
    cb, causal = _nt(Cm, Bm), _causal(Q)                 # [Q, Q]
    per, w = _heads_a_tile(P)
    for j in range(R // per):  # ray-tpu: noqa[RT506]
        at = slice(j * w, (j + 1) * w)
        X, prev = x_ref[:, at], state[:, at]
        if s_ref:
            s_ref[0][:, at] = prev
        X32 = X.astype(F32)
        y = jnp.zeros((Q, w), F32)
        e, left, keep = [], [], []
        for k in range(per):  # ray-tpu: noqa[RT506]
            r = j * per + k
            lc = lcol[:, r:r + 1]
            m = cb * (jnp.exp(lc - lrow[r:r + 1] + causal) * dtrow[r:r + 1])
            y = y + _mm(m.astype(dtype), _of_head(X, k, P))
            total = lc[Q - 1:Q]                          # [1, 1]
            e.append(jnp.exp(lc))
            left.append(jnp.exp(total - lc) * dtcol[:, r:r + 1])
            keep.append(jnp.exp(total))
        y = y + _mm(Cm, prev.astype(dtype)) * _by_head(e, P)
        y_ref[:, at] = (y + X32 * d_ref[:, at]).astype(y_ref.dtype)
        state[:, at] = (_by_head(keep, P) * prev
                        + _tn(Bm, (X32 * _by_head(left, P)).astype(dtype)))


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, lcol_ref, dtcol_ref, lrow_ref,
                    dtrow_ref, d_ref, s_ref, dy_ref, dx_ref, db_ref, dc_ref,
                    dlcol_ref, ddtcol_ref, dd_ref, dstate, *, R: int,
                    P: int):
    """The cotangents of a chunk, the chunks of a row walked last to first;
    ``dstate`` holds the cotangent of the state the chunk hands on.  With m
    = cb exp(l_q - l_s) dt_s the masked product a head: l_q's cotangent is
    the rows' sums of dm m, which is dy_q . (m X)_q; l_s's the columns'
    (negative), which is X_s . (m^T dy)_s, and dt_s's that over dt_s: two
    products more a head, and no [Q, Q] array is summed."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _row_end():
        dstate[...] = jnp.zeros(dstate.shape, F32)

    Bm, Cm = b_ref[...], c_ref[...]
    dtype, Q = Bm.dtype, Bm.shape[0]
    lcol, dtcol = lcol_ref[...], dtcol_ref[...]
    lrow, dtrow = lrow_ref[...], dtrow_ref[...]
    cb, causal = _nt(Cm, Bm), _causal(Q)
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    dcb = jnp.zeros((Q, Q), F32)
    dB = dC = jnp.zeros(Bm.shape, F32)
    per, w = _heads_a_tile(P)
    rows = lambda a, k: jnp.sum(_of_head(a, k, P), axis=1, keepdims=True)
    for j in range(R // per):  # ray-tpu: noqa[RT506]
        at = slice(j * w, (j + 1) * w)
        X, dY = x_ref[:, at], dy_ref[:, at]
        X32, dY32 = X.astype(F32), dY.astype(F32)
        prev, dS = s_ref[:, at], dstate[:, at]           # [N, w] float32
        prev_b, dS_b = prev.astype(dtype), dS.astype(dtype)
        dXl = _mm(Bm, dS_b)      # the cotangent of left_s X_s, [Q, w]
        held = jnp.sum(dS * prev, axis=0, keepdims=True)             # [1, w]
        y = jnp.zeros((Q, w), F32)
        dx = jnp.zeros((Q, w), F32)
        e, grow, left, keep = [], [], [], []
        for k in range(per):  # ray-tpu: noqa[RT506]
            r = j * per + k
            lc = lcol[:, r:r + 1]
            Ld = jnp.exp(lc - lrow[r:r + 1] + causal) * dtrow[r:r + 1]
            dYk = _of_head(dY, k, P)
            dcb = dcb + _nt(dYk, X) * Ld
            m = (cb * Ld).astype(dtype)
            y = y + _mm(m, _of_head(X, k, P))
            dx = dx + _tn(m, dYk)
            total = lc[Q - 1:Q]
            e.append(jnp.exp(lc))
            grow.append(jnp.exp(total - lc))
            left.append(grow[k] * dtcol[:, r:r + 1])
            keep.append(jnp.exp(total))
        e_, left_ = _by_head(e, P), _by_head(left, P)
        y = y + _mm(Cm, prev_b) * e_                     # y less D X
        into, out_of, lefts = dY32 * y, X32 * dx, dXl * X32
        for k in range(per):  # ray-tpu: noqa[RT506]
            r = j * per + k
            dtc = dtcol[:, r:r + 1]
            dleft, down = rows(lefts, k), rows(out_of, k)            # [Q, 1]
            t = dleft * left[k]
            dtotal = (jnp.sum(t, axis=0, keepdims=True)
                      + keep[k] * rows(held, k))
            dlcol_ref[:, r:r + 1] = (rows(into, k) - down - t
                                     + jnp.where(last, dtotal, 0.0))
            ddtcol_ref[:, r:r + 1] = (
                jnp.where(dtc > 0, down / jnp.where(dtc > 0, dtc, 1.0), 0.0)
                + dleft * grow[k])
        dx_ref[:, at] = (dx + dXl * left_
                         + dY32 * d_ref[:, at]).astype(dx_ref.dtype)
        dd_ref[:, at] = jnp.sum(dY32 * X32, axis=0, keepdims=True)
        dYe, Xl = (dY32 * e_).astype(dtype), (X32 * left_).astype(dtype)
        dC = dC + _nt(dYe, prev_b)
        dB = dB + _nt(Xl, dS_b)
        dstate[:, at] = _by_head(keep, P) * dS + _tn(Cm, dYe)
    dcb = dcb.astype(dtype)
    dc_ref[...] = (_mm(dcb, Bm) + dC).astype(dc_ref.dtype)
    db_ref[...] = (_tn(dcb, Cm) + dB).astype(db_ref.dtype)


def _ssd_call(kernel, name, dims, reverse, extra_in, outs, interpret):
    """A kernel over the grid (row, group or slice of one, chunk): the eight
    arrays every kernel reads first (``_laid_out``), then ``extra_in`` /
    ``outs`` as (spec, array or shape) pairs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    Bt, G, nc, Q, R, P, N, slices = dims
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    tokens = lambda w, of=(lambda g: g): pl.BlockSpec(
        (None, Q, w), lambda b, g, c: (b, at(c), of(g)))
    col = pl.BlockSpec((None, None, Q, R), lambda b, g, c: (b, g, at(c), 0))
    row = pl.BlockSpec((None, None, R, Q), lambda b, g, c: (b, g, 0, at(c)))
    lanes = pl.BlockSpec((None, 1, R * P), lambda b, g, c: (g, 0, 0))
    state = pl.BlockSpec((None, None, None, N, R * P),
                         lambda b, g, c: (b, g, at(c), 0, 0))
    part = pl.BlockSpec((None, None, None, 1, R * P),
                        lambda b, g, c: (b, g, at(c), 0, 0))
    # B and C are their group's, whatever slice of it the grid row is; a
    # row's share of their gradients is its own.
    specs = {"tokens": tokens(R * P), "share": tokens(N),
             "bc": tokens(N) if slices == 1 else tokens(
                 N, lambda g: g // slices),
             "col": col, "row": row, "lanes": lanes, "state": state,
             "part": part}
    first = ["tokens", "bc", "bc", "col", "col", "row", "row", "lanes"]
    return pl.pallas_call(
        functools.partial(kernel, R=R, P=P), grid=(Bt, G, nc),
        in_specs=[specs[k] for k in first + [k for k, _ in extra_in]],
        out_specs=[specs[k] for k, _ in outs],
        out_shape=[shape for _, shape in outs],
        scratch_shapes=[pltpu.VMEM((N, R * P), F32)],
        interpret=interpret, name=name,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}))


def _running_sum(a, Q: int, layout: str, back: bool = False):
    """The sums of a [Bt, S, H] float32 over the tokens up to each one inside
    its chunk of Q (``back``: from each one on), laid out ``bcqh`` [Bt, nc,
    Q, H] or ``bhcq``: a product with a triangle of ones at full precision,
    the layout falling out of the product (a cumulative sum over a view with
    the group's 8 heads minor, and the turns after it, took 2.3 ms a row of
    8,192 on the chip where these take 0.7)."""
    Bt, S, H = a.shape
    ones = jnp.tril(jnp.ones((Q, Q), F32))
    return jnp.einsum(f"qs,bcsh->{layout}", ones.T if back else ones,
                      a.reshape(Bt, S // Q, Q, H),
                      precision=jax.lax.Precision.HIGHEST)


#: The channels of a group that a grid step holds at the most: Nemotron-3-
#: Nano's whole group, 8 heads of 64.  (At a chunk of 256 a step of 512
#: channels holds 2.4 MiB of blocks and scratch beside the [Q, Q] float32
#: mask a head; a group of 4,096 whole would hold 2 MB an array.)
_STEP_LANES = 512


def _slices(R: int, P: int) -> int:
    """The slices a group of ``R`` heads of ``P`` channels goes through the
    kernels in: the fewest whose heads fill whole lane tiles inside
    ``_STEP_LANES`` channels; 1 where the group fits or nothing divides."""
    from .attention import LANES
    per = _heads_a_tile(P)[0]
    return next((n for n in range(1, R + 1)
                 if R % n == 0 and (R // n) % per == 0
                 and (R // n * P) % LANES == 0
                 and R // n * P <= _STEP_LANES), 1)


def _laid_out(X, dt, A, B, C, D, Q, drop=None):
    """(dims, the eight arrays a kernel reads first) from the scan's
    arguments over whole chunks.  ``dims`` counts a wide group's slices as
    groups (``G`` of them, of ``R`` heads each) and says how many of them
    share a B and C."""
    Bt, S, H, P = X.shape
    groups, N = B.shape[2:]
    slices = _slices(H // groups, P)
    G = groups * slices
    R = H // G
    a = dt * A.astype(F32)
    if drop is not None:
        a = a - drop[..., None]
    col = lambda v: jnp.moveaxis(v.reshape(Bt, S, G, R), 1, 2)
    row = lambda v: v.reshape(Bt, G, R, S)
    lanes = jnp.repeat(D.astype(F32), P).reshape(G, 1, R * P)
    return (Bt, G, S // Q, Q, R, P, N, slices), (
        X.reshape(Bt, S, H * P), B.reshape(Bt, S, groups * N),
        C.reshape(Bt, S, groups * N), col(_running_sum(a, Q, "bcqh")), col(dt),
        row(_running_sum(a, Q, "bhcq")), row(jnp.swapaxes(dt, 1, 2)), lanes)


def _kernel_forward(X, dt, A, B, C, D, drop, Q, interpret, keep: bool):
    dims, ins = _laid_out(X, dt, A, B, C, D, Q, drop)
    Bt, G, nc, _, R, P, N, _ = dims
    outs = [("tokens", jax.ShapeDtypeStruct(ins[0].shape, X.dtype))]
    if keep:
        outs.append(("state", jax.ShapeDtypeStruct((Bt, G, nc, N, R * P),
                                                   F32)))
    out = _ssd_call(_ssd_fwd_kernel, f"ssd_fwd_q{Q}", dims, False, [], outs,
                    interpret)(*ins)
    return [out[0].reshape(X.shape)] + list(out[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan_kernels(X, dt, A, B, C, D, drop, Q, interpret):
    with jax.named_scope("block/ssm/scan"):
        return _kernel_forward(X, dt, A, B, C, D, drop, Q, interpret,
                               False)[0]


def _scan_kernels_fwd(X, dt, A, B, C, D, drop, Q, interpret):
    with jax.named_scope("block/ssm/scan"):
        y, states = _kernel_forward(X, dt, A, B, C, D, drop, Q, interpret,
                                    True)
    return y, (X, dt, A, B, C, D, drop, states)


def _scan_kernels_bwd(Q, interpret, saved, dy):
    X, dt, A, B, C, D, drop, states = saved
    with jax.named_scope("block/ssm/scan"):
        dims, ins = _laid_out(X, dt, A, B, C, D, Q, drop)
        Bt, G, nc, _, R, P, N, slices = dims
        S, H = X.shape[1], X.shape[2]
        like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, F32)
        # B's and C's gradients: a group's own, or a slice's share of them.
        dbc = ("bc", like(ins[1])) if slices == 1 else (
            "share", f32(Bt, S, G * N))
        dx, db, dc, dl, ddt, dd = _ssd_call(
            _ssd_bwd_kernel, f"ssd_bwd_q{Q}", dims, True,
            [("state", states), ("tokens", dy)],
            [("tokens", like(ins[0])), dbc, dbc, ("col", f32(Bt, G, S, R)),
             ("col", f32(Bt, G, S, R)), ("part", f32(Bt, G, nc, 1, R * P))],
            interpret)(*ins, states, dy.reshape(ins[0].shape))
        if slices > 1:
            db, dc = (g.reshape(Bt, S, G // slices, slices, N).sum(axis=3)
                      .astype(B.dtype) for g in (db, dc))
        by_token = lambda c: jnp.moveaxis(c, 1, 2).reshape(Bt, S, H)
        # l is the running sum of dt A inside a chunk: a token's dt A is in
        # the l of every token from it on there.
        da = _running_sum(by_token(dl), Q, "bcqh", back=True).reshape(
            Bt, S, H)
        return (dx.reshape(X.shape),
                (by_token(ddt) + da * A.astype(F32)).astype(dt.dtype),
                jnp.sum(da * dt, axis=(0, 1)).astype(A.dtype),
                db.reshape(B.shape), dc.reshape(C.shape),
                jnp.sum(dd.reshape(Bt, G, nc, R, P), axis=(0, 2, 4)
                        ).reshape(H).astype(D.dtype), None)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _kernels(X, B, Q: int, interpret: bool) -> bool:
    """Whether the kernels take a scan of these shapes here: a group's
    channels, the state's size and the chunk each whole lane tiles."""
    from .attention import LANES, _on_tpu     # at the call: tests steer it
    H, P = X.shape[2:]
    G, N = B.shape[2:]
    return bool((interpret or _on_tpu()) and (H // G * P) % LANES == 0
                and (LANES % P == 0 or P % LANES == 0)
                and N % LANES == 0 and Q % LANES == 0)


def ssd_scan(X, dt, A, B, C, D, chunk: int, *, segment_ids=None,
             interpret: bool = False):
    """The recurrence above over every row, in chunks of ``chunk`` tokens.

    X [Bt, S, H, P]; dt [Bt, S, H] float32, positive (after the softplus);
    A [H] negative; B, C [Bt, S, G, N] with H a multiple of G (head h reads
    group ``h // (H / G)``); D [H].  Returns y [Bt, S, H, P] in X's dtype.
    Every row starts from a zero state, and with ``segment_ids`` [Bt, S]
    every document of a row (a run of equal ids): a token whose predecessor
    has another id decays the state before it by ``exp(-_DROP)``, zero in
    float32, which is the recurrence with ``S_{t-1} = 0`` there.  In the
    chunked forms that one term in the running sums does all four things a
    boundary asks for: the decayed mask is zero between two documents'
    tokens, a chunk's own state adds up its last document's tokens alone,
    the state handed in reaches the tokens before the chunk's first
    boundary alone, and is handed on iff the chunk holds none; forward and
    backward, by the kernels as they are.  On a TPU (or with ``interpret``,
    for the tests) and where the shapes tile (``_kernels``) the Pallas pair
    computes it, elsewhere ``jnp``."""
    _refuse_a_mesh()
    S, H = X.shape[1:3]
    G = B.shape[2]
    if H % G:
        raise ValueError(f"{H} heads do not split into {G} groups")
    kernel = _kernels(X, B, chunk, interpret)
    telemetry.inc("ray_tpu_ssm_path_total",
                  tags={"path": "kernel" if kernel else "xla",
                        "chunk": str(chunk),
                        "segments": "no" if segment_ids is None else "yes",
                        "group_channels": str(H // G * X.shape[3])})
    dt = dt.astype(F32)
    drop = None if segment_ids is None else _DROP * _starts(
        segment_ids).astype(F32)
    pad = -S % chunk
    if pad:
        # Tokens that keep the state (dt 0: decay 1, nothing added) and that
        # nothing before them sees.
        grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2))
        X, dt, B, C = grow(X), grow(dt), grow(B), grow(C)
        drop = None if drop is None else grow(drop)
    if kernel:
        y = _scan_kernels(X, dt, A, B, C, D, drop, chunk, interpret)
    else:
        with jax.named_scope("block/ssm/scan"):
            y = _scan_xla(X, dt, A, B, C, D, chunk, drop)
    return y[:, :S].astype(X.dtype)


def _groups(x, groups: int):
    """The last axis in ``groups`` contiguous slices.  (As slices, and not as
    a [..., groups, d / groups] view: the TPU compiler tiles that view's two
    minor dimensions together and writes a copy of it out, and one of every
    statistic broadcast back over it.)"""
    w = x.shape[-1] // groups
    return [x[..., k * w:(k + 1) * w] for k in range(groups)]


def _gated(y, z, eps):
    """(``y * silu(z)`` float32, 1 / rms over the last axis)."""
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    return v, jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True)
                            + eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_group_norm(y, z, g, groups: int, eps: float = 1e-5):
    """Gate, then norm: ``v = y * silu(z)``; each of ``groups`` groups of
    channels divided by its own rms; times g.  y, z [B, S, d], g [d] ->
    [B, S, d] in y's dtype, float32 inside.

    The backward is written out: two passes over y, z and the cotangent (a
    group's two sums, then the gradients)."""
    with jax.named_scope("block/ssm/norm"):
        out = []
        for yk, zk, gk in zip(*(_groups(a, groups) for a in (y, z, g))):
            v, r = _gated(yk, zk, eps)
            out.append((v * r * gk.astype(F32)).astype(y.dtype))
        return jnp.concatenate(out, axis=-1)


def _norm_fwd(y, z, g, groups, eps):
    return gated_group_norm(y, z, g, groups, eps), (y, z, g)


def _norm_bwd(groups, eps, res, dout):
    y, z, g = res
    dy, dz, dg = [], [], []
    with jax.named_scope("block/ssm/norm"):
        for yk, zk, gk, dk in zip(*(_groups(a, groups)
                                    for a in (y, z, g, dout))):
            v, r = _gated(yk, zk, eps)
            dk = dk.astype(F32)
            h = dk * gk.astype(F32)
            # out = v r g with r = (mean v^2 + eps)^-1/2 over the group.
            dv = r * h - v * r ** 3 * jnp.mean(v * h, axis=-1, keepdims=True)
            z32 = zk.astype(F32)
            sig = jax.nn.sigmoid(z32)
            dy.append((dv * z32 * sig).astype(y.dtype))
            dz.append((dv * yk.astype(F32) * sig
                       * (1.0 + z32 * (1.0 - sig))).astype(z.dtype))
            dg.append(jnp.sum(dk * v * r, axis=tuple(range(y.ndim - 1))
                              ).astype(g.dtype))
        return tuple(jnp.concatenate(a, axis=-1) for a in (dy, dz, dg))


gated_group_norm.defvjp(_norm_fwd, _norm_bwd)
