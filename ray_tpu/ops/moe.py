"""Mixture-of-experts: sigmoid routing and a dropless layer over held experts.

Absent from the reference (SURVEY §2.4 EP row: delegated to vLLM) — built
natively.

Sigmoid scores with a selection bias, nothing dropped, and a layer that is
told which experts it holds (``sigmoid_routing``, ``dropless_experts``,
``update_selection_bias``; what ``models/afmoe.py`` runs).  The router
scores all ``X`` experts and picks ``k`` of them (8 of 128 there); the
layer computes the part of the result that its own ``Xh`` experts give,
as one chip of an expert-parallel group does, without the exchange.  The
assignments to held experts are sorted by expert into a buffer of static
size, R rows: twice the load expected at the share of the experts the
layer holds (``buffer_tiers``: T * k over the largest power of two that
leaves twice T * k * Xh / X, never over a quarter of the worst case; a call
whose load passes R takes its tokens through the buffer in as many slices,
so nothing is ever dropped), and multiplied by grouped matrix products over
the ragged groups:
three a row for a gated expert (``act(x W_gate) * (x W_up)``, then
``W_down``; Trinity's and Xing4.0's SwiGLU), two for an un-gated one
(``act(x W_up) W_down``; Nemotron-H's ``relu2``).
Rows go into the buffer and come back out through a pair of primitives
that are each other's transpose (``rows_of_tokens``, ``tokens_from_rows``)
on the indices of one counting sort a call (``_places``): gathers and
dense passes, forward and backward, and no scatter of rows, places or
counts.  On a TPU v5e a gathered row of 4 KB costs 6.4 ns and a
scatter-added one 88 (PERF.md, PR 30).  Into the buffer a row reads its
token (one XLA gather of R rows).  Out of it a token sums the rows it
holds, and on a TPU that is a Pallas kernel (``_sum_rows_kernel``) that
copies only the rows in use: a group's rows are in token order, so the rows
a tile of tokens holds of one expert are one contiguous run of the buffer,
and the kernel copies the Xh runs of a tile and adds each row to its
token's float32 sum in VMEM.  The ``jnp`` form it replaced there
(``_sum_rows_xla``, still what runs off the chip) gathers all T * k slots
of a call where an eighth hold a row and sums over k: 0.86 ms a time at
k 8, and 3.04 / 2.30 ms at k 6 / 4, where k in the second-minor place of
the float32 ``[T, k, E]`` array does not fill a tile of 8 and XLA copies
the whole array into a padded layout first; the kernel takes 0.28 / 0.31 /
0.27 ms at the three sparse cells' shapes (PERF.md, PR 45).

The grouped products are upstream's two Pallas kernels (megablox ``gmm``
and ``tgmm``) under a ``custom_vjp`` of this module (``grouped_matmul``),
and the tiles of each follow the call's shapes (``_gmm_tiles``).  ``gmm``
walks the buffer in tiles of ``tm`` rows and VISITS, for every group, every
tile the group touches, paying a whole tile's products a visit: a group of
``n`` rows that starts anywhere costs about ``n / tm + 1`` visits, so the
share of the products that is work is ``tiles in use / (tiles in use +
groups - 1)``.  With one row of 8,192 tokens a call a held expert gets
384-512 rows, and at ``tm`` 512 every tile straddles two groups and is
computed twice (30 / 40 / 46 % of the roofline in the three sparse cells;
PERF.md, PR 44).  The three kernels a product meets want different tiles:
the forward product and the rows' gradient (``gmm``, the second with the
weights transposed) a low tile of rows, which pays only while the whole
contraction is one tile, because the weight block's index then stays put
over a group's visits and the pipeline does not fetch it again (with k in
tiles the block is fetched anew every grid step: 2 MB against a few
microseconds of products); the weights' gradient (``tgmm``) contracts over
``tm``, its bytes a step do not depend on it, and its result's tiles cover
k and n with the least padding.  Every tile set fits Mosaic's DEFAULT
scoped VMEM (16 MiB), double buffers and the float32 accumulator counted
(``_VMEM_BUDGET``): ``vmem_limit_bytes`` is never raised, because a kernel
that asks for more hangs Xing4.0's compiled step in its first call
(PERF.md, PR 42).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..util import telemetry
from .norms import poly_norm


class SigmoidRouting(NamedTuple):
    expert_index: jax.Array     # [T, k] int32, over all X experts
    weights: jax.Array          # [T, k] float32
    counts: jax.Array           # [X] int32: assignments each expert got


def sigmoid_routing(x, router_w, bias, k: int, route_scale: float = 1.0,
                    route_norm: bool = True, eps=1e-20, n_group: int = 1,
                    topk_group: int = 1) -> SigmoidRouting:
    """x [T, E], router_w [E, X], bias [X] float32 (the selection bias: state,
    not a parameter).  ``s = sigmoid(x W)`` in float32; the ``k`` experts are
    chosen by ``s + bias`` and weighted by ``s`` alone, normalised over the
    chosen (``route_norm``, by ``sum + eps``) and scaled.  torchtitan's MoE
    router with ``score_func="sigmoid"``; LFM2's adds 1e-6 to the sum.

    With ``n_group`` > 1 the choice is group-limited (DeepSeek-V3's
    ``noaux_tc``): the X experts are ``n_group`` consecutive groups, a group
    scores the sum of its two largest ``s + bias``, the ``topk_group`` best
    groups are kept and the ``k`` chosen inside them.  One group traces the
    equations it always did."""
    s = jax.nn.sigmoid(jnp.einsum(
        "te,ex->tx", x.astype(jnp.float32), router_w.astype(jnp.float32)))
    choice = s + jax.lax.stop_gradient(bias)
    if n_group > 1:
        with jax.named_scope("groups"):
            by_group = choice.reshape(choice.shape[0], n_group, -1)
            best = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
            _, kept = jax.lax.top_k(best, topk_group)            # [T, g]
            keep = jnp.any(kept[..., None] == jnp.arange(n_group), axis=1)
            choice = jnp.where(keep[..., None], by_group, -jnp.inf
                               ).reshape(choice.shape)
    _, top = jax.lax.top_k(choice, k)
    # One compare serves the weights and the counts: as a gather and a
    # scatter-add (and the gather's transpose, a second one) they cost 2.2
    # of a call's 2.7 ms in the router on the chip (PERF.md, PR 30).
    chosen = top[..., None] == jnp.arange(s.shape[-1])       # [T, k, X]
    w = jnp.sum(jnp.where(chosen, s[:, None, :], 0), axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    counts = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    return SigmoidRouting(top, w * route_scale, counts)


def update_selection_bias(bias, counts, rate: float = 1e-3):
    """The step's update of the selection bias from the assignments each
    expert got in it: ``d = rate * sign(mean(n) - n)``, ``b + d - mean(d)``
    (torchtitan's rule; no gradient reaches the bias).  Leading axes (layers)
    broadcast."""
    n = counts.astype(jnp.float32)
    d = rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)
    return bias + d - jnp.mean(d, axis=-1, keepdims=True)


#: The dropless buffer holds at most 1 / BUFFER_TIERS of the worst case's
#: rows: the tiers where an eighth of the experts or more is held
#: (``buffer_tiers`` takes more where less is).
BUFFER_TIERS = 4

#: The tiles every grouped product took until PR 44 (PERF.md, PR 29: best
#: of six at 2,048 rows a group), still ``tgmm``'s where groups are tall and
#: ``gmm``'s where the contraction is too long to stay whole; 1,024 is the
#: widest k / n tile of ``tgmm``.
_BIG_TILES = (512, 1024, 1024)

#: Rows of a tile where a group is a few tiles tall or less, and the rows a
#: group from which ``tgmm`` reads faster at 512 again (PERF.md, PR 44,
#: step 0: 256 wins by 7 % at 1,024 rows a group, 512 by 1.5 % at 4,096).
_LOW_TILE, _TALL_GROUP = 256, 4096

#: What the double buffers of a grid step's three blocks and its float32
#: accumulator may count: three quarters of Mosaic's default scoped VMEM of
#: 16 MiB, the rest being the body's own temporaries (a float32 product, a
#: transposed block: the compiler's own count passed this one by up to
#: 3.0 MiB over 321 tile sets compiled for a v5e; PERF.md, PR 44).
_VMEM_BUDGET = 12 * 2 ** 20

#: the three kernels a grouped product meets
GMM_KINDS = ("fwd", "dlhs", "tgmm")


def _gmm_vmem_bytes(kind: str, tm: int, tk: int, tn: int,
                    itemsize: int = 2) -> int:
    """Scoped VMEM a grid step of upstream's kernel holds: two buffers of
    each of its three blocks and the float32 accumulator.  ``gmm`` ("fwd",
    "dlhs") accumulates a [tm, tn] tile of rows over k; ``tgmm`` a [tk, tn]
    tile of one group's weights over chunks of tm rows."""
    if kind == "tgmm":
        return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def _cover(size: int, most: int) -> int:
    """The tile up to ``most`` that covers ``size`` with the least padding:
    ``size`` itself where it fits, else a multiple of 128 (the largest at a
    tie).  2,688 -> 896 (x 3), 1,856 -> 640 (x 3 = 1,920), 3,584 -> 896."""
    if size <= most:
        return size
    return min(range(most - most % 128, 127, -128),
               key=lambda t: -(-size // t) * t - size)


def _row_tile(tile: int, rows: int) -> int:
    """Upstream asks that ``tm`` divide the buffer's rows: the largest
    multiple of 128 up to ``tile`` that does, else of 8 (a sublane tile),
    else the largest divisor."""
    for step in (128, 8, 1):
        for t in range(min(tile, rows) // step * step, 0, -step):
            if rows % t == 0:
                return t
    return rows


def _gmm_tiles(kind: str, rows_a_group: float, k: int, n: int):
    """(tm, tk, tn) of one of the three kernels of a grouped product, from
    the call's shapes alone.  ``kind``: "fwd" (``gmm``: rows [R, k] times
    weights [G, k, n]), "dlhs" (``gmm`` on transposed weights [G, n, k]: the
    rows' gradient, which contracts the forward's n) or "tgmm" (the weights'
    gradient [G, k, n], contracting the rows).  ``k`` and ``n`` are the
    KERNEL's, as upstream's tiling is.  ``rows_a_group``: the rows a group
    is expected to hold (static: T * k / X for a layer call).

    A visit pays a whole tile's products, so a tile is low
    (``_LOW_TILE``: at 384-512 rows a group 256 and 128 read the same,
    1.2-1.7 times faster than 512; PERF.md, PR 44).  For ``gmm`` a low
    tile pays only with the contraction whole: the weight block ``(group,
    0, n)`` then keeps its index over a group's visits and is fetched once
    a group, where with k in tiles it is fetched anew every step.  So
    tk = k, and tn covers n with the least padding among the widths that
    keep the step inside ``_VMEM_BUDGET``; that read faster than today's
    tiles at every height from 128 to 8,192 rows a group, so ``gmm`` does
    not ask for the rows.  A k too long for that at tn 512 keeps today's
    tiles.  ``tgmm`` contracts over tm, reads tm * (tk + tn) a step
    whatever tm is, covers k and n with the least padding up to 1,024, and
    takes the tall tile again from ``_TALL_GROUP`` rows a group."""
    if kind == "tgmm":
        tm = _LOW_TILE if rows_a_group < _TALL_GROUP else _BIG_TILES[0]
        return (tm, _cover(k, _BIG_TILES[1]), _cover(n, _BIG_TILES[2]))
    fits = next((tn for tn in range(2 * _BIG_TILES[2], 511, -128)
                 if _gmm_vmem_bytes(kind, _LOW_TILE, k, tn) <= _VMEM_BUDGET),
                None)
    return (_LOW_TILE, k, _cover(n, fits)) if fits else _BIG_TILES


def _tiles_of(kind, rows, k, n, rows_a_group, tiling):
    """The tiles of one kernel of a call: an explicit ``tiling`` or
    ``_gmm_tiles``' pick, cut to the operand (``tm`` to a divisor of the
    buffer's ``rows``) and counted."""
    tm, tk, tn = tiling or _gmm_tiles(kind, rows_a_group, k, n)
    t = (_row_tile(tm, rows), min(tk, k), min(tn, n))
    telemetry.inc("ray_tpu_gmm_tile_geometry_total", tags={
        "kind": kind, "tm": str(t[0]), "tk": str(t[1]), "tn": str(t[2]),
        "rows_a_group": str(int(rows_a_group))})
    return t


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, rhs, group_sizes, rows_a_group, tiling, interpret):
    """Upstream's ``gmm`` under a rule of this module's, so that the
    forward product, the rows' gradient and the weights' gradient each take
    tiles of their own (upstream's ``ops.gmm`` hands all three one)."""
    # the submodule by its full name: the package exports the function
    # ``gmm`` (upstream's custom_vjp) over the module's
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    (R, K), N = lhs.shape, rhs.shape[2]
    return gmm(
        lhs, rhs, group_sizes, lhs.dtype,
        _tiles_of("fwd", R, K, N, rows_a_group, tiling), interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, rows_a_group, tiling, interpret):
    return (_gmm(lhs, rhs, group_sizes, rows_a_group, tiling, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(rows_a_group, tiling, interpret, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    lhs, rhs, group_sizes = res
    (R, K), (G, _, N) = lhs.shape, rhs.shape
    d_lhs = gmm(
        g, rhs, group_sizes, lhs.dtype,
        _tiles_of("dlhs", R, N, K, rows_a_group, tiling),
        transpose_rhs=True, interpret=interpret)
    d_rhs = tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
        _tiles_of("tgmm", R, K, N, rows_a_group, tiling),
        num_actual_groups=G, interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, impl: Optional[str] = None,
                   rows_a_group: Optional[float] = None, tiling=None):
    """``lhs[rows of group g] @ rhs[g]``: lhs [R, K] sorted by group, rhs
    [G, K, N], group_sizes [G] int32 -> [R, N].  Rows past the last group
    are unspecified, forward and backward (the Pallas kernels do not visit
    them, so their time follows the rows in use and not R); callers mask
    them.

    ``impl``: "gmm" is upstream's Pallas grouped matmul (megablox; ``gmm``
    and ``tgmm`` in a device trace), "gmm_interpret" the same interpreted,
    "ragged_dot" is ``lax.ragged_dot``; None takes "gmm" on a TPU.

    The Pallas path is upstream's two kernels under this module's own
    ``custom_vjp``: ``gmm`` for the product, ``gmm`` on the transposed
    weights for the rows' gradient, ``tgmm`` for the weights' gradient.  A
    kernel VISITS, for every group, every tile of ``tm`` rows the group
    touches, and pays a whole tile's products a visit; so each of the three
    takes the tiles ``_gmm_tiles`` picks for its shapes and for
    ``rows_a_group``, the rows a group is expected to hold (static; None:
    R / G, a full buffer), bf16 operands and float32 accumulation whatever
    the tiles.  An explicit ``tiling`` (tm, tk, tn) wins, for all three as
    upstream's does.  No tile set asks for more than Mosaic's default
    scoped VMEM (``_VMEM_BUDGET``)."""
    if impl is None:
        impl = "gmm" if jax.default_backend() == "tpu" else "ragged_dot"
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=lhs.dtype)
    if rows_a_group is None:
        rows_a_group = lhs.shape[0] / rhs.shape[0]
    return _gmm(lhs, rhs, group_sizes, float(rows_a_group),
                None if tiling is None else tuple(tiling),
                impl == "gmm_interpret")


def _take(v, at):
    """``v[at]`` for a 1-D ``v``, moved as rows of 128: the row that holds
    the place is gathered and the place picked out of it by a compare.  On
    the chip a scalar gather costs 8.6 ns a place, a row of 128 costs 1.9
    (PERF.md, PR 30)."""
    blocks = -(-v.shape[0] // 128)
    rows = jnp.pad(v, (0, blocks * 128 - v.shape[0])).reshape(blocks, 128)
    here = jnp.arange(128, dtype=at.dtype) == (at % 128)[..., None]
    return jnp.sum(jnp.where(here, rows[at // 128], 0), axis=-1)


def _first_set(running, size):
    """Places of the first ``size`` set entries of a 0/1 array, from its
    inclusive running sum ``running`` [L]; L where there are fewer.  The
    inverse of a running sum without a scatter or a sort: the r-th set entry
    lies in the first block of 128 whose end passes r (a compare against
    every block's end), and in it at the first entry that passes r (one
    gathered block a place, a compare and a sum)."""
    L = running.shape[0]
    blocks = -(-L // 128)
    by_block = jnp.pad(running, (0, blocks * 128 - L), mode="edge"
                       ).reshape(blocks, 128)
    r = jnp.arange(size, dtype=jnp.int32)[:, None]
    block = jnp.sum(by_block[:, -1][None, :] <= r, axis=1, dtype=jnp.int32)
    block = jnp.minimum(block, blocks - 1)
    inside = jnp.sum(by_block[block] <= r, axis=1, dtype=jnp.int32)
    return jnp.minimum(block * 128 + inside, L)


#: tokens between two entries of ``_Places.starts``: the smallest tile of
#: tokens of ``_sum_rows_kernel``
_GRANULE = 64

#: copies of runs in flight in ``_sum_rows_kernel``
_RUNS_IN_FLIGHT = 8

#: rows a copy starts on a multiple of: a bf16 tile's
_RUN_ALIGN = 16

#: the tests' way in: the kernel path off the chip, interpreted
_INTERPRET_ROWS = False


class _Places(NamedTuple):
    """Where the rows of one call's buffer come from and go back to: the
    indices of one counting sort, computed once a call (``_places``) and
    shared by dispatch and combine, forward and backward.  ``tok`` and
    ``live`` serve the gather into the buffer, ``row`` the ``jnp`` sum out
    of it and the weights' gradient, ``starts`` and ``tok`` the kernel's
    (``_sum_rows_kernel``: the runs it copies, and each row's token),
    ``slot`` the rows' weights."""
    sizes: jax.Array    # [Xh] rows of each held expert
    live: jax.Array     # [R, 1] bool: a group holds the row
    tok: jax.Array      # [R] the row's token
    slot: jax.Array     # [R] the row's assignment, t * k + j
    row: jax.Array      # [T, k] the assignment's row; R where it has none
    starts: jax.Array   # [Xh, T / _GRANULE + 1] first row of each held
    #                     expert for the tokens from g * _GRANULE on (a
    #                     group's rows are in token order); its last, the
    #                     group's end; none past R


def _places(local, Xh, rows):
    """``local`` [T, k]: each assignment's expert among the Xh held, Xh where
    it is not held; a token names an expert at most once.  -> (_Places, rows
    in use).

    A counting sort by (expert, token) without a scatter.  A (token, expert)
    cell is set where the token goes to the expert; the running sum over the
    cells read expert by expert gives each set cell, and so each held
    assignment, its row.  The other direction, each row's cell, is that
    sum's inverse (``_first_set``).  Assignments past ``rows`` get no row."""
    T, k = local.shape
    chosen = local[:, :, None] == jnp.arange(Xh, dtype=local.dtype)
    cells = jnp.any(chosen, axis=1)                          # [T, Xh]
    running = jnp.cumsum(cells.T.reshape(Xh * T).astype(jnp.int32))
    # Rows past ``used`` belong to no group: the grouped products leave
    # them unwritten, forward and backward, so nothing may read them.
    used = jnp.minimum(running[-1], rows)
    live = (jnp.arange(rows) < used)[:, None]
    place = running.reshape(Xh, T).T - 1                     # [T, Xh]
    row = jnp.sum(jnp.where(chosen, place[:, None, :], 0), axis=2)
    row = jnp.where((local < Xh) & (row < rows), row, rows)
    cell = jnp.minimum(_first_set(running, rows), Xh * T - 1)    # e * T + t
    tok, expert = cell % T, cell // T
    which = jnp.sum(jnp.where(chosen, jnp.arange(k)[None, :, None], 0),
                    axis=1)                                  # [T, Xh]: j
    slot = tok * k + _take(which.reshape(T * Xh), tok * Xh + expert)
    ends = running.reshape(Xh, T)
    starts = jnp.concatenate([(ends - cells.T)[:, ::_GRANULE], ends[:, -1:]],
                             axis=1)
    return _Places(jnp.sum(cells, axis=0, dtype=jnp.int32), live, tok, slot,
                   row, jnp.minimum(starts, rows)), used


def _gather_rows(x, at: _Places):
    return jnp.where(at.live, x[at.tok], 0)


def _sum_rows_xla(y, w, at: _Places):
    """``_sum_rows`` in ``jnp``: one gather over all [T, k] slots, a select
    and a float32 sum over k.  What runs off the chip, and what the kernel
    is tested against."""
    R = y.shape[0]
    z = y[jnp.minimum(at.row, R - 1)].astype(jnp.float32)    # [T, k, E]
    if w is not None:
        z = z * w[..., None]
    return jnp.sum(jnp.where((at.row < R)[..., None], z, 0),
                   axis=1).astype(y.dtype)


def _run_rows(R: int, Xh: int, tiles: int) -> int:
    """Rows one copy of ``_sum_rows_kernel`` fetches: the run of a tile and
    an expert at a FULL buffer (twice the usual load) and ``_RUN_ALIGN``
    for where it starts, in whole tiles of rows; a longer run takes a
    second copy."""
    run = R // (Xh * tiles) + _RUN_ALIGN
    return min(R, -(-run // _RUN_ALIGN) * _RUN_ALIGN)


def _rows_tile(T: int, R: int, E: int, Xh: int, dtype) -> Optional[int]:
    """Tokens of a grid step of ``_sum_rows_kernel``, from what the call can
    see; None where it goes down the ``jnp`` form: off the chip (but for
    ``_INTERPRET_ROWS``), lanes not in whole tiles, a dtype the kernel was
    not written for, a token count ``_GRANULE`` does not divide.  The
    largest tile whose blocks stay inside ``_VMEM_BUDGET``: the copies in
    flight, a run in float32, the tile's float32 sum and the two buffers of
    its result."""
    from .attention import LANES, _on_tpu   # at the call: tests steer it
    if not ((_on_tpu() or _INTERPRET_ROWS) and E % LANES == 0
            and R % _RUN_ALIGN == 0
            and dtype in (jnp.bfloat16, jnp.float32)):
        return None
    size = jnp.dtype(dtype).itemsize
    for tm in (512, 256, 128, _GRANULE):
        if T % tm:
            continue
        run = _run_rows(R, Xh, T // tm)
        if (_RUNS_IN_FLIGHT * run * size + 4 * run + 4 * tm
                + 2 * tm * size) * E <= _VMEM_BUDGET:
            return tm
    return None


def _sum_rows_body(starts, tok, w, y, out, stage, wide, acc, sems, *, Xh,
                   tm, run, R, weighted):
    """One tile of ``tm`` tokens.  A group's rows are in token order, so the
    tile's rows of one held expert are one contiguous run of the buffer,
    from one entry of ``starts`` to a later one: the runs are copied out of
    HBM, up to
    ``_RUNS_IN_FLIGHT`` at once, ``run`` rows a copy from a multiple of
    ``_RUN_ALIGN`` at or under the run's first row, and each row of a run
    is added, times its weight, to its token's row of the tile's float32
    sum.  Only rows INSIDE a run are added (the copies bring neighbours
    along, and rows no group holds: never read past the conversion)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    step = tm // _GRANULE
    entries = pl.num_programs(0) * step + 1     # of ``starts``, an expert
    acc[...] = jnp.zeros_like(acc)

    def run_of(e):
        a = starts[e * entries + i * step]
        b = starts[e * entries + (i + 1) * step]
        base = a // _RUN_ALIGN * _RUN_ALIGN
        return a, b, base, jnp.where(b > a, (b - base + run - 1) // run, 0)

    total = Xh * jax.lax.fori_loop(
        0, Xh, lambda e, most: jnp.maximum(most, run_of(e)[3]), 0)

    def item(q):
        """Copy ``q`` of the tile: round q // Xh of expert q % Xh."""
        a, b, base, copies = run_of(q % Xh)
        nth = q // Xh
        first = base + nth * run
        start = pl.multiple_of(jnp.minimum(first, R - run), _RUN_ALIGN)
        return (nth < copies, start, jnp.maximum(a, first),
                jnp.minimum(b, first + run))

    def copy(q, start):
        slot = q % _RUNS_IN_FLIGHT
        return pltpu.make_async_copy(y.at[pl.ds(start, run)], stage.at[slot],
                                     sems.at[slot])

    def issue(q):
        there, start, _, _ = item(q)

        @pl.when(jnp.logical_and(q < total, there))
        def _():
            copy(q, start).start()

    def ahead(q, carry):
        issue(q)
        return carry

    jax.lax.fori_loop(0, _RUNS_IN_FLIGHT, ahead, 0)

    def visit(q, carry):
        there, start, lo, hi = item(q)

        @pl.when(there)
        def _():
            copy(q, start).wait()
            slot = q % _RUNS_IN_FLIGHT

            def widen(g, c):
                at = pl.multiple_of(g * _RUN_ALIGN, _RUN_ALIGN)
                wide[pl.ds(at, _RUN_ALIGN), :] = stage[
                    slot, pl.ds(at, _RUN_ALIGN), :].astype(jnp.float32)
                return c

            jax.lax.fori_loop((lo - start) // _RUN_ALIGN,
                              (hi - start + _RUN_ALIGN - 1) // _RUN_ALIGN,
                              widen, 0)

            def add(r, c):
                t = tok[r] - i * tm
                v = wide[pl.ds(r - start, 1), :]
                if weighted:
                    v = v * w[r]
                acc[pl.ds(t, 1), :] = acc[pl.ds(t, 1), :] + v
                return c

            jax.lax.fori_loop(lo, hi, add, 0)

        issue(q + _RUNS_IN_FLIGHT)
        return carry

    jax.lax.fori_loop(0, total, visit, 0)
    out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _sum_rows_kernel(y, w_rows, starts, tok, *, tm: int, interpret: bool):
    """``_sum_rows`` as a Pallas kernel over tiles of ``tm`` tokens: the
    buffer stays in HBM and only the runs of rows the tile's tokens hold
    are copied (``_sum_rows_body``); ``w_rows`` [R] float32 the rows'
    weights, None for 1; ``starts`` and ``tok`` are ``_Places``'.  Rows,
    weights and run starts ride as scalars.  A jit of its own, so that the
    layers of a step that call it at one shape trace and lower it once
    (0.5 s a time on the host: 20 of them made a cell's cached step 15 s
    slower to fetch; PERF.md, PR 45)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (R, E), Xh = y.shape, starts.shape[0]
    tiles = (starts.shape[1] - 1) * _GRANULE // tm
    run = _run_rows(R, Xh, tiles)
    weighted = w_rows is not None
    body = functools.partial(_sum_rows_body, Xh=Xh, tm=tm, run=run, R=R,
                             weighted=weighted)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, E), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((_RUNS_IN_FLIGHT, run, E), y.dtype),
                pltpu.VMEM((run, E), jnp.float32),
                pltpu.VMEM((tm, E), jnp.float32),
                pltpu.SemaphoreType.DMA((_RUNS_IN_FLIGHT,))]),
        out_shape=jax.ShapeDtypeStruct((tiles * tm, E), y.dtype),
        interpret=interpret,
        name="rows_sum_weighted" if weighted else "rows_sum",
    )(starts.reshape(-1), tok,
      w_rows if weighted else jnp.zeros((1,), jnp.float32), y)


def _sum_rows(y, w, at: _Places, op: str):
    """[T, E]: ``sum_j w[t, j] * y[at.row[t, j]]`` over the assignments that
    have a row (``w`` None: 1), in float32, rounded once; rows no group
    holds are never added.  On a TPU a kernel that copies the rows in use
    (``_sum_rows_kernel``), elsewhere ``_sum_rows_xla``; ``op`` names the
    mover for the counter."""
    T, k = at.row.shape
    tm = _rows_tile(T, *y.shape, at.sizes.shape[0], y.dtype)
    telemetry.inc("ray_tpu_moe_rows_path_total", tags={
        "path": "kernel" if tm else "xla", "op": op, "tokens": str(T),
        "slots": str(k), "lanes": str(y.shape[1])})
    if tm is None:
        return _sum_rows_xla(y, w, at)
    w_rows = None if w is None else _take(w.reshape(-1), at.slot)
    return _sum_rows_kernel(y, w_rows, at.starts, at.tok, tm=tm,
                            interpret=_INTERPRET_ROWS)


@jax.custom_vjp
def rows_of_tokens(x, at: _Places):
    """x [T, E] -> [R, E]: row r is ``x[at.tok[r]]``, 0 where no group holds
    the row.  Its transpose is ``tokens_from_rows`` with weights of 1."""
    with jax.named_scope("dispatch"):
        return _gather_rows(x, at)


def _rows_fwd(x, at):
    return rows_of_tokens(x, at), at


def _rows_bwd(at, g):
    with jax.named_scope("dispatch"):
        return _sum_rows(g, None, at, "rows_of_tokens_bwd"), None


rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def tokens_from_rows(y, w, at: _Places):
    """y [R, E], w [T, k] float32 -> [T, E]: each token's rows, each times
    its assignment's weight, summed.  Rows no group holds are never read (a
    select, not a product: they may hold anything).  Its transpose is
    ``rows_of_tokens`` times the rows' weights, and a row-dot for ``w``."""
    with jax.named_scope("combine"):
        return _sum_rows(y, w, at, "tokens_from_rows")


def _tokens_fwd(y, w, at):
    return tokens_from_rows(y, w, at), (y, w, at)


def _tokens_bwd(res, g):
    y, w, at = res
    with jax.named_scope("combine"):
        g_rows = _gather_rows(g, at).astype(jnp.float32)     # 0 if not live
        d_rows = jnp.sum(jnp.where(at.live, y, 0).astype(jnp.float32)
                         * g_rows, axis=1)
        d_w = _take(jnp.pad(d_rows, (0, 1)), at.row)
        d_y = g_rows * _take(w.reshape(-1), at.slot)[:, None]
        return d_y.astype(y.dtype), d_w, None


tokens_from_rows.defvjp(_tokens_fwd, _tokens_bwd)


#: an expert's activation, by the name a model's configuration gives it;
#: ``poly_norm`` has weights of its own (``activation_of``)
ACTIVATIONS = {"silu": jax.nn.silu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x)),
               "poly_norm": poly_norm}


def activation_of(name: str, weights=None):
    """The activation ``name`` as a function of the rows alone; one with
    weights of its own closes over them (``weights``: its keyword
    arguments, arrays and numbers: ``poly_norm``'s ``p``, ``scale``,
    ``clamp``, ``eps``), and autodiff hands them their gradient."""
    act = ACTIVATIONS[name]
    return act if weights is None else functools.partial(act, **weights)


def _held_rows(xt, top, w, w_gate, w_up, w_down, held_start, rows, impl,
               activation="silu", experts=None, act_weights=None):
    """The held experts' part for tokens xt [T, E] in a buffer of ``rows``
    rows: (out [T, E], rows in use).  Right whenever the assignments to held
    experts number at most ``rows``.  ``w_gate`` None: an un-gated expert.
    ``experts``: all X the router chose among, so that a held one expects
    T * k / X rows (None: the buffer's rows over the held).  An activation
    with weights (``act_weights``) sums its weights' gradient over the
    buffer's rows, and the rows no group holds are unspecified on both
    sides of a grouped product: they are zeroed before it and after it, so
    that neither they nor their cotangent reach that sum."""
    Xh, act = w_up.shape[0], activation_of(activation, act_weights)
    expected = top.size / experts if experts else None
    with jax.named_scope("dispatch"):
        local = top - held_start
        local = jnp.where((local >= 0) & (local < Xh), local, Xh)
        at, used = _places(local, Xh, rows)
    x_rows = rows_of_tokens(xt, at)
    with jax.named_scope("experts"):
        mm = functools.partial(grouped_matmul, group_sizes=at.sizes,
                               impl=impl, rows_a_group=expected)
        held = (lambda a: a) if act_weights is None else (
            lambda a: jnp.where(at.live, a, 0))
        if w_gate is None:
            h = held(act(held(mm(x_rows, w_up))))
        else:
            h = held(act(held(mm(x_rows, w_gate))) * held(mm(x_rows, w_up)))
        y_rows = mm(h, w_down)
    return tokens_from_rows(y_rows, w.astype(jnp.float32), at), used


def buffer_tiers(T: int, held: Optional[int] = None,
                 routed: Optional[int] = None) -> int:
    """The tiers a call of T tokens divides its worst case by, where
    ``held`` of the router's ``routed`` experts are held: the largest power
    of two that leaves the buffer TWICE the expected load, ``2 ** floor(log2(
    routed / (2 * held)))``, never under ``BUFFER_TIERS`` (the share unknown,
    or an eighth and over), and only while a slice of T / tiers tokens is
    whole tiles of the sum's kernel (``_GRANULE``).  1 where the tokens do
    not split into ``BUFFER_TIERS``: the buffer is the worst case's."""
    if T % BUFFER_TIERS:
        return 1
    tiers = BUFFER_TIERS
    if held and routed:
        while (4 * tiers * held <= routed
               and T % (2 * tiers * _GRANULE) == 0):
            tiers *= 2
    return tiers


def buffer_rows(T: int, k: int, held: Optional[int] = None,
                routed: Optional[int] = None) -> int:
    """Rows of the buffer a call of T tokens with k assignments each goes
    through, ``held`` of ``routed`` experts held: T * k over
    ``buffer_tiers``.  A call whose held assignments pass them takes the
    buffer in that many slices (``dropless_experts``)."""
    return T * k // buffer_tiers(T, held, routed)


def dropless_experts(xt, routing: SigmoidRouting, w_gate, w_up, w_down,
                     held_start: int = 0, impl: Optional[str] = None,
                     activation: str = "silu", act_weights=None):
    """``sum_j w[t, j] * Expert_{top[t, j]}(xt[t])`` over the assignments
    to the experts held here, ``held_start <= e < held_start + Xh``; every
    such assignment is computed, whatever the imbalance.

    xt [T, E]; w_gate / w_up [Xh, E, M], w_down [Xh, M, E].  An expert is
    ``act(x W_gate) * (x W_up)``, then ``W_down``, with ``act`` the
    ``activation`` named (``silu``, ``relu2``; ``poly_norm`` with its
    weights in ``act_weights``, one set for all the held experts, which get
    their gradient); with ``w_gate`` None it has
    no gate, ``act(x W_up) W_down``: two grouped products a row for three.
    Returns (out [T, E], stats) with ``stats = (held, dropped)``: the
    assignments to held experts and those of them not computed (identically
    0).

    Buffers are static.  All T*k assignments may go to held experts, so the
    worst case needs T*k rows; a share of Xh / X is the usual case, T*k *
    Xh / X rows.  The tokens therefore go through a buffer of R = T*k /
    tiers rows at once when the held assignments fit it, and otherwise in
    ``tiers`` slices of the tokens, one after the other through the same
    buffer (a slice of T / tiers tokens has at most that many assignments).
    ``tiers`` follows the share the call can see (``buffer_tiers``: Xh of
    ``routing.counts``' X): the largest power of two that leaves R twice
    the expected load, never under BUFFER_TIERS.  An eighth held (Trinity,
    Xing4.0, Nemotron, Kanana, LFM2) is 4 tiers, 16,384 rows for 8,192
    tokens at k 8; a thirty-second or a forty-eighth (MiMo-V2-Flash,
    Ling-3.0-flash, Motif-3-beta) 16 tiers and 4,096 rows, where four
    tiers were 8 and 12 times the load.  What that cost, a layer call's
    recomputed forward and backward alone on a TPU v5e at R 16,384 and at
    4,096 (PERF.md, PR 63, step 0): MiMo's shape (E 4,096, M 2,048, 8 of
    256) 8.66 -> 4.86 ms, Ling's (E 2,560, M 768, 16 of 512) 3.68 -> 2.06,
    Motif's (E 4,096, M 1,280, 8 of 384, PolyNorm) 7.36 -> 3.89; the
    grouped products 4.25 -> 4.08, 1.56 -> 1.40 and 3.07 -> 2.92 of them.

    Rows move without a scatter: into the buffer each row reads its token
    (``rows_of_tokens``), out of it each token sums the rows it holds, each
    times its weight, in float32 (``tokens_from_rows``; rows no group holds
    are never read), and the backward of each is the other.  What each
    costs on a TPU v5e at the sparse cells' shapes (one row of 8,192
    tokens, an eighth of the slots held; PERF.md, PR 45): the gather into
    the buffer its whole size (R rows read and written, 0.10 ms, and the
    select that zeroes the rows no group holds 0.09-0.20 ms more), as do
    the activation and the backward's row passes (``_tokens_bwd``'s
    float32 [R, E] gather, select and row-dot, the sum of the two
    products' cotangents, PolyNorm's ``held`` selects), R being twice the
    expected load at any share; the sum out of it the
    rows in use (the kernel: 0.27-0.31 ms, a third of it a tile's fixed
    costs, the rest two cycles a row's vector register: each row is added
    to its token's row of the sum on its own); the grouped products the
    rows in use; ``_places`` about 0.2 ms, twice a call (the recomputed
    forward makes it again)."""
    T, k = routing.expert_index.shape
    Xh, X = w_up.shape[0], routing.counts.shape[0]
    top, w = routing.expert_index, routing.weights
    held = jnp.sum(routing.counts[held_start:held_start + Xh])
    tiers = buffer_tiers(T, Xh, X)
    rows = T * k // tiers
    telemetry.inc("ray_tpu_moe_buffer_total", tags={
        "rows": str(rows), "tiers": str(tiers), "held": str(Xh),
        "routed": str(X), "tokens": str(T), "slots": str(k)})
    run = functools.partial(_held_rows, w_gate=w_gate, w_up=w_up,
                            w_down=w_down, held_start=held_start, rows=rows,
                            impl=impl, activation=activation, experts=X,
                            act_weights=act_weights)
    if tiers == 1:
        out, used = run(xt, top, w)
        return out, (held, held - used)

    def at_once():
        return run(xt, top, w)

    def in_slices():
        split = lambda a: a.reshape((tiers, T // tiers) + a.shape[1:])
        # Recomputed in the backward pass, or the gradient would keep every
        # slice's buffers (and, through the cond, keep room for them on the
        # usual path too).
        out, used = jax.lax.map(jax.checkpoint(lambda s: run(*s)),
                                (split(xt), split(top), split(w)))
        return out.reshape(xt.shape), jnp.sum(used)

    out, used = jax.lax.cond(held <= rows, at_once, in_slices)
    return out, (held, held - used)
