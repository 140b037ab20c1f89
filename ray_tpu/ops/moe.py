"""Mixture-of-experts: routing and dispatch, two families.

Absent from the reference (SURVEY §2.4 EP row: delegated to vLLM) — built
natively.

* Softmax top-k with a capacity (``moe_layer``, what ``LlamaConfig`` with
  ``num_experts > 0`` runs).  The expert dimension carries the ``expert``
  logical axis, so under the ``ep`` mesh axis GSPMD partitions the expert
  einsums and inserts the token exchange implied by the dispatch.  The
  default dispatch is capacity-based and SORTED (argsort assignments by
  expert + segment offsets -> O(T*k) index arrays) rather than the GShard
  one-hot ``[T, X, C]`` tensor, and DROPS what passes the capacity; dense
  (masked) dispatch remains available via ``capacity_factor=0`` for
  exactness tests.
* Sigmoid scores with a selection bias, nothing dropped, and a layer that is
  told which experts it holds (``sigmoid_routing``, ``dropless_experts``,
  ``update_selection_bias``; what ``models/afmoe.py`` runs).  The router
  scores all ``X`` experts and picks ``k`` of them (8 of 128 there); the
  layer computes the part of the result that its own ``Xh`` experts give,
  as one chip of an expert-parallel group does, without the exchange.  The
  assignments to held experts are sorted by expert into a buffer of static
  size and multiplied by grouped matrix products over the ragged groups.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class RoutingInfo(NamedTuple):
    combine_weights: jax.Array  # [B, S, X] softmax weights, zero off top-k
    router_probs: jax.Array     # [B, S, X] full softmax (for aux loss)
    expert_index: jax.Array     # [B, S, k]


def top_k_routing(x, router_w, k: int = 2,
                  router_noise: float = 0.0,
                  rng: Optional[jax.Array] = None) -> RoutingInfo:
    """x: [B, S, E]; router_w: [E, X] -> routing info."""
    logits = jnp.einsum("bse,ex->bsx", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    if router_noise > 0.0 and rng is not None:
        logits = logits + router_noise * jax.random.normal(
            rng, logits.shape, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    # Renormalize the selected experts' weights to sum to one.
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    combine = jnp.zeros_like(probs)
    combine = jnp.put_along_axis(
        combine, topi, topv, axis=-1, inplace=False) \
        if hasattr(jnp, "put_along_axis") else _scatter(combine, topi, topv)
    return RoutingInfo(combine, probs, topi)


def _scatter(zeros, idx, vals):
    one_hot = jax.nn.one_hot(idx, zeros.shape[-1], dtype=vals.dtype)
    return jnp.einsum("bskx,bsk->bsx", one_hot, vals)


def load_balancing_loss(info: RoutingInfo, num_experts: int) -> jax.Array:
    """Switch-transformer style aux loss."""
    me = jnp.mean(info.router_probs, axis=(0, 1))            # [X]
    ce = jnp.mean((info.combine_weights > 0).astype(jnp.float32), axis=(0, 1))
    return num_experts * jnp.sum(me * ce)


def capacity_dispatch(info: RoutingInfo, num_experts: int,
                      capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Build GShard-style dispatch/combine tensors with capacity dropping.

    Tokens are assigned slots within each expert in token order via a
    cumulative count; assignments beyond ``capacity`` are dropped (their
    contribution to the output is zero — the residual stream carries them).

    Returns (dispatch [T, X, C] one-hot float, combine [T, X, C]) over
    flattened tokens T = B*S.
    """
    B, S, X = info.combine_weights.shape
    k = info.expert_index.shape[-1]
    idx = info.expert_index.reshape(B * S, k)
    weights = info.combine_weights.reshape(B * S, X)

    counts = jnp.zeros((X,), jnp.int32)
    dispatch = jnp.zeros((B * S, X, capacity), jnp.float32)
    combine = jnp.zeros((B * S, X, capacity), jnp.float32)
    # Traced inside callers' jitted MoE layers; k is this path's top-k
    # constant (1-2 for the softmax router; the sigmoid router's 8 go
    # through dropless_experts), so the unrolled loop is two fused
    # segments, not dispatch.
    for j in range(k):  # ray-tpu: noqa[RT506]
        oh = jax.nn.one_hot(idx[:, j], X, dtype=jnp.int32)     # [T, X]
        pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]     # [T, X]
        keep = (pos < capacity) & (oh > 0)
        counts = counts + jnp.sum(oh * keep, axis=0)
        slot = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity,
                              dtype=jnp.float32)               # [T, X, C]
        d_j = slot * keep[..., None].astype(jnp.float32)
        dispatch = dispatch + d_j
        w_j = jnp.take_along_axis(weights, idx[:, j:j + 1], axis=-1)
        combine = combine + d_j * w_j[..., None]
    return dispatch, combine


def sorted_dispatch(info: RoutingInfo, num_experts: int, capacity: int):
    """Sort-based token routing: assignments ordered by expert, with
    per-expert segment offsets giving each token its slot.

    Replaces the one-hot ``[T, X, C]`` dispatch tensor (O(T*X*C) memory
    and FLOPs) with O(T*k) index arrays: argsort assignments by expert,
    slot = position - expert segment start, drop slots >= capacity.

    Returns (tok_s [N], e_s [N], slot_s [N], w_s [N], keep [N]) over
    N = T*k assignments in expert-sorted order; ``slot_s`` equals
    ``capacity`` (out of range -> scatter mode 'drop') for dropped
    assignments.
    """
    B, S, X = info.combine_weights.shape
    k = info.expert_index.shape[-1]
    T = B * S
    N = T * k
    e_flat = info.expert_index.reshape(N)
    tok_flat = jnp.arange(N, dtype=jnp.int32) // k
    weights = info.combine_weights.reshape(T, X)
    w_flat = jnp.take_along_axis(
        weights, info.expert_index.reshape(T, k), axis=-1).reshape(N)
    order = jnp.argsort(e_flat, stable=True)  # token order within expert
    e_s = e_flat[order]
    tok_s = tok_flat[order]
    w_s = w_flat[order]
    counts = jnp.bincount(e_flat, length=num_experts)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    slot_s = jnp.arange(N, dtype=counts.dtype) - starts[e_s]
    keep = slot_s < capacity
    slot_s = jnp.where(keep, slot_s, capacity)  # OOB -> dropped by scatter
    return tok_s, e_s, slot_s, w_s, keep


def moe_layer(x, router_w, w_gate, w_up, w_down, k: int = 2,
              rng: Optional[jax.Array] = None,
              router_noise: float = 0.0,
              capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """SwiGLU expert MLPs with top-k routing.

    x: [B, S, E]; router_w: [E, X]; w_gate/w_up: [X, E, M]; w_down: [X, M, E].
    Returns (output [B, S, E], aux_loss scalar).

    The default is capacity-based sparse dispatch (sorted, see
    ``sorted_dispatch``): each expert processes at most
    ``ceil(k * T * capacity_factor / X)`` token slots, so expert FLOPs
    scale as top_k * capacity_factor / num_experts of dense; overflowing
    assignments are dropped (the residual stream carries them).  Under the
    ``ep`` mesh axis the per-expert buffers carry the ``expert`` logical
    axis, so GSPMD partitions the expert einsums and inserts the token
    exchange implied by the scatter/gather (GShard recipe with sorted
    instead of one-hot dispatch).

    ``capacity_factor == 0`` selects dense (masked) dispatch: every expert
    sees every token — exact, O(num_experts) FLOPs, useful for parity
    tests and tiny models.
    """
    import math

    X = router_w.shape[-1]
    info = top_k_routing(x, router_w, k=k, rng=rng,
                         router_noise=router_noise)
    if capacity_factor and capacity_factor > 0.0:
        B, S, E = x.shape
        T = B * S
        capacity = max(int(math.ceil(k * T * capacity_factor / X)), 1)
        tok_s, e_s, slot_s, w_s, keep = sorted_dispatch(info, X, capacity)
        xt = x.reshape(T, E)
        # Dispatch: gather token embeddings into per-expert slot buffers
        # (slot == capacity is out of bounds -> mode='drop').
        expert_in = jnp.zeros((X, capacity, E), x.dtype).at[
            e_s, slot_s].set(xt[tok_s], mode="drop")
        gate = jnp.einsum("xce,xem->xcm", expert_in, w_gate)
        up = jnp.einsum("xce,xem->xcm", expert_in, w_up)
        h = jax.nn.silu(gate) * up
        expert_out = jnp.einsum("xcm,xme->xce", h, w_down)
        # Combine: weighted gather back to tokens (dropped slots read the
        # zero row via clamped slot? no — 'fill' gathers zeros for OOB).
        per_asgn = expert_out.at[e_s, slot_s].get(
            mode="fill", fill_value=0)                       # [N, E]
        contrib = per_asgn * (w_s * keep)[:, None].astype(per_asgn.dtype)
        out = jnp.zeros((T, E), contrib.dtype).at[tok_s].add(contrib)
        out = out.reshape(B, S, E)
    else:
        # Dense dispatch: compute all experts, weight by combine matrix.
        # Under the ep axis, each device computes only its expert shard
        # ("x" dim) and GSPMD reduces the combine einsum across ep.
        gate = jnp.einsum("bse,xem->bsxm", x, w_gate)
        up = jnp.einsum("bse,xem->bsxm", x, w_up)
        h = jax.nn.silu(gate) * up
        expert_out = jnp.einsum("bsxm,xme->bsxe", h, w_down)
        out = jnp.einsum("bsxe,bsx->bse", expert_out,
                         info.combine_weights.astype(expert_out.dtype))
    return out.astype(x.dtype), load_balancing_loss(info, X)


# ------------------------------------------------- sigmoid router, dropless

class SigmoidRouting(NamedTuple):
    expert_index: jax.Array     # [T, k] int32, over all X experts
    weights: jax.Array          # [T, k] float32
    counts: jax.Array           # [X] int32: assignments each expert got


def sigmoid_routing(x, router_w, bias, k: int, route_scale: float = 1.0,
                    route_norm: bool = True) -> SigmoidRouting:
    """x [T, E], router_w [E, X], bias [X] float32 (the selection bias: state,
    not a parameter).  ``s = sigmoid(x W)`` in float32; the ``k`` experts are
    chosen by ``s + bias`` and weighted by ``s`` alone, normalised over the
    chosen (``route_norm``) and scaled.  torchtitan's MoE router with
    ``score_func="sigmoid"``."""
    s = jax.nn.sigmoid(jnp.einsum(
        "te,ex->tx", x.astype(jnp.float32), router_w.astype(jnp.float32)))
    _, top = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = jnp.take_along_axis(s, top, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    counts = jnp.zeros((s.shape[-1],), jnp.int32).at[top.reshape(-1)].add(1)
    return SigmoidRouting(top, w * route_scale, counts)


def update_selection_bias(bias, counts, rate: float = 1e-3):
    """The step's update of the selection bias from the assignments each
    expert got in it: ``d = rate * sign(mean(n) - n)``, ``b + d - mean(d)``
    (torchtitan's rule; no gradient reaches the bias).  Leading axes (layers)
    broadcast."""
    n = counts.astype(jnp.float32)
    d = rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)
    return bias + d - jnp.mean(d, axis=-1, keepdims=True)


#: (m, k, n) tiles of the grouped products on the chip (PERF.md, PR 29).
GMM_TILING = (512, 1024, 1024)

#: The dropless buffer holds 1 / BUFFER_TIERS of the worst case's rows.
BUFFER_TIERS = 4


def grouped_matmul(lhs, rhs, group_sizes, impl: Optional[str] = None):
    """``lhs[rows of group g] @ rhs[g]``: lhs [R, K] sorted by group, rhs
    [G, K, N], group_sizes [G] int32 -> [R, N].  Rows past the last group
    are unspecified (the Pallas kernel does not visit them, so its time
    follows the rows in use and not R); callers mask them.

    ``impl``: "gmm" is upstream's Pallas grouped matmul (megablox; ``gmm``
    and ``tgmm`` in a device trace), "gmm_interpret" the same interpreted,
    "ragged_dot" is ``lax.ragged_dot``; None takes "gmm" on a TPU."""
    if impl is None:
        impl = "gmm" if jax.default_backend() == "tpu" else "ragged_dot"
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    tiling = tuple(min(t, d) for t, d in zip(
        GMM_TILING, (lhs.shape[0], lhs.shape[1], rhs.shape[2])))
    return gmm(lhs, rhs, group_sizes, lhs.dtype, tiling,
               interpret=impl == "gmm_interpret")


def _sort_by_group(group, num_groups):
    """(stable argsort of ``group`` [N] with values below ``num_groups``, the
    groups' sizes).  A counting sort: a row's place is its group's start plus
    its rank inside the group, from one running sum over [N, groups].  An XLA
    sort of 262,144 keys takes the TPU compiler 12 s a call site; this takes
    1.5 s (described v5e, PR 29)."""
    n = group.shape[0]
    member = (group[:, None] == jnp.arange(num_groups)[None, :]
              ).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(member, axis=0), group[:, None],
                               axis=1)[:, 0] - 1
    sizes = jnp.sum(member, axis=0)
    place = (jnp.cumsum(sizes) - sizes)[group] + rank
    order = jnp.zeros((n,), jnp.int32).at[place].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    return order, sizes


def _held_rows(xt, top, w, w_gate, w_up, w_down, held_start, rows, impl):
    """The held experts' part for tokens xt [T, E] in a buffer of ``rows``
    rows: (out [T, E], rows in use).  Right whenever the assignments to held
    experts number at most ``rows``."""
    T, k = top.shape
    Xh = w_gate.shape[0]
    with jax.named_scope("dispatch"):
        local = top - held_start
        local = jnp.where((local >= 0) & (local < Xh), local, Xh)
        flat = local.reshape(T * k)
        order, sizes = _sort_by_group(flat, Xh + 1)
        # Held assignments first, grouped by expert, in token order.
        order, sizes = order[:rows], sizes[:Xh]
        used = jnp.minimum(jnp.sum(sizes), rows)
        # Rows past ``used`` belong to no group: the grouped products leave
        # them unwritten, forward and backward, so they are cut off on both
        # sides (here, or their garbage gradients would be added to tokens).
        live = (jnp.arange(rows) < used)[:, None]
        tok = order // k
        x_rows = jnp.where(live, xt[tok], 0)
    with jax.named_scope("experts"):
        mm = functools.partial(grouped_matmul, group_sizes=sizes, impl=impl)
        h = jax.nn.silu(mm(x_rows, w_gate)) * mm(x_rows, w_up)
        y_rows = mm(h, w_down)
    with jax.named_scope("combine"):
        w_rows = w.reshape(T * k)[order][:, None].astype(y_rows.dtype)
        y_rows = jnp.where(live, y_rows, 0) * w_rows
        out = jnp.zeros(xt.shape, y_rows.dtype).at[tok].add(y_rows)
    return out, used


def dropless_experts(xt, routing: SigmoidRouting, w_gate, w_up, w_down,
                     held_start: int = 0, impl: Optional[str] = None):
    """``sum_j w[t, j] * Expert_{top[t, j]}(xt[t])`` over the assignments
    to the experts held here, ``held_start <= e < held_start + Xh``; every
    such assignment is computed, whatever the imbalance.

    xt [T, E]; w_gate / w_up [Xh, E, M], w_down [Xh, M, E].  Returns (out
    [T, E], stats) with ``stats = (held, dropped)``: the assignments to held
    experts and those of them not computed (identically 0).

    Buffers are static.  All T*k assignments may go to held experts, so the
    worst case needs T*k rows; a share of Xh / X is the usual case.  The
    tokens therefore go through a buffer of T*k / BUFFER_TIERS rows at once
    when the held assignments fit it, and otherwise in BUFFER_TIERS slices
    of the tokens, one after the other through the same buffer (a slice of
    T / BUFFER_TIERS tokens has at most that many assignments).  The XLA passes
    over the buffer (gather, activation, scatter) cost its whole size, the
    grouped products only the rows in use."""
    T, k = routing.expert_index.shape
    Xh, tiers = w_gate.shape[0], BUFFER_TIERS
    top, w = routing.expert_index, routing.weights
    held = jnp.sum(routing.counts[held_start:held_start + Xh])
    run = functools.partial(_held_rows, w_gate=w_gate, w_up=w_up,
                            w_down=w_down, held_start=held_start, impl=impl)
    if T % tiers:
        out, used = run(xt, top, w, rows=T * k)
        return out, (held, held - used)
    rows = T * k // tiers

    def at_once():
        return run(xt, top, w, rows=rows)

    def in_slices():
        split = lambda a: a.reshape((tiers, T // tiers) + a.shape[1:])
        # Recomputed in the backward pass, or the gradient would keep every
        # slice's buffers (and, through the cond, keep room for them on the
        # usual path too).
        out, used = jax.lax.map(
            jax.checkpoint(lambda s: run(*s, rows=rows)),
            (split(xt), split(top), split(w)))
        return out.reshape(xt.shape), jnp.sum(used)

    out, used = jax.lax.cond(held <= rows, at_once, in_slices)
    return out, (held, held - used)
