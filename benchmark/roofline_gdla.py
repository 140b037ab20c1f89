"""What a flash-attention call must do when its values are narrower than its
keys, its key heads fewer than its query heads and its mask a band (grouped
differential attention on latent keys: scores over ``d_qk`` = 192, values of
``d_v`` = 128, 80 query heads over 16 key heads, a window of 128 tokens on
three layers of four), from shapes alone: operations and bytes for
``roofline.least_seconds``.  The peaks stay in ``roofline.py``."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark.roofline_moe import visible_pairs


def flash_call(which: str, batch: int, heads: int, kv_heads: int, seq: int,
               d_qk: int, d_v: int, window: Optional[int] = None,
               itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one causal flash-attention kernel call over
    the useful band only: the pairs with ``0 <= t - s`` (``< window``), for
    every one of the ``heads`` query heads (the noise heads' too: their
    result is subtracted, not skipped).  ``fwd`` forms S = Q K^T over d_qk
    and P V over d_v; ``dq`` re-forms S, forms dP = dO V^T over d_v and dQ =
    dS K over d_qk; ``dkv`` re-forms S and dP and forms dV = P^T dO over
    d_v and dK = dS^T Q over d_qk; ``bwd`` (the one pass in the place of
    the last two) forms S and dP once and all three gradients.  Bytes: each
    operand read once and each result written once at its own width, keys
    and values (and their gradients) once a KEY head: a group's query heads
    share them."""
    over = {"fwd": d_qk + d_v, "dq": 2 * d_qk + d_v,
            "dkv": 2 * d_qk + 2 * d_v, "bwd": 3 * d_qk + 2 * d_v}[which]
    ops = 2.0 * batch * heads * visible_pairs(seq, window) * over
    q, o = (batch * heads * seq * d * itemsize for d in (d_qk, d_v))
    k, v = (batch * kv_heads * seq * d * itemsize for d in (d_qk, d_v))
    lse = batch * heads * seq * 4
    moved = {"fwd": q + k + v + o + lse,               # q,k,v -> o,lse
             "dq": q + k + v + o + 2 * lse + q,        # ..,do,lse,di -> dq
             "dkv": q + k + v + o + 2 * lse + k + v,
             "bwd": q + k + v + o + 2 * lse + q + k + v}[which]
    return ops, float(moved)
