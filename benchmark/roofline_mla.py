"""What a flash-attention call must do when its values are not as wide as its
keys (latent attention: scores over ``d_qk`` = 192, values of ``d_v`` = 128),
from shapes alone: operations and bytes for ``roofline.least_seconds``.  The
peaks stay in ``roofline.py``."""

from __future__ import annotations

from typing import Tuple

from benchmark.roofline_moe import visible_pairs


def flash_call(which: str, batch: int, heads: int, kv_heads: int, seq: int,
               d_qk: int, d_v: int, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one causal flash-attention kernel call over
    the visible triangle.  ``fwd`` forms S = Q K^T over d_qk and P V over
    d_v; ``dq`` re-forms S, forms dP = dO V^T over d_v and dQ = dS K over
    d_qk; ``dkv`` re-forms S and dP and forms dV = P^T dO over d_v and dK =
    dS^T Q over d_qk.  Bytes: each operand read once and each result written
    once at its own width, never padded."""
    over = {"fwd": d_qk + d_v, "dq": 2 * d_qk + d_v,
            "dkv": 2 * d_qk + 2 * d_v}[which]
    ops = 2.0 * batch * heads * visible_pairs(seq) * over
    q, o = (batch * heads * seq * d * itemsize for d in (d_qk, d_v))
    k, v = (batch * kv_heads * seq * d * itemsize for d in (d_qk, d_v))
    lse = batch * heads * seq * 4
    moved = {"fwd": q + k + v + o + lse,               # q,k,v -> o,lse
             "dq": q + k + v + o + 2 * lse + q,        # ..,do,lse,di -> dq
             "dkv": q + k + v + o + 2 * lse + k + v}[which]
    return ops, float(moved)
