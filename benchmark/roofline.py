"""Peaks of the chips the benchmark knows, and what each kernel must do.

The functions give the operations and bytes that the algorithm needs for
one call, from its shapes alone; a kernel's roofline share is the least
time the chip could take for them over the time the trace shows.
"""

from __future__ import annotations

import json
import re
from typing import Any, Callable, Dict, Optional, Tuple

from benchmark import common

#: device_kind -> peaks of ONE chip.  Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).  A kind that is not
#: here is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"add it to benchmark/roofline.py with its source")
    return PEAKS[device_kind]


def least_seconds(ops: float, nbytes: float, device_kind: str) -> float:
    p = peaks(device_kind)
    return max(ops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])


def visible_pairs(seq: int, window=None) -> float:
    """(t, s) pairs with ``0 <= t - s`` (and ``< window``) in a row of
    ``seq`` tokens: the band a causal attention has to touch."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def flash_call(which: str, batch: int, heads: int, kv_heads: int, seq: int,
               d_qk: int, d_v: Optional[int] = None,
               window: Optional[int] = None, itemsize: int = 2,
               pairs: Optional[float] = None) -> Tuple[float, float]:
    """(operations, bytes) of one causal flash-attention kernel call: THE
    count of every attention roofline (until PR 61 four functions, a cell
    family each).  Operations over the useful band only, the pairs with
    ``0 <= t - s`` (``< window``), for every one of the ``heads`` query
    heads, ``2 * pairs`` a product and channel: ``fwd`` forms S = Q K^T
    over ``d_qk`` and P V over ``d_v``; ``dq`` re-forms S, forms dP = dO
    V^T over d_v and dQ = dS K over d_qk; ``dkv`` re-forms S and dP and
    forms dV = P^T dO over d_v and dK = dS^T Q over d_qk; ``bwd`` (the one
    pass in the place of the last two, ``flash_bwd``) forms S and dP ONCE
    and all three gradients, five products a tile, ``3 d_qk + 2 d_v``.

    Bytes are what the ALGORITHM has to move through HBM: each operand read
    once and each result written once at its own width, never padded; keys
    and values, and their gradients, once a KEY head (a group's query heads
    share them).  ``bwd`` reads q, k, v, ``do`` (as wide as o), the
    log-sum-exp and delta and writes dq, dk, dv.  NOT counted, because the
    algorithm does not need it: under a group the one pass leaves its dk /
    dv as float32 shares a QUERY head, and a sum outside the kernel reads
    them again and writes the key head's (``ops/attention.py``, PR 60's
    paragraph); nor is that sum's time in a reader's denominator, since the
    fusion that makes it is named differently in every cell.  At the cells'
    shapes every call is bound by its operations, not its bytes, so the
    choice moves no reading.

    ``d_v`` defaults to ``d_qk``; ``pairs`` overrides the band's count
    (``flash_attention_call``)."""
    d_v = d_qk if d_v is None else d_v
    if pairs is None:
        pairs = visible_pairs(seq, window)
    over = {"fwd": d_qk + d_v, "dq": 2 * d_qk + d_v,
            "dkv": 2 * d_qk + 2 * d_v, "bwd": 3 * d_qk + 2 * d_v}[which]
    ops = 2.0 * batch * heads * pairs * over
    q, o = (batch * heads * seq * d * itemsize for d in (d_qk, d_v))
    k, v = (batch * kv_heads * seq * d * itemsize for d in (d_qk, d_v))
    lse = batch * heads * seq * 4
    moved = {"fwd": q + k + v + o + lse,               # q,k,v -> o,lse
             "dq": q + k + v + o + 2 * lse + q,        # ..,do,lse,di -> dq
             "dkv": q + k + v + o + 2 * lse + k + v,
             "bwd": q + k + v + o + 2 * lse + q + k + v}[which]
    return ops, float(moved)


def flash_attention_call(which: str, batch: int, heads: int, kv_heads: int,
                         seq: int, head_dim: int,
                         itemsize: int = 2) -> Tuple[float, float]:
    """``flash_call`` at one head size with the pairs counted as half the
    square, ``seq * seq / 2`` (the diagonal's half left out), as
    ``flash_attn_roofline`` has counted them since PR 26: ``flash_call``'s
    own ``seq * (seq + 1) / 2`` would move that reading by 1 / seq (+0.024 %
    of itself at 4,096 tokens), which is why this door stays."""
    return flash_call(which, batch, heads, kv_heads, seq, head_dim,
                      itemsize=itemsize, pairs=seq * seq / 2.0)


def kernels_share(reader: str, reduced: Dict[str, Any], device_kind: str,
                  pattern: str, call: Callable[[Any], Tuple[float, float]]
                  ) -> Optional[float]:
    """A roofline reader's number: 100 x the least seconds the chip could
    take for the trace's operations whose ``program/label`` matches
    ``pattern``, over the seconds the trace shows for them; ``call(match)``
    gives one call's (operations, bytes).  Says what it matched on a
    ``[kernels]`` line (label: calls, seconds, least seconds), so that a
    traced run shows each kernel against its own roofline and the
    denominator can be checked against the trace.  None where nothing
    matched."""
    rx = re.compile(pattern)
    matched = {}
    for key, seconds in reduced.get("op_seconds", {}).items():
        m = rx.search(key)
        if m:
            calls = reduced["op_counts"][key]
            matched[key.split("/", 1)[1]] = [
                calls, seconds, calls * least_seconds(*call(m), device_kind)]
    spent = sum(v[1] for v in matched.values())
    if not spent:
        return None
    least = sum(v[2] for v in matched.values())
    common.say("kernels", reader=reader, spent_s=spent, least_s=least,
               matched=json.dumps(matched))
    return 100.0 * least / spent


def paged_attention_call(cache_tokens: float, slots: int, heads: int,
                         kv_heads: int, head_dim: int,
                         itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one decode call of paged attention in one
    layer: ``cache_tokens`` is the sum of the sequences' lengths.  Each
    cached key and value is read once; q and the output are [slots, H, D].
    """
    ops = 4.0 * heads * head_dim * cache_tokens          # qK^T and pV
    moved = (2.0 * kv_heads * head_dim * itemsize * cache_tokens
             + 2.0 * slots * heads * head_dim * itemsize)
    return ops, moved


def train_flops_per_token(num_params: int) -> float:
    """Model FLOPs a trained token needs, forward and backward (6*P):
    recomputation does not count, and attention's own term is left out,
    so the share of peak is a floor."""
    return 6.0 * num_params
