"""What the kernels of a windowed, expert-routed block must do, from shapes
and counts alone: operations and bytes for ``roofline.least_seconds``.  The
peaks stay in ``roofline.py``."""

from __future__ import annotations

from typing import Tuple


def visible_pairs(seq: int, window=None) -> float:
    """(t, s) pairs with ``0 <= t - s`` (and ``< window``) in a row of
    ``seq`` tokens: the band a causal attention has to touch."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def banded_flash_call(which: str, batch: int, heads: int, kv_heads: int,
                      seq: int, head_dim: int, window=None,
                      itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one flash-attention kernel call whose
    operations are counted over the visible band only.  Matmuls a call as
    ``roofline.flash_attention_call``: 2 forward, 3 for dq, 4 for dk/dv,
    each 2 * pairs * head_dim operations a head; bytes are each operand
    read once and each result written once."""
    matmuls = {"fwd": 2, "dq": 3, "dkv": 4}[which]
    ops = matmuls * 2.0 * batch * heads * visible_pairs(seq, window) * head_dim
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    moved = {"fwd": 2 * q + 2 * kv + lse,
             "dq": 3 * q + 2 * kv + 2 * lse + q,
             "dkv": 2 * q + 2 * kv + 2 * lse + 2 * kv}[which]
    return ops, float(moved)


def expert_products(assignments: float, hidden: int, width: int,
                    experts: int, passes: int = 4,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of the grouped products of ONE expert layer in
    one train step under full remat, for ``assignments`` rows routed to the
    ``experts`` held: three products a pass (gate, up, down), 2 * hidden *
    width operations a row each, in ``passes`` = 4 passes' worth: forward,
    the recomputed forward, and a backward of twice the forward (a gradient
    in the rows and one in the weights for each product).  Bytes: each
    row-side operand and result once a product (hidden + width elements a
    row), and the held weights once a product."""
    products = 3 * passes
    ops = products * 2.0 * assignments * hidden * width
    moved = products * itemsize * (assignments * (hidden + width)
                                   + experts * hidden * width)
    return ops, float(moved)
