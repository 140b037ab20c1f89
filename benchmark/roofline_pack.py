"""What attention over a PACKED row must do, from the documents' lengths
alone and whatever implements it: the pairs inside documents, and a flash
kernel call's operations and bytes over them, for
``roofline.least_seconds``.  The peaks and the count of a call stay in
``roofline.py``."""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmark import roofline


def pairs_inside(lengths: Iterable[int]) -> float:
    """(t, s) pairs a causal attention inside documents has to touch in a
    row whose documents have these lengths: sum of len^2 / 2 (the
    diagonal's half left out, as ``roofline.flash_attention_call`` counts
    a whole row's)."""
    return sum(n * n for n in lengths) / 2.0


def pairs_share(lengths: Iterable[int], seq: int) -> float:
    """sum of len^2 / seq^2: the share of a row's causal triangle that the
    pairs inside its documents are, and so what is left to compute where
    blocks between documents are skipped."""
    return 2.0 * pairs_inside(lengths) / (float(seq) * seq)


def flash_seg_call(which: str, rows: int, heads: int, kv_heads: int, seq: int,
                   head_dim: int, pairs_a_row: float) -> Tuple[float, float]:
    """(operations, bytes) of one ``flash_seg_*`` kernel call over ``rows``
    packed rows: ``roofline.flash_call``'s products over the pairs inside
    documents only, its bytes as for any call (every operand is read and
    every result written whatever the mask)."""
    return roofline.flash_call(which, rows, heads, kv_heads, seq, head_dim,
                               pairs=pairs_a_row)
