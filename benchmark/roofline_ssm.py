"""What a state-space mixer's scan and convolution, and an un-gated expert
layer's grouped products, must do, from shapes alone and whatever implements
them: operations and bytes for ``roofline.least_seconds``.  The peaks stay in
``roofline.py``."""

from __future__ import annotations

from typing import Tuple

from benchmark.roofline_moe import expert_products


def scan_passes(tokens: float, heads: int, head_dim: int, groups: int,
                state: int, chunk: int, passes: int = 4,
                itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ONE mixer's chunked scan in one train step
    under full remat.  Bytes: X, B, C and the time step read once and y
    written once, in ``itemsize`` bytes (the gate z belongs to the norm's
    pass); the decay masks and the states never in HBM: a floor.
    Operations a token: inside a chunk the C . B product a group and the
    masked product with X a head, 2 * chunk * (groups * state + heads *
    head_dim); the chunk's own state and the carried state's read-out, 2 *
    heads * head_dim * state each.  In ``passes`` = 4 passes' worth:
    forward, the recomputed forward, and a backward of twice the forward,
    as ``roofline_moe.expert_products`` counts a layer's passes."""
    width = heads * head_dim
    ops = passes * tokens * (2.0 * chunk * (groups * state + width)
                             + 4.0 * width * state)
    moved = passes * tokens * itemsize * (2 * width + 2 * groups * state
                                          + heads)
    return ops, float(moved)


def conv_passes(tokens: float, channels: int, kernel: int, passes: int = 4,
                itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ONE mixer's causal depthwise convolution in
    one train step under full remat: its channels read once and written
    once a pass, ``kernel`` multiply-adds a channel a token."""
    return (passes * 2.0 * kernel * tokens * channels,
            float(passes * 2 * tokens * channels * itemsize))


def ungated_expert_products(assignments: float, hidden: int, width: int,
                            experts: int, passes: int = 4,
                            itemsize: int = 2) -> Tuple[float, float]:
    """``roofline_moe.expert_products`` for experts without a gate: two
    grouped products a pass (up, down) for three, so two thirds of its
    operations and of its bytes at the same sizes."""
    ops, moved = expert_products(assignments, hidden, width, experts, passes,
                                 itemsize)
    return ops * 2 / 3, moved * 2 / 3
