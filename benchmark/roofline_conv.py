"""What a double-gated short convolution must do, from shapes alone and
whatever implements it: operations and bytes for ``roofline.least_seconds``.
The peaks stay in ``roofline.py``."""

from __future__ import annotations

from typing import Tuple

#: channel-wide arrays a token that cross HBM in one train step under full
#: remat, at the least: the forward reads B, C and u and writes the result
#: (4), the recomputed forward the same (4), and the backward reads B, C, u
#: and the result's cotangent and writes three gradients (7).  The taps and
#: their gradient are K numbers a channel, not a token's.
ARRAYS_A_STEP = 4 + 4 + 7


def gated_conv_passes(tokens: float, channels: int, taps: int,
                      itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's gated short convolution in one
    train step under full remat: three operands read and one result written
    a forward pass, twice (the remat), and a backward of four reads and
    three writes, ``ARRAYS_A_STEP`` = 15 arrays of ``channels`` a token in
    ``itemsize`` bytes: a floor, which a form that writes ``B * u``, a padded
    copy or a tap as a pass of its own does not reach.  Operations a token
    and channel: the two gates and ``taps`` multiply-adds forward (2 * taps
    + 2), counted four passes' worth as ``roofline_ssm.conv_passes`` counts
    them (a backward of twice the forward); beside the bytes they are
    nothing (a v5e does 240 operations in a byte's time)."""
    return (4 * (2.0 * taps + 2) * tokens * channels,
            float(ARRAYS_A_STEP * tokens * channels * itemsize))
