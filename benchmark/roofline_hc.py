"""What the hyper-connection passes of a sublayer must do, from shapes alone:
operations and bytes for ``roofline.least_seconds``.  The peaks stay in
``roofline.py``."""

from __future__ import annotations

from typing import Tuple


def sublayer_passes(tokens: float, lanes: int, hidden: int, passes: int = 4,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ONE sublayer's hyper-connection in one train
    step under full remat: the fewest bytes the mathematics moves, the
    stream of ``lanes`` x ``hidden`` numbers a token read once for the maps
    and the collect, and read once and written once for the write-back, in
    ``passes`` = 4 passes' worth (forward, the recomputed forward, and a
    backward of twice the forward: a gradient arrives at every lane it was
    written to and leaves at every lane it was read from), as
    ``roofline_moe.expert_products`` counts a layer's passes.  The
    sublayer's own input and output (one lane's worth each) and the maps
    (2 lanes + lanes^2 numbers a token) are left out: the least is a floor.
    Operations: the thin product (2 * lanes * hidden * (2 lanes + lanes^2)),
    the collect (2 * lanes * hidden) and the write-back (2 * lanes * hidden
    * (lanes + 1)) a token."""
    stream = tokens * lanes * hidden
    width = 2 * lanes + lanes * lanes
    ops = passes * 2.0 * stream * (width + 1 + lanes + 1)
    return ops, float(passes * 3 * stream * itemsize)
