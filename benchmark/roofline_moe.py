"""What the grouped products of an expert-routed block must do, from shapes
and counts alone: operations and bytes for ``roofline.least_seconds``.  The
peaks stay in ``roofline.py``, and so does the flash kernels' count
(``roofline.flash_call``, with ``visible_pairs``; until PR 61 this file's
``banded_flash_call``)."""

from __future__ import annotations

from typing import Tuple


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
