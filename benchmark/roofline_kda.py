"""What a Kimi-Delta-Attention layer's chunked scan must do, from shapes
alone and whatever implements it: operations and bytes for
``roofline.least_seconds``.  The peaks stay in ``roofline.py``; the layer's
causal convolution is ``roofline_ssm.conv_passes``' (three of them side by
side: q, k and v)."""

from __future__ import annotations

from typing import Tuple


def scan_passes(tokens: float, heads: int, dk: int, dv: int, chunk: int,
                passes: int = 4, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's chunked gated delta rule in one
    train step under full remat.  Bytes: q, k, v read and o written once in
    ``itemsize`` bytes, the decay ``g`` (float32, a channel) and beta
    (float32, a head) read once; the running sums, the masks, ``A``, its
    inverse, ``U`` and the states never in HBM: a floor.  Operations a token
    a head, the chunked form's products with the triangles counted as
    triangles: ``A`` and ``Aqk`` (chunk * dk each), the inverse applied to
    the right-hand side and ``Aqk U`` (chunk * dv each), the two read-outs
    of the carried state and the chunk's own state (2 * dk * dv each);
    building the triangular inverse is left out: a floor again.  In
    ``passes`` = 4 passes' worth: forward, the recomputed forward, and a
    backward of twice the forward, as ``roofline_ssm.scan_passes``."""
    ops = passes * tokens * heads * (2.0 * chunk * (dk + dv) + 6.0 * dk * dv)
    moved = passes * tokens * heads * (itemsize * (2 * dk + 2 * dv)
                                       + 4 * dk + 4)
    return ops, float(moved)
