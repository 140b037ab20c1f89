"""What the attention kernels of a model with sink-biased window layers and
grouped 192 / 128 full layers must do, from shapes alone: operations and bytes
for ``roofline.least_seconds``.  The count of a flash call is
``roofline.flash_call``'s (operations over the useful band alone, the
backward counted as PR 61 counts it); what this file adds is the two kinds'
own head counts and the sink.  The peaks stay in ``roofline.py``."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import roofline


def window_call(which: str, rows: int, s: Dict[str, Any],
                seq: int) -> Tuple[float, float]:
    """(operations, bytes) of one kernel call of a WINDOW layer: ``s["H"]``
    query heads over ``s["Hskv"]`` key heads at ``s["D"]`` / ``s["Dv"]``, the
    band ``0 <= t - s < s["W"]``.  The sink is one float32 a head that the
    forward reads (its column costs one ``exp`` a row, no product: nothing
    is added to the operations); db is formed outside the kernels."""
    ops, moved = roofline.flash_call(which, rows, s["H"], s["Hskv"], seq,
                                     s["D"], s["Dv"], s["W"])
    return ops, moved + (4.0 * rows * s["H"] if which == "fwd" else 0.0)


def full_call(which: str, rows: int, s: Dict[str, Any],
              seq: int) -> Tuple[float, float]:
    """(operations, bytes) of one kernel call of a FULL layer: ``s["H"]``
    query heads over ``s["Hkv"]`` key heads, the causal triangle."""
    return roofline.flash_call(which, rows, s["H"], s["Hkv"], seq, s["D"],
                               s["Dv"])
