"""The WINDOW layers' flash kernels' share of their roofline in the traced
steps: as ``gdla_attn_roofline``, over the kernels whose names carry a
window (``flash_fwd_d192v128_w128``; a trace's label drops trailing digits:
``flash_fwd_d192v128_w``), the operations counted over the useful band alone
(``0 <= t - s < sliding_window``): what the tiles compute beside the band is
the reading's loss.  None where the trace holds no such kernel."""

from benchmark.layer_metrics.gdla_attn_roofline import share

KERNEL = r"/flash_(fwd|dq|dkv|bwd)_d\d+v\d+_w\d*<"


def read(facts):
    arch = facts.get("arch") or {}
    return share("gdla_window_roofline", facts, KERNEL,
                 arch.get("sizes", {}).get("W"))
