"""The FULL layers' flash kernels' share of their roofline in the traced
steps: ``flash_fwd_d192v128``, ``flash_dq_d192v128`` and ``flash_dkv_d192v128``
(``flash_bwd_..`` where the one pass serves) called in ONE part under
grouped-query attention, a group of query heads over each key head
(``benchmark/roofline_sink.full_call``: operations over the causal triangle
for all the query heads, keys and values moved once a key head), over the time
the trace shows for them.  A windowed call's name goes on (``_w128``), so it
is not matched here (``sink_window_roofline`` reads those).  None where the
trace holds no such kernel or the model's sizes name no window layers' key
heads beside the full layers'."""

from benchmark import roofline_sink
from benchmark.layer_metrics.sink_window_roofline import share

KERNEL = r"/flash_(fwd|dq|dkv|bwd)_d\d+v\d*<"


def read(facts):
    return share("gqa192_attn_roofline", facts, KERNEL,
                 roofline_sink.full_call)
