"""``grouped_mm_roofline`` for expert layers whose experts have no gate: the
least time the chip could take for the rows the traced steps really routed to
the held experts at TWO grouped products a pass (up, down;
``benchmark/roofline_ssm.ungated_expert_products``), over the time the trace
shows for the grouped-matmul kernels (``gmm`` and ``tgmm``, or
``ragged-dot``).  None where the model's experts have a gate (its sizes name
no un-gated width) or no step was traced."""

from benchmark import roofline, roofline_ssm, trace
from benchmark.layer_metrics.grouped_mm_roofline import KERNELS


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or not arch.get("moe_traced") \
            or "Hm" not in arch.get("sizes", {}):
        return None
    spent, _ = trace.ops_matching(t, KERNELS)
    s, layers = arch["sizes"], arch["expert_layers"]
    least = 0.0
    for step in arch["moe_traced"]:
        ops, moved = roofline_ssm.ungated_expert_products(
            step["moe_held_assignments"], s["E"], s["Me"], s["Xh"])
        least += layers * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
    return 100.0 * least / spent if spent else None
