"""The expert layers' grouped matrix products' share of their roofline in the
traced steps: the least time the chip could take for the rows the steps
really routed to the held experts (forward, recomputed forward and
backward), over the time the trace shows for the grouped-matmul kernels
(``gmm`` and ``tgmm``, upstream's Pallas kernels, or ``ragged-dot``)."""

from benchmark import roofline, roofline_moe, trace

KERNELS = r"/(t?gmm|ragged-dot[\w\-]*)<"


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or not arch.get("moe_traced"):
        return None
    spent, _ = trace.ops_matching(t, KERNELS)
    s, layers = arch["sizes"], arch["expert_layers"]
    least = 0.0
    for step in arch["moe_traced"]:
        ops, moved = roofline_moe.expert_products(
            step["moe_held_assignments"], s["E"], s["Me"], s["Xh"])
        least += layers * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
    return 100.0 * least / spent if spent else None
