"""Model FLOP/s utilisation of a sparse model while the device is busy:
6 * (the parameters every token multiplies by * the tokens of the traced
steps + one expert's parameters * the expert evaluations those steps really
did), over the seconds an operation ran times the bf16 peak.  Recomputed
operations and attention's own are not counted, so it is a floor."""

from benchmark import roofline


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("moe_traced"):
        return None
    chips = facts["device"]["count"]
    p, layers = arch["parameters"], arch["expert_layers"]
    tokens = len(arch["moe_traced"]) * facts["tokens_per_step"] / chips
    evaluations = layers * sum(step["moe_held_assignments"]
                               for step in arch["moe_traced"])
    flops = 6.0 * (p["always"] * tokens + p["expert"] * evaluations)
    return 100.0 * flops / t["busy_s"] / roofline.peaks(
        facts["device"]["kind"])["flops_bf16"]
