"""Model FLOP/s utilisation of a looped stack while the device is busy:
6 * what a token multiplies by in a step * the tokens of the traced steps,
over the seconds an operation ran times the bf16 peak.  A token meets every
layer once a pass and the head and the exit gate after each
(``archs/ouro.parameters``: ``multiplied_a_token``), where ``mfu_pct``'s
6 * parameters would count each weight once.  Recomputed operations and
attention's own are not counted, so it is a floor.  None where the runner
states no such count."""

from benchmark import roofline


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch \
            or "multiplied_a_token" not in arch.get("parameters", {}):
        return None
    chips = facts["device"]["count"]
    tokens = facts["trace_steps"] * facts["tokens_per_step"] / chips
    flops = 6.0 * arch["parameters"]["multiplied_a_token"] * tokens
    return 100.0 * flops / t["busy_s"] / roofline.peaks(
        facts["device"]["kind"])["flops_bf16"]
