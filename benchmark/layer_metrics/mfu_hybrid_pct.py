"""Model FLOP/s utilisation of a hybrid stack with a tied head while the
device is busy: 6 x (the parameters a token multiplies by: all but the
table's look-up, the tied head once, ``parameters()["multiplied"]``) x the
tokens of the traced steps, over the seconds an operation ran times the bf16
peak.  Recomputed operations, attention's own and the scan's are not
counted, so it is a floor, as ``mfu_pct`` is.  None where the runner's arch
module counts no such parameters."""

from benchmark import roofline


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch \
            or "multiplied" not in arch.get("parameters", {}):
        return None
    tokens = facts["trace_steps"] * facts["tokens_per_step"] \
        / facts["device"]["count"]
    flops = roofline.train_flops_per_token(
        arch["parameters"]["multiplied"]) * tokens
    return 100.0 * flops / t["busy_s"] / roofline.peaks(
        facts["device"]["kind"])["flops_bf16"]
