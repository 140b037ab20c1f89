"""Model FLOP/s utilisation while the device is busy: 6 * parameters *
the tokens of the traced steps, over the seconds the trace shows an
operation running (a chip's share of both) times the bf16 peak.  Recomputed
operations and attention's own operations are not counted, so it is a
floor; what the host costs the step shows in ``idle_share``, not here."""

from benchmark import roofline, weights


def read(facts):
    t = facts.get("trace")
    if not t or not t.get("busy_s") or "trace_steps" not in facts:
        return None
    chips = facts["device"]["count"]
    tokens = facts["trace_steps"] * facts["tokens_per_step"] / chips
    flops = roofline.train_flops_per_token(
        weights.num_params(facts["cell"]["sizes"])) * tokens
    return 100.0 * flops / t["busy_s"] / roofline.peaks(
        facts["device"]["kind"])["flops_bf16"]
