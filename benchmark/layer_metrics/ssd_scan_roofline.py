"""The state-space scan's share of its roofline in the traced steps: the least
time the chip could take for every mixer's chunked scan
(``benchmark/roofline_ssm.scan_passes``: X, B, C and the time step read once
and y written once, the chunk products' operations, four passes' worth under
full remat), over the seconds of every operation traced under the program's
``block/ssm/scan`` scope, whatever implements it, as the runner sums them
with ``benchmark/scopes.py``.  None where the runner found no such scope."""

from benchmark import roofline, roofline_ssm, scopes


def share_of_least(facts, scope, passes):
    """100 x (the least seconds of ``passes(tokens, sizes)`` -> (operations,
    bytes) for every mixer of the traced steps) / (the seconds traced under
    ``scope``); None where there is no such scope or the model has no
    mixers."""
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or not arch.get("scopes") \
            or "Hm" not in arch.get("sizes", {}):
        return None
    spent = scopes.seconds_under(arch["scopes"], scope)
    if not spent:
        return None
    s = arch["sizes"]
    tokens = facts["trace_steps"] * facts["tokens_per_step"] \
        / facts["device"]["count"]
    return 100.0 * s["kinds"].count("M") * roofline.least_seconds(
        *passes(tokens, s), facts["device"]["kind"]) / spent


def read(facts):
    return share_of_least(
        facts, "block/ssm/scan", lambda tokens, s: roofline_ssm.scan_passes(
            tokens, s["Hm"], s["P"], s["G"], s["N"], s["Q"]))
