"""The gated delta rule's share of its roofline in the traced steps: the least
time the chip could take for every KDA layer's chunked scan
(``benchmark/roofline_kda.scan_passes``: q, k, v, the decay and beta read once
and o written once, the chunked form's products at the file's ``kda_chunk``,
the triangular inverse left out, four passes' worth under full remat), over
the seconds of every operation traced under the program's
``block/attn/kda/scan`` scope, whatever implements it, as the runner sums them
with ``benchmark/scopes.py``.  None where the runner found no such scope."""

from benchmark import roofline, roofline_kda, scopes


def share_of_least(facts, scope, passes):
    """100 x (the least seconds of ``passes(tokens, sizes)`` -> (operations,
    bytes) for every KDA layer of the traced steps) / (the seconds traced
    under ``scope``); None where there is no such scope or the model has no
    KDA layers."""
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or not arch.get("scopes") \
            or "kda" not in arch.get("sizes", {}).get("kinds", ()):
        return None
    spent = scopes.seconds_under(arch["scopes"], scope)
    if not spent:
        return None
    s = arch["sizes"]
    tokens = facts["trace_steps"] * facts["tokens_per_step"] \
        / facts["device"]["count"]
    return 100.0 * list(s["kinds"]).count("kda") * roofline.least_seconds(
        *passes(tokens, s), facts["device"]["kind"]) / spent


def read(facts):
    return share_of_least(
        facts, "block/attn/kda/scan",
        lambda tokens, s: roofline_kda.scan_passes(
            tokens, s["H"], s["D"], s["D"], s["Q"]))
