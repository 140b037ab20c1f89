"""The hyper-connection passes' share of their roofline in the traced steps:
the least time the chip could take to move the stream as often as the
mathematics needs (``benchmark/roofline_hc.sublayer_passes``: read once for
the maps and the collect, read and written once for the write-back, four
passes' worth under full remat) for every sublayer of every layer and the
prediction module's, over the seconds of every operation traced under the
program's ``block/hc`` scopes (``maps``, ``collect``, ``deposit``), whatever
implements them, as the runner sums them with ``benchmark/scopes.py``.  None
where the runner found no such scope."""

from benchmark import roofline, roofline_hc, scopes


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or not arch.get("scopes") \
            or "n" not in arch.get("sizes", {}):
        return None
    spent = scopes.seconds_under(arch["scopes"], "block/hc")
    if not spent:
        return None
    s = arch["sizes"]
    tokens = facts["trace_steps"] * facts["tokens_per_step"] \
        / facts["device"]["count"]
    sublayers = 2 * (s["L"] + 1)            # the module's layer too
    ops, moved = roofline_hc.sublayer_passes(tokens, s["n"], s["E"])
    return 100.0 * sublayers * roofline.least_seconds(
        ops, moved, facts["device"]["kind"]) / spent
