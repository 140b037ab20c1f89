"""The mixers' causal convolution's share of its roofline in the traced steps:
the least time the chip could take to read its channels once and write them
once a pass (``benchmark/roofline_ssm.conv_passes``, four passes' worth under
full remat) for every mixer, over the seconds of every operation traced under
the program's ``block/ssm/conv`` scope, as the runner sums them with
``benchmark/scopes.py``.  None where the runner found no such scope."""

from benchmark import roofline_ssm
from benchmark.layer_metrics.ssd_scan_roofline import share_of_least


def read(facts):
    return share_of_least(
        facts, "block/ssm/conv", lambda tokens, s: roofline_ssm.conv_passes(
            tokens, s["Hm"] * s["P"] + 2 * s["G"] * s["N"], s["K"]))
