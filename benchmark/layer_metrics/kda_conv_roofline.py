"""The KDA layers' causal convolution's share of its roofline in the traced
steps: the least time the chip could take to read its channels (q, k and v
side by side: 3 x heads x head size) once and write them once a pass
(``benchmark/roofline_ssm.conv_passes``, four passes' worth under full remat)
for every KDA layer, over the seconds of every operation traced under the
program's ``block/attn/kda/conv`` scope, as the runner sums them with
``benchmark/scopes.py``.  None where the runner found no such scope."""

from benchmark import roofline_ssm
from benchmark.layer_metrics.kda_scan_roofline import share_of_least


def read(facts):
    return share_of_least(
        facts, "block/attn/kda/conv",
        lambda tokens, s: roofline_ssm.conv_passes(
            tokens, 3 * s["H"] * s["D"], s["K"]))
