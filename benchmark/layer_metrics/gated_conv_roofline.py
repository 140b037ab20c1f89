"""The double-gated short convolutions' share of their roofline in the traced
steps: the least time the chip could take to move what every ``conv`` layer's
convolution must move (``benchmark/roofline_conv.gated_conv_passes``: three
operands read and one result written a forward pass, twice under full remat,
and the backward's four reads and three writes), over the seconds of every
operation traced under the program's ``block/conv/gate`` scope, whatever
implements it (the kernels ``gated_conv_fwd`` / ``gated_conv_bwd``, or
``jnp``), the first forward's among them
(``conv_device_share.seconds_under``).  None where the runner found no such
scope or the model has no such layer."""

from benchmark import roofline, roofline_conv
from benchmark.layer_metrics.conv_device_share import seconds_under


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or not arch.get("scopes") \
            or "c" not in str(arch.get("sizes", {}).get("kinds", "")):
        return None
    spent = seconds_under(arch["scopes"], "block/conv/gate")
    if not spent:
        return None
    s = arch["sizes"]
    tokens = facts["trace_steps"] * facts["tokens_per_step"] \
        / facts["device"]["count"]
    return 100.0 * s["kinds"].count("c") * roofline.least_seconds(
        *roofline_conv.gated_conv_passes(tokens, s["E"], s["K"]),
        facts["device"]["kind"]) / spent
