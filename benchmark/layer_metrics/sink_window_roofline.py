"""The WINDOW layers' flash kernels' share of their roofline in the traced
steps: the least time the chip could take for every ``flash_fwd_.._w128_sink``
(the forward whose rows start at the sink), ``flash_dq_.._w128`` and
``flash_dkv_.._w128`` call the trace shows (``benchmark/roofline_sink.
window_call``: operations over the useful band ``0 <= t - s < window`` alone,
so what the tiles compute beside the band is the reading's loss), over the
time it shows for them.  The kernels are told by name (a trace's label drops
trailing digits: ``flash_dq_d192v128_w``).  None where the trace holds no
such kernel or the model's sizes name no window layers' key heads."""

from benchmark import roofline, roofline_sink

KERNEL = r"/flash_(fwd|dq|dkv|bwd)_d\d+v\d+_w\d*(?:_sink)?<"


def share(reader, facts, kernel, call):
    """``reader``'s 100 x least / spent over the trace's kernels that match
    ``kernel`` (its first group the kind of call) at ``call``'s count."""
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or "Hskv" not in arch.get("sizes", {}):
        return None
    return roofline.kernels_share(
        reader, t, facts["device"]["kind"], kernel,
        lambda m: call(m.group(1), arch["rows_a_call"], arch["sizes"],
                       facts["seq_len"]))


def read(facts):
    return share("sink_window_roofline", facts, KERNEL,
                 roofline_sink.window_call)
