"""The FULL layers' flash kernels' share of their roofline in the traced
steps: the least time the chip could take for every ``flash_fwd_d192v128``,
``flash_dq_d192v128``, ``flash_dkv_d192v128`` and ``flash_bwd_d192v128`` call
the trace shows (operations over the causal triangle for all the query
heads, keys and values moved once a key head;
``benchmark/roofline.flash_call``), over the time it shows for them.
The kernels are told by name (a trace's label drops trailing digits:
``flash_fwd_d192v``), and a windowed call's name goes on (``_w128``), so it
is not matched here (``gdla_window_roofline`` reads those).  A call holds the
rows the program gives a layer at a time.  None where the trace holds no
such kernel or the model's sizes name no key heads and window."""

from benchmark import roofline

KERNEL = r"/flash_(fwd|dq|dkv|bwd)_d\d+v\d*<"


def share(reader, facts, kernel, window):
    """``reader``'s 100 x least / spent over the trace's kernels that match
    ``kernel`` (its first group the kind of call), at ``window``."""
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or "W" not in arch.get("sizes", {}):
        return None
    s = arch["sizes"]
    return roofline.kernels_share(
        reader, t, facts["device"]["kind"], kernel,
        lambda m: roofline.flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["Hkv"],
            facts["seq_len"], s["dn"] + s["dr"], s["dv"], window))


def read(facts):
    return share("gdla_attn_roofline", facts, KERNEL, None)
