"""All flash-attention calls of the traced steps against their roofline,
operations counted over the visible band only: the window layers' calls
(kernels named ``flash_*_w<window>``; a trace's label drops trailing digits,
so ``flash_fwd_w``, and either form is matched) at the band's count, the
full layers' (``flash_*``) at the causal triangle's; forward, the backward's
one pass (``flash_bwd``, ``flash_bwd_w``: since PR 61; the reading was the
forward's alone from PR 60 until then) and the pair where a call keeps it.
A call holds the rows the program gives a layer at a time (``rows_a_call``
in the runner's facts), not the step's."""

from benchmark import roofline


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch:
        return None
    s = arch["sizes"]
    return roofline.kernels_share(
        "window_attn_roofline", t, facts["device"]["kind"],
        r"/flash_(fwd|dq|dkv|bwd)(_w\d*)?<",
        lambda m: roofline.flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["Hkv"],
            facts["seq_len"], s["D"],
            window=s["window"] if m.group(2) else None))
