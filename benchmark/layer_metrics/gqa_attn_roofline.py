"""The unwindowed flash kernels' share of their roofline in the traced steps
of a model whose attention layers have no window and no positions: the least
time the chip could take for every ``flash_fwd`` and ``flash_bwd`` call the
trace shows (the backward's one pass under the group, its float32 shares'
sum outside the kernel in neither the count nor the time: counted since
PR 61, the reading was the forward's alone from PR 60 until then), and
``flash_dq`` / ``flash_dkv`` where a call keeps the pair (operations over
the causal triangle; ``benchmark/roofline.flash_call`` at the model's query
and key heads), over the time it shows for them.  A call holds the rows the
program gives a layer at a time.  None where the trace holds no such kernel
or the model is another."""

from benchmark import roofline


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or "Hm" not in arch.get("sizes", {}):
        return None
    s = arch["sizes"]
    return roofline.kernels_share(
        "gqa_attn_roofline", t, facts["device"]["kind"],
        r"/flash_(fwd|dq|dkv|bwd)<",
        lambda m: roofline.flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["Hkv"],
            facts["seq_len"], s["D"]))
