"""The flash kernels' share of their roofline at a head size that is not 128
(LFM2's 64): the least time the chip could take for every ``flash_fwd_d64``
and ``flash_bwd_d64`` call the trace shows (the backward's one pass under
the group: counted since PR 61, the reading was the forward's alone from
PR 60 until then), and ``flash_dq_d64`` / ``flash_dkv_d64`` where a call
keeps the pair (operations over the causal triangle;
``benchmark/roofline.flash_call`` at the model's query and key heads and
head size), over the time it shows for them.  The kernels are told by name
(a trace's label drops trailing digits: ``flash_fwd_d``).  A call holds the
rows the program gives a layer at a time.

The operations are counted at the chip's bf16 peak, which a 128 x 128 MXU
reaches only on contractions of 128 or more: the scores and dq / dk contract
over the head's 64 channels, half a tile, so on a v5e a share near half is
this kernel's ceiling and not a fault of its walk (PERF.md, PR 47, has the
chip's table).  None where the trace holds no such kernel, as on a program
without them."""

from benchmark import roofline


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or "D" not in arch.get("sizes", {}):
        return None
    s = arch["sizes"]
    return roofline.kernels_share(
        "flash_d64_roofline", t, facts["device"]["kind"],
        r"/flash_(fwd|dq|dkv|bwd)_d\d*<",
        lambda m: roofline.flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["Hkv"],
            facts["seq_len"], s["D"]))
