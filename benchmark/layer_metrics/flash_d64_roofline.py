"""The flash kernels' share of their roofline at a head size that is not 128
(LFM2's 64): the least time the chip could take for every ``flash_fwd_d64``,
``flash_dq_d64`` and ``flash_dkv_d64`` call the trace shows (operations over
the causal triangle; ``benchmark/roofline_moe.banded_flash_call`` at the
model's query and key heads and head size), over the time it shows for them.
The kernels are told by name (a trace's label drops trailing digits:
``flash_fwd_d``).  A call holds the rows the program gives a layer at a time.

The operations are counted at the chip's bf16 peak, which a 128 x 128 MXU
reaches only on contractions of 128 or more: the scores and dq / dk contract
over the head's 64 channels, half a tile, so on a v5e a share near half is
this kernel's ceiling and not a fault of its walk (PERF.md, PR 47, has the
chip's table).  None where the trace holds no such kernel, as on a program
without them."""

import re

from benchmark import roofline, roofline_moe


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or "D" not in arch.get("sizes", {}):
        return None
    s = arch["sizes"]
    least = spent = 0.0
    for key, seconds in t.get("op_seconds", {}).items():
        m = re.search(r"/flash_(fwd|dq|dkv)_d\d*<", key)
        if not m:
            continue
        ops, moved = roofline_moe.banded_flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["Hkv"],
            facts["seq_len"], s["D"])
        least += t["op_counts"][key] * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
        spent += seconds
    return 100.0 * least / spent if spent else None
