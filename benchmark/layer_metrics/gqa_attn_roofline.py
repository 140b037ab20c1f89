"""The unwindowed flash kernels' share of their roofline in the traced steps
of a model whose attention layers have no window and no positions: the least
time the chip could take for every ``flash_fwd``, ``flash_dq`` and
``flash_dkv`` call the trace shows (operations over the causal triangle;
``benchmark/roofline_moe.banded_flash_call`` at the model's query and key
heads), over the time it shows for them.  A call holds the rows the program
gives a layer at a time.  None where the trace holds no such kernel or the
model is another."""

import re

from benchmark import roofline, roofline_moe


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or "Hm" not in arch.get("sizes", {}):
        return None
    s = arch["sizes"]
    least = spent = 0.0
    for key, seconds in t.get("op_seconds", {}).items():
        m = re.search(r"/flash_(fwd|dq|dkv)<", key)
        if not m:
            continue
        ops, moved = roofline_moe.banded_flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["Hkv"],
            facts["seq_len"], s["D"])
        least += t["op_counts"][key] * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
        spent += seconds
    return 100.0 * least / spent if spent else None
