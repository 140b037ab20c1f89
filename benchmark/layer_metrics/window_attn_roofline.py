"""All flash-attention calls of the traced steps against their roofline,
operations counted over the visible band only: the window layers' calls
(kernels named ``flash_*_w<window>``; a trace's label drops trailing digits,
so ``flash_fwd_w``, and either form is matched) at the band's count, the
full layers' (``flash_*``) at the causal triangle's.  A call holds the rows the program gives a layer at a
time (``rows_a_call`` in the runner's facts), not the step's."""

import re

from benchmark import roofline, roofline_moe


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch:
        return None
    s = arch["sizes"]
    least = spent = 0.0
    for key, seconds in t.get("op_seconds", {}).items():
        m = re.search(r"/flash_(fwd|dq|dkv)(_w\d*)?<", key)
        if not m:
            continue
        ops, moved = roofline_moe.banded_flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["Hkv"],
            facts["seq_len"], s["D"], s["window"] if m.group(2) else None)
        least += t["op_counts"][key] * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
        spent += seconds
    return 100.0 * least / spent if spent else None
