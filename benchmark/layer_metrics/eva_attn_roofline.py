"""The four EVA attention kernels' share of their roofline in the traced
steps: the least time the chip could take for every ``eva_fwd``, ``eva_dq``,
``eva_dkv`` and ``eva_dsum`` call the trace shows (operations over the
visible pairs only, local and remote; ``benchmark/roofline_eva.eva_call``),
over the time it shows for them.  The kernels are told by name
(``eva_fwd_w2048c16``; a trace's label drops trailing digits).  None where
the trace holds no such kernel, as on a program without them."""

import re

from benchmark import roofline, roofline_eva


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch:
        return None
    s = arch["sizes"]
    rows = facts["rows"] // facts["device"]["count"]
    least = spent = 0.0
    for key, seconds in t.get("op_seconds", {}).items():
        m = re.search(r"/eva_(fwd|dq|dkv|dsum)_w\d*c?\d*<", key)
        if not m:
            continue
        ops, moved = roofline_eva.eva_call(
            m.group(1), rows, s["H"], facts["seq_len"], s["D"], s["window"],
            s["chunk"])
        least += t["op_counts"][key] * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
        spent += seconds
    return 100.0 * least / spent if spent else None
