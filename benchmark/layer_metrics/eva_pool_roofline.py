"""The pooling kernel pair's share of its roofline in the traced steps,
which the memory bounds: the least time for every ``eva_pool_fwd`` and
``eva_pool_bwd`` call the trace shows (k and v read, the summaries written;
backward k, v and the summaries' gradients read, dk and dv written;
``benchmark/roofline_eva.pool_call``) over the time it shows for them.  None
where the trace holds no such kernel."""

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
        m = re.search(r"/eva_pool_(fwd|bwd)_c\d*<", key)
        if not m:
            continue
        ops, moved = roofline_eva.pool_call(
            m.group(1), rows, s["H"], facts["seq_len"], s["D"], s["chunk"])
        least += t["op_counts"][key] * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
        spent += seconds
    return 100.0 * least / spent if spent else None
