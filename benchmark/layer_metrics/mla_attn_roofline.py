"""The latent-attention flash kernels' share of their roofline in the traced
steps: the least time the chip could take for every ``flash_fwd_d192v128``,
``flash_dq_d192v128`` and ``flash_dkv_d192v128`` call the trace shows
(operations over the visible triangle, scores over d_qk and values over d_v;
``benchmark/roofline_mla.flash_call``), over the time it shows for them.  The
kernels are told by name (a trace's label drops trailing digits:
``flash_fwd_d192v``).  A call holds the rows the program gives a layer at a
time.  None where the trace holds no such kernel, as on a program without
them."""

import re

from benchmark import roofline, roofline_mla


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or "dv" not in arch.get("sizes", {}):
        return None
    s = arch["sizes"]
    least = spent = 0.0
    for key, seconds in t.get("op_seconds", {}).items():
        m = re.search(r"/flash_(fwd|dq|dkv)_d\d+v\d*<", key)
        if not m:
            continue
        ops, moved = roofline_mla.flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["H"],
            facts["seq_len"], s["dn"] + s["dr"], s["dv"])
        least += t["op_counts"][key] * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
        spent += seconds
    return 100.0 * least / spent if spent else None
