"""Median start-to-start time of the decode program on the device.  The
decode program is the one in which the paged-attention kernel ran."""

import statistics

from benchmark import trace

PAGED_KERNEL = r"paged_attention"


def decode_programs(reduced):
    return trace.modules_with_op(reduced, PAGED_KERNEL)


def read(facts):
    t = facts.get("trace")
    if not t:
        return None
    runs = [r for m in decode_programs(t) for r in t["module_runs"].get(m, ())]
    gaps = trace.start_to_start(runs)
    return 1e3 * statistics.median(gaps) if gaps else None
