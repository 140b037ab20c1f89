"""The flash-attention kernels' share of their roofline in the traced steps:
the least time the chip could take for every forward, dq and dk/dv call the
trace shows, over the time it shows for them."""

from benchmark import roofline, trace

#: The kernels of ``ray_tpu/ops/attention.py`` carry no name of their own
#: (the trace calls them after the scope they sit in: closed_call,
#: rematted_computation, checkpoint), so each is told by what it returns:
#: the forward its output and float32 log-sum-exp, dq one array, dk/dv two
#: (in float32 where query heads share a key head and are summed after).
KERNELS = {"fwd": r"<bf16,f32>$", "dq": r"<bf16>$",
           "dkv": r"<(bf16,bf16|f32,f32)>$"}


def read(facts):
    t = facts.get("trace")
    if not t or "rows" not in facts:
        return None
    s = facts["cell"]["sizes"]
    rows = facts["rows"] // facts["device"]["count"]
    least = spent = 0.0
    for which, pattern in KERNELS.items():
        seconds, calls = trace.ops_matching(t, pattern)
        ops, moved = roofline.flash_attention_call(
            which, rows, s["H"], s["Hkv"], facts["seq_len"], s["D"])
        least += calls * roofline.least_seconds(
            ops, moved, facts["device"]["kind"])
        spent += seconds
    return 100.0 * least / spent if spent else None
