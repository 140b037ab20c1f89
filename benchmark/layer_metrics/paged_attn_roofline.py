"""The paged-attention kernel's share of its roofline in the traced window:
the least time the chip could take for the cached keys and values its calls
had to read, over the time the trace shows for them.  The cache's fill is
the client's own count of tokens in flight, averaged over the window."""

from benchmark import roofline, trace
from benchmark.layer_metrics.decode_gap_p50_ms import PAGED_KERNEL


def read(facts):
    t = facts.get("trace")
    if not t or "trace_cache_tokens" not in facts:
        return None
    seconds, calls = trace.ops_matching(t, PAGED_KERNEL)
    if not calls:
        return None
    s = facts["cell"]["sizes"]
    ops, moved = roofline.paged_attention_call(
        facts["trace_cache_tokens"], facts["engine_options"]["max_slots"],
        s["H"], s["Hkv"], s["D"])
    least = roofline.least_seconds(ops, moved, facts["device"]["kind"])
    return 100.0 * calls * least / seconds
