"""Share of the traced window the device spent in programs other than the
decode program: the prefills, their cache writes and the logits' stacking."""

from benchmark.layer_metrics.decode_gap_p50_ms import decode_programs


def read(facts):
    t = facts.get("trace")
    if not t or not t.get("window_s"):
        return None
    decode = set(decode_programs(t))
    if not decode:
        return None
    other = sum(v for k, v in t["op_seconds"].items()
                if k.split("/", 1)[0] not in decode)
    return 100.0 * other / t["window_s"]
