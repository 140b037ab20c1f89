"""90th percentile of the time from when a request was due to its first
token at the client."""

from benchmark import loadgen, traffic


def read(facts):
    ttft = loadgen.ttft_ms(facts.get("requests", ()))
    return traffic.percentile(ttft, 90) if ttft else None
