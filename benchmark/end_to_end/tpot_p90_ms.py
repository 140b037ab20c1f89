"""90th percentile over requests of (last token - first token) / (tokens - 1)
at the client."""

from benchmark import loadgen, traffic


def read(facts):
    tpot = loadgen.tpot_ms(facts.get("requests", ()))
    return traffic.percentile(tpot, 90) if tpot else None
