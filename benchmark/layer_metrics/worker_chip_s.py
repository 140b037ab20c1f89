"""Seconds from the spawn of the process that holds the chips to its
backend being up: the start of its ``worker_start`` span (the node spawned
it) to the end of its ``worker_backend_init`` (its own first touch of the
backend returned).  With several such processes, the slowest."""

from benchmark import spans


def read(facts):
    loaded = spans.load(facts)
    spawned = {s.get("pid"): s for s in spans.named(loaded, "worker_start")}
    took = [up["end"] - spawned[up["process"]]["start"]
            for up in spans.named(loaded, "worker_backend_init")
            if up.get("process") in spawned]
    return max(took) if took else None
