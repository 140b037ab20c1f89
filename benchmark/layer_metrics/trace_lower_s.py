"""Seconds of set-up in which jax traced the cell's programs and lowered
them to MLIR: the union of the ``jax_trace`` and ``jax_lower`` spans (a
union, so a nested ``jax.jit``'s trace, which lies inside its parent's,
and a helper traced inside a lowering count once) of a process that holds
the chips, over the spans that end before the window starts.  With a warm
compile cache this is most of ``compile_s``: the executable is fetched, the
Python on the way to its key is not.  With several such processes, the
slowest.  None where the program records no such span."""

from benchmark import spans


def in_setup(loaded, facts, *names):
    """{process: its spans of these names that end before the window
    starts}, over the processes that hold the chips (those with a
    ``worker_backend_init`` span)."""
    holders = {s.get("process")
               for s in spans.named(loaded, "worker_backend_init")}
    found = {}
    for s in loaded or []:
        if s["name"] in names and s.get("process") in holders \
                and s["end"] <= facts["window_start"]:
            found.setdefault(s["process"], []).append(s)
    return found


def union_s(intervals):
    """Seconds that the (start, end) pairs cover, overlaps once."""
    total, reach = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi > reach:
            total += hi - max(lo, reach)
            reach = hi
    return total


def read(facts):
    found = in_setup(spans.load(facts), facts, "jax_trace", "jax_lower")
    return max((union_s((s["start"], s["end"]) for s in mine)
                for mine in found.values()), default=None)
