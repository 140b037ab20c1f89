"""The largest share of a chip's memory that the run held inside the
window: (``bytes_in_use`` + ``bytes_reserved``) / ``bytes_limit`` of the
fullest local device, over the ``worker_sample`` spans that start inside the
window (the program takes one every 2 s in the process that holds the
chips).  ``bytes_reserved`` is what the runtime keeps for the step's
temporaries; ``memory_peak_bytes`` leaves it out.  None where the program
takes no such sample, and where a sample has no ``bytes_limit`` (a CPU)."""

from benchmark import spans


def read(facts):
    samples = spans.inside(
        spans.named(spans.load(facts), "worker_sample"), facts)
    if not samples or not all(s.get("bytes_limit") for s in samples):
        return None
    return 100.0 * max(
        (s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0))
        / s["bytes_limit"] for s in samples)
