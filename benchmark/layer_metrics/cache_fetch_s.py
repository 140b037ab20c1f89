"""Seconds of set-up spent fetching compiled programs from the persistent
compile cache: the ``xla_compile`` spans with ``cache_hit`` of a process
that holds the chips, over the spans that end before the window starts
(``seconds`` is jax's own measure of each).  0 where every program was
compiled here.  With several such processes, the slowest.  None where the
program records no ``xla_compile`` span in set-up."""

from benchmark import spans
from benchmark.layer_metrics.trace_lower_s import in_setup


def seconds_where(facts, hit):
    found = in_setup(spans.load(facts), facts, "xla_compile")
    return max((sum((s.get("seconds", spans.seconds(s)) for s in mine
                     if bool(s.get("cache_hit")) == hit), 0.0)
                for mine in found.values()), default=None)


def read(facts):
    return seconds_where(facts, True)
