"""Seconds of set-up spent in backend compiles that the persistent cache
did not have: the ``xla_compile`` spans without ``cache_hit`` of a process
that holds the chips, over the spans that end before the window starts.
Small on a warm run (a program under jax's thresholds for storing is never
stored, and a run with the cache off compiles everything here); tens of
seconds when this side of a comparison ran cold, which is the first thing
a reader of a ``setup_s`` regression needs.  With several such processes,
the slowest.  None where the program records no ``xla_compile`` span in
set-up."""

from benchmark.layer_metrics.cache_fetch_s import seconds_where


def read(facts):
    return seconds_where(facts, False)
