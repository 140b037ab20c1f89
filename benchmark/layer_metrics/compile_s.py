"""Seconds in the warm-up calls that compile (or fetch from the cache) the
cell's programs."""


def read(facts):
    return facts["compile_s"]
