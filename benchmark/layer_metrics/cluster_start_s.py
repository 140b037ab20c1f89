"""Seconds in ``ray_tpu.init()``: runtime, node and object store up (the
program's ``runtime_init`` span)."""

from benchmark import spans


def read(facts):
    found = spans.named(spans.load(facts), "runtime_init")
    return spans.seconds(found[0]) if found else None
