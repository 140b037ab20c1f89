"""Median milliseconds the host spends placing a step's batch on the
devices (``train_place_batch`` spans that start inside the window)."""

from benchmark import spans


def read(facts):
    median = spans.median_seconds(spans.inside(
        spans.named(spans.load(facts), "train_place_batch"), facts))
    return None if median is None else 1e3 * median
