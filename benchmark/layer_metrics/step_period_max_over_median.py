"""The longest step period of the window over the median one: 1.00-1.03 in
a run whose steps all took their usual time, 2-3 where one stalled.

A period runs from the start of one ``train_place_batch`` span to the start
of the next of the same process (the program numbers them: ``step``), over
the spans that start inside the window.  The first ``2 + trace_steps``
periods of the window are left out, traced run or not: the runners start
the profiler inside period 1 and stop it inside period ``1 + trace_steps``,
and both calls take their time.  Under 5 periods left, or a program whose
spans carry no ``step``: None.  With several processes, the worst."""

import statistics

from benchmark import spans


def read(facts):
    found = spans.inside(
        spans.named(spans.load(facts), "train_place_batch"), facts)
    by_process = {}
    for s in found:
        if "step" in s:
            by_process.setdefault(s.get("process"), []).append(s)
    ratios = []
    for beats in by_process.values():
        beats.sort(key=lambda s: s["step"])
        periods = [b["start"] - a["start"] for a, b in zip(beats, beats[1:])
                   if b["step"] == a["step"] + 1][2 + facts["trace_steps"]:]
        if len(periods) >= 5:
            ratios.append(max(periods) / statistics.median(periods))
    return max(ratios) if ratios else None
