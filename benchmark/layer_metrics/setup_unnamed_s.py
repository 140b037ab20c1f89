"""Set-up that no span of the program explains: ``setup_s`` less the union,
over every process, of the spans between ``run.py``'s ``T0``
(``window_start - setup_s``) and ``window_start``.

A span that only holds others (``group`` in its record: ``train_fit``,
``train_loop``, ``engine_step``) names nothing and is left out.  The
warm-up steps count as named: the stretch from a worker's second numbered
``train_place_batch`` (``step`` 1) to the window's start.  Step 0's batch
is the one the step is compiled on, placed BEFORE ``lower().compile()`` in
every runner (and before the first call, which compiles, in a plain loop):
counted from there the stretch would hide the compile and whatever the
runner does round it.  None where no span carries ``group``: a program
from before the field, whose holders cannot be told from its parts."""

from benchmark import spans
from benchmark.layer_metrics.trace_lower_s import union_s


def read(facts):
    loaded = spans.load(facts) or []
    if not any(s.get("group") for s in loaded):
        return None
    hi = facts["window_start"]
    lo = hi - facts["setup_s"]
    named = [(max(s["start"], lo), min(s["end"], hi)) for s in loaded
             if not s.get("group") and s["end"] > lo and s["start"] < hi]
    warm_up = [s["start"] for s in spans.named(loaded, "train_place_batch")
               if s.get("step", 0) >= 1 and s["start"] < hi]
    if warm_up:
        named.append((max(min(warm_up), lo), hi))
    return facts["setup_s"] - union_s(named)
