"""The share of a delta-rule state's row that a chunk hands on, in the last
reported step, as the program recorded it: the gauge
``ray_tpu_kda_chunk_carry`` (the mean over KDA layers, chunks, heads and
channels of exp(sum of the log-decay g over a chunk)) in the ``counters.json``
that ``ray_tpu.shutdown()`` leaves beside ``spans.jsonl``.  It moves if
someone changes the chunk, the gate's bound or where ``A_log`` and ``dt_bias``
start.  None where the program records no such gauge."""

import json
import os

from benchmark import spans


def read(facts):
    found = spans.find()
    if found is None:
        return None
    try:
        with open(os.path.join(os.path.dirname(found), "counters.json")) as f:
            samples = json.load(f)["samples"]
    except (OSError, ValueError, KeyError):
        return None
    got = samples.get("ray_tpu_kda_chunk_carry")
    return float(got[0]["value"]) if got else None
