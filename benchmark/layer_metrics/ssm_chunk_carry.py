"""The share of a recurrent state that a chunk hands on, in the last reported
step, as the program recorded it: the gauge ``ray_tpu_ssm_chunk_carry`` (the
mean over mixers, chunks and heads of exp(sum of dt * A over a chunk)) in the
``counters.json`` that ``ray_tpu.shutdown()`` leaves beside ``spans.jsonl``.
It moves if someone changes the chunk, drops the carry or starts ``A_log``
elsewhere.  None where the program records no such gauge."""

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
    got = samples.get("ray_tpu_ssm_chunk_carry")
    return float(got[0]["value"]) if got else None
