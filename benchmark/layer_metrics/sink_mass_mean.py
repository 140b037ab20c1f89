"""The mean share of a window row's softmax mass that the learned attention
sinks took in the last reported step, as the program recorded it: the gauge
``ray_tpu_attn_sink_mass_mean`` (the mean of ``exp(b_h - lse_t)`` over window
layers, held heads and positions) in the ``counters.json`` that
``ray_tpu.shutdown()`` leaves beside ``spans.jsonl``.  Near 0 the sinks do
nothing and the cell measures a plain window; near 1 the layers attend to
nothing.  None where the program records no such gauge."""

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
    got = samples.get("ray_tpu_attn_sink_mass_mean")
    return float(got[0]["value"]) if got else None
