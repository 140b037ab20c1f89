"""How far the hyper-connections' lane-to-lane maps were from doubly
stochastic in the last reported step, as the program recorded it: the gauge
``ray_tpu_hc_sinkhorn_residual`` (the largest |row sum - 1| or |column sum -
1| over every sublayer and token) in the ``counters.json`` that
``ray_tpu.shutdown()`` leaves beside ``spans.jsonl``.  It rises if someone
cuts Sinkhorn's iterations.  None where the program records no such gauge."""

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
    got = samples.get("ray_tpu_hc_sinkhorn_residual")
    return float(got[0]["value"]) if got else None
