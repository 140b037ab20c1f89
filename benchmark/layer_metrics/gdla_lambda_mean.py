"""The mean weight a differential attention's noise heads were subtracted
with in the last reported step, as the program recorded it: the gauge
``ray_tpu_gdla_lambda_mean`` (the mean of sigmoid(lambda) over tokens, signal
heads and layers) in the ``counters.json`` that ``ray_tpu.shutdown()`` leaves
beside ``spans.jsonl``.  0 or 1 says the pair is dead.  None where the
program records no such gauge."""

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
    got = samples.get("ray_tpu_gdla_lambda_mean")
    return float(got[0]["value"]) if got else None
