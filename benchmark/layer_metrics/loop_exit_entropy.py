"""Mean entropy (nats) of a looped stack's exit distribution in the last
reported step, as the program recorded it: the gauge
``ray_tpu_train_loop_exit_entropy`` in the ``counters.json`` that
``ray_tpu.shutdown()`` leaves beside ``spans.jsonl``.  At most log(passes);
near 0 the distribution has collapsed on one pass.  None where the program
records no such gauge."""

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
    got = samples.get("ray_tpu_train_loop_exit_entropy")
    return float(got[0]["value"]) if got else None
