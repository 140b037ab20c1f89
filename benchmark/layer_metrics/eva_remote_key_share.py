"""Share of the forward walk's score columns that are summaries of earlier
windows and not tokens of the query's own: from the program's counter
``ray_tpu_eva_step_geometry_total`` in ``counters.json``, the forward
kernel's summary steps times their block over all its steps times theirs
(a q block's rows are the same on both).  0 at a row of one window, 47 % at
32,768 / 2,048 / 16 (48.4 % by exact pairs: the last block of a run is
counted whole).  None where the program counts no such kernel."""

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
    for sample in samples.get("ray_tpu_eva_step_geometry_total", ()):
        tags = sample.get("tags", {})
        if tags.get("kernel", "").startswith("eva_fwd"):
            remote = int(tags["summary_steps"]) * int(tags["block_s"])
            local = int(tags["token_steps"]) * int(tags["block_k"])
            return 100.0 * remote / (remote + local)
    return None
