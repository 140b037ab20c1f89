"""Requests the engine preempted in the run (``ray_tpu_llm_preemptions_total``)."""


def read(facts):
    counters = facts.get("counters")
    if counters is None:
        return None
    return float(counters.get("ray_tpu_llm_preemptions_total", 0.0))
