"""Peak share of the cache's pages in use (``ray_tpu_llm_kv_page_occupancy``,
sampled every 20 ms inside the replica)."""


def read(facts):
    peak = facts.get("kv_occupancy_peak")
    return None if peak is None else 100.0 * peak
