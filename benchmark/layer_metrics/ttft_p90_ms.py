"""The first-token tail as a per-layer metric, for cells that are not
judged on it (long prompts, where a run holds too few requests)."""

from benchmark.end_to_end.ttft_p90_ms import read  # noqa: F401
