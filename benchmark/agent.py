"""The benchmark's ear inside the serving replica.

The replica process holds the chip, so only it can trace the device, read
its memory and read the engine's counters.  ``build_params`` (the
benchmark's own callable, which the deployment runs inside the replica)
starts this thread; the driver process talks to it through small files in
the run's temporary directory: ``cmd-<n>.json`` in, ``reply-<n>.json`` out.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time
from typing import Any, Dict, Optional

from benchmark import common

COUNTERS = ("ray_tpu_llm_preemptions_total", "ray_tpu_llm_tokens_total",
            "ray_tpu_llm_requests_finished_total",
            "ray_tpu_llm_prefill_chunks_total")
OCCUPANCY = "ray_tpu_llm_kv_page_occupancy"


def _counters() -> Dict[str, float]:
    from ray_tpu.util import metrics
    out: Dict[str, float] = {}
    for snap in metrics.local_snapshots():
        if snap["name"] in COUNTERS:
            for name, tags, value in snap["samples"]:
                key = name + "".join(f"{{{k}={v}}}"
                                     for k, v in sorted(tags.items()))
                out[key] = out.get(key, 0.0) + value
    return out


def _occupancy() -> float:
    from ray_tpu.util import telemetry
    samples = telemetry.gauge(OCCUPANCY).snapshot()["samples"]
    return max((v for _n, _t, v in samples), default=0.0)


class Agent:
    def __init__(self, run_dir: str, device: Dict[str, Any]):
        self.run_dir, self.device = run_dir, device
        self.base: Dict[str, float] = {}
        self.peak = 0.0
        self.trace_at: Optional[float] = None
        self.trace_s = 0.0
        self.trace_dir: Optional[str] = None
        self.trace_wall = None
        self.tracing = False

    def start(self) -> None:
        threading.Thread(target=self._loop, daemon=True,
                         name="bench-agent").start()

    def _reply(self, n: str, body: Dict[str, Any]) -> None:
        tmp = os.path.join(self.run_dir, f".reply-{n}.tmp")
        with open(tmp, "w") as f:
            json.dump(body, f)
        os.replace(tmp, os.path.join(self.run_dir, f"reply-{n}.json"))

    def _begin(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        self.base, self.peak = _counters(), 0.0
        self.trace_at, self.trace_s = cmd.get("trace_at"), cmd.get(
            "trace_s", 0.0)
        return {"ok": True}

    def _end(self) -> Dict[str, Any]:
        import jax
        if self.tracing:
            self._stop_trace()
        now = _counters()
        out = {"device": self.device,
               "counters": {k: v - self.base.get(k, 0.0)
                            for k, v in now.items()},
               "kv_occupancy_peak": self.peak,
               "memory_peak_bytes": common.memory_peak_bytes(),
               "compile_cache": jax.config.jax_compilation_cache_dir}
        if self.trace_wall:
            from benchmark import trace
            out["trace"] = trace.reduce(trace.load(
                trace.find_xplane(self.trace_dir)))
            out["trace_wall"] = self.trace_wall
        return out

    def _stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()
        self.tracing = False
        self.trace_wall = (self.trace_wall, common.now())

    def _loop(self) -> None:
        from benchmark import trace
        while True:
            for path in sorted(glob.glob(
                    os.path.join(self.run_dir, "cmd-*.json"))):
                n = os.path.basename(path)[4:-5]
                with open(path) as f:
                    cmd = json.load(f)
                os.remove(path)
                if cmd["op"] == "begin":
                    self._reply(n, self._begin(cmd))
                elif cmd["op"] == "end":
                    self._reply(n, self._end())
                    return
            self.peak = max(self.peak, _occupancy())
            t = common.now()
            if self.trace_at and not self.tracing and not self.trace_wall \
                    and t >= self.trace_at:
                self.trace_dir = os.path.join(self.run_dir, "trace")
                self.trace_wall = common.now()
                trace.start(self.trace_dir)
                self.tracing = True
            elif self.tracing and t >= self.trace_wall + self.trace_s:
                self._stop_trace()
            time.sleep(0.02)


def ask(run_dir: str, n: int, cmd: Dict[str, Any],
        timeout_s: float = 180.0) -> Dict[str, Any]:
    """Driver side: send one command and wait for its reply."""
    tmp = os.path.join(run_dir, f".cmd-{n}.tmp")
    with open(tmp, "w") as f:
        json.dump(cmd, f)
    os.replace(tmp, os.path.join(run_dir, f"cmd-{n}.json"))
    reply = os.path.join(run_dir, f"reply-{n}.json")
    deadline = time.monotonic() + timeout_s
    while not os.path.exists(reply):
        if time.monotonic() > deadline:
            raise TimeoutError(f"the replica's agent did not answer {cmd}")
        time.sleep(0.02)
    with open(reply) as f:
        return json.load(f)
