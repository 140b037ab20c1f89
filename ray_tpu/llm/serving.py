"""LLM serving: the engine as a serve deployment.

Reference analog: serve.llm build_openai_app / VLLMService (reference:
python/ray/serve/llm, llm/_internal/serve/) — a replica owns the engine
(and its chips via ``num_tpus``), requests join the continuous batch, and
the serve layer provides routing/autoscaling/self-healing around it.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .engine import InferenceEngine, SamplingParams

#: Grace past a request's own timeout before the abandon sweep reclaims
#: it: a caller that is *about* to collect its result never races the
#: sweeper.
_ABANDON_GRACE_S = 5.0


def _require_chip_grant() -> None:
    """A replica the framework placed reaches a chip only through a
    grant (``num_tpus >= 1``).  Placed with none on a host that has
    chips, it would take the chip if it happened to be free and the CPU
    if not: refuse, unless ``JAX_PLATFORMS=cpu`` asks for a CPU replica."""
    import os

    from ..accelerators.tpu import TPUAcceleratorManager as tpu
    if "RAY_TPU_WORKER_ID" not in os.environ:
        return  # built directly in the caller's own process
    if tpu.get_current_process_visible_chips() is None \
            and os.environ.get("JAX_PLATFORMS") != "cpu" \
            and tpu.detect_num_chips() > 0:
        raise RuntimeError(
            "LLM replica started without a TPU grant on a host with "
            "chips: deploy with num_tpus=1, or set JAX_PLATFORMS=cpu in "
            "the worker's environment for a CPU replica")


class LLMServer:
    """Deployment callable hosting one InferenceEngine.

    A background thread drives ``engine.step()`` whenever work exists;
    requests block on a per-request event (continuous batching means a
    request joins mid-flight instead of waiting for a batch boundary).
    The drive thread idles on an event kicked at submit (no sleep-poll)
    and is joined by a bounded :meth:`close`.  A periodic sweep cancels
    ABANDONED requests — a caller that vanished leaves its engine slot,
    KV pages, and ``_events``/``_results`` entries reclaimable instead
    of leaked forever.
    """

    def __init__(self, build_params: Callable[[], tuple],
                 engine_options: Optional[Dict[str, Any]] = None):
        from .._private import sanitizer
        from ..accelerators.tpu import init_backend
        from ..util import telemetry

        _require_chip_grant()
        # The replica's own first touch of the backend, before the
        # weights are: seconds to the chip apart from seconds to weights.
        init_backend()
        with telemetry.profile_span("llm_build_params", "llm"):
            params, cfg = build_params()
        with telemetry.profile_span("engine_init", "llm"):
            self.engine = InferenceEngine(params, cfg,
                                          **(engine_options or {}))
        self._results: Dict[int, Any] = {}
        self._events: Dict[int, threading.Event] = {}
        # request id -> monotonic deadline after which the request
        # counts as abandoned (its submitter's own timeout + grace).
        self._deadlines: Dict[int, float] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._work = threading.Event()
        self._last_sweep = 0.0
        self._thread = sanitizer.spawn(self._drive, name="llm-drive")

    def _submit(self, prompt_tokens: List[int], params: SamplingParams,
                timeout_s: float) -> tuple:
        """Register + enqueue one request; kicks the drive thread.
        Returns (request id, its completion event, the Request)."""
        ev = threading.Event()
        with self._lock:
            req = self.engine.submit(list(prompt_tokens), params)
            rid = req.request_id
            self._events[rid] = ev
            self._deadlines[rid] = time.monotonic() + timeout_s \
                + _ABANDON_GRACE_S
        self._work.set()
        return rid, ev, req

    def _forget(self, rid: int) -> None:
        with self._lock:
            self._events.pop(rid, None)
            self._results.pop(rid, None)
            self._deadlines.pop(rid, None)

    def _sweep_abandoned(self) -> None:
        """Cancel requests whose submitter stopped waiting: frees the
        engine slot + pages and drops the bookkeeping entries.
        Throttled — deadlines carry seconds of grace, so an O(pending)
        scan per decode step would be pure hot-loop overhead."""
        now = time.monotonic()
        if now - self._last_sweep < 0.5:
            return
        self._last_sweep = now
        with self._lock:
            stale = [rid for rid, dl in self._deadlines.items()
                     if now > dl]
            for rid in stale:
                self._deadlines.pop(rid, None)
                self._events.pop(rid, None)
                self._results.pop(rid, None)
        for rid in stale:
            self.engine.cancel(rid)

    def _drive(self) -> None:
        while not self._stop.is_set():
            if not self.engine.has_work():
                # Event-kicked idle (no 5 ms busy-poll): submit wakes us
                # instantly; the timeout bounds the abandon sweep lag.
                self._work.wait(timeout=0.5)
                self._work.clear()
                self._sweep_abandoned()
                continue
            for req in self.engine.step():
                with self._lock:
                    # The deadline entry stays until the caller collects
                    # the result: a finished-but-never-claimed result is
                    # exactly the other abandonment shape the sweep must
                    # reclaim (engine.cancel on a finished id is a no-op).
                    ev = self._events.get(req.request_id)
                    if ev is not None:
                        # Only store results someone is waiting for
                        # (abandoned requests would otherwise accumulate).
                        self._results[req.request_id] = req
                if ev is not None:
                    ev.set()
            self._sweep_abandoned()

    def __call__(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """{"prompt_tokens": [...], "max_tokens": N, ...} ->
        {"output_tokens": [...], "finish_reason": ...}"""
        params = SamplingParams.from_body(body)
        timeout_s = float(body.get("timeout_s", 300))
        rid, ev, _req = self._submit(list(body["prompt_tokens"]), params,
                                     timeout_s)
        if not ev.wait(timeout=timeout_s):
            # Abandon cleanly: release the engine slot/pages and drop the
            # bookkeeping so repeated timeouts can't leak.
            self._forget(rid)
            self.engine.cancel(rid)
            return {"error": "generation timed out"}
        with self._lock:
            req = self._results.pop(rid)
            self._events.pop(rid, None)
            self._deadlines.pop(rid, None)
        return {"output_tokens": req.output_tokens,
                "finish_reason": req.finish_reason}

    def stream(self, body: Dict[str, Any]):
        """Token-streaming entry point: yields tokens as the engine emits
        them (served via ``handle.options(stream=True)`` -> a streaming
        actor call, so each token publishes the moment it exists —
        reference: serve.llm streaming chat completions)."""
        import time as _time

        from ..util import telemetry
        params = SamplingParams.from_body(body)
        timeout_s = float(body.get("timeout_s", 300))
        # The Request that _submit created, not a later lookup in
        # ``engine.running``: a request that finished first is gone there.
        rid, ev, req = self._submit(list(body["prompt_tokens"]), params,
                                    timeout_s)
        deadline = _time.monotonic() + timeout_s
        sent = 0
        polls = 0
        try:
            while True:
                done = ev.wait(timeout=0.01)
                polls += 1
                toks = list(req.output_tokens)
                while sent < len(toks):
                    yield {"token": int(toks[sent]), "index": sent}
                    sent += 1
                if done and sent >= len(req.output_tokens):
                    yield {"finish_reason": req.finish_reason,
                           "num_tokens": sent}
                    return
                if _time.monotonic() > deadline:
                    self.engine.cancel(rid)
                    yield {"error": "generation timed out"}
                    return
        finally:
            # How much polling ran beside the engine's loop: one counter,
            # not a span per 10 ms round of every stream.
            telemetry.inc("ray_tpu_llm_stream_polls_total", polls)
            self._forget(rid)
            # A consumer that drops the generator mid-stream
            # (GeneratorExit) must not leave the slot generating to
            # max_tokens: cancel is a no-op if the request already
            # finished, and _forget above removed the sweep's deadline
            # entry so nothing else would ever reclaim it.
            self.engine.cancel(rid)

    def generate_batch(self, prompts: List[List[int]],
                       max_tokens: int = 64) -> List[List[int]]:
        """Offline batch entry point (reference: llm batch stages)."""
        # The caller waits the events SEQUENTIALLY (600 s each), so the
        # k-th request is legitimately uncollected for up to k*600 s —
        # its abandon deadline must cover the whole batch, not one slot.
        evs = [self._submit(list(p), SamplingParams(max_tokens=max_tokens),
                            timeout_s=600.0 * len(prompts))[:2]
               for p in prompts]
        out = []
        for rid, ev in evs:
            finished = ev.wait(timeout=600)
            with self._lock:
                req = self._results.pop(rid, None)
                self._events.pop(rid, None)
                self._deadlines.pop(rid, None)
            if not finished:
                # Give up on this prompt like __call__ does: free its
                # slot/pages now instead of letting it generate to
                # max_tokens for a result nobody will collect.
                self.engine.cancel(rid)
            out.append(req.output_tokens if req else [])
        return out

    def close(self, timeout_s: float = 5.0) -> None:
        """Bounded teardown: stop and JOIN the drive thread (a replica
        teardown that leaves it running is exactly the leak the
        sanitizer gate flags)."""
        self._stop.set()
        self._work.set()
        self._thread.join(timeout_s)

    # Serve replica teardown calls shutdown() when a deployment exposes
    # it; keep the old name as the public alias.
    shutdown = close


def build_llm_deployment(build_params: Callable[[], tuple], *,
                         name: str = "llm",
                         num_replicas: int = 1,
                         num_tpus: int = 0,
                         max_ongoing_requests: int = 64,
                         engine_options: Optional[Dict[str, Any]] = None,
                         autoscaling_config=None):
    """Wrap the engine in a serve deployment (reference:
    serve/llm build_llm_deployment)."""
    from .. import serve

    dep = serve.deployment(
        LLMServer, name=name, num_replicas=num_replicas,
        num_tpus=num_tpus, max_ongoing_requests=max_ongoing_requests,
        autoscaling_config=autoscaling_config)
    return dep.bind(build_params, engine_options)
