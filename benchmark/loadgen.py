"""Load on the serve handle from the client's side: an open loop on a
wall-clock schedule and a closed loop of waiting clients.

The core is ``ray_tpu/llm/disagg/loadgen.py``'s (arrivals pinned to the
wall clock, drawn before the clock starts); here latency runs from when a
request was *due*, how late the generator sent it is kept, lengths come
from the traffic file, and tokens are timed as they reach the client
through ``handle.options(stream=True)``.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from benchmark import common
from benchmark.traffic import Offered


@dataclass
class Served:
    offered: Offered
    due: Optional[float] = None        # wall clock
    sent: float = 0.0
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    finish_reason: str = ""
    error: str = ""
    done: float = 0.0

    @property
    def ok(self) -> bool:
        return (not self.error and self.finish_reason == "length"
                and len(self.tokens) == self.offered.max_tokens)


def stream_one(handle, rec: Served, get: Callable) -> None:
    """Send one request and time each token as it arrives."""
    body = {"prompt_tokens": rec.offered.prompt,
            "max_tokens": rec.offered.max_tokens, "temperature": 0.0,
            "timeout_s": 120}
    rec.sent = common.now()
    try:
        gen = handle.options(stream=True, method_name="stream").remote(body)
        for ref in gen:
            item = get(ref, timeout=180)
            if "token" in item:
                rec.token_times.append(common.now())
                rec.tokens.append(item["token"])
            elif "error" in item:
                rec.error = str(item["error"])
            else:
                rec.finish_reason = item.get("finish_reason", "")
    except Exception as e:  # noqa: BLE001 - a failed request is a result
        rec.error = f"{type(e).__name__}: {e}"
    rec.done = common.now()


def open_loop(handle, get, offered: List[Offered], workers: int,
              on_start: Callable[[float], None]) -> List[Served]:
    """Send each request when it is due, whether or not earlier ones have
    finished, and wait for all of them."""
    jobs: "queue.Queue" = queue.Queue()

    def work():
        while True:
            rec = jobs.get()
            if rec is None:
                return
            stream_one(handle, rec, get)

    pool = [threading.Thread(target=work, daemon=True, name=f"client-{i}")
            for i in range(workers)]
    for t in pool:
        t.start()
    recs = [Served(o) for o in offered]
    t0 = common.now() + 0.05
    on_start(t0)
    for rec in recs:
        rec.due = t0 + rec.offered.due_s
        wait = rec.due - common.now()
        if wait > 0:
            time.sleep(wait)
        jobs.put(rec)
    for _ in pool:
        jobs.put(None)
    for t in pool:
        t.join(timeout=240)
    return recs


def closed_loop(handle, get, offered: List[Offered], clients: int,
                lead_s: float, seconds: float,
                on_start: Callable[[float], None]) -> List[Served]:
    """``clients`` callers, each sending its next request when its last
    reply ends, until the window closes.  Requests still in flight then are
    left to the replica's shutdown; they are not part of the sample."""
    recs = [Served(o) for o in offered]
    lock, nxt = threading.Lock(), iter(recs)
    t0 = common.now()
    stop_at = t0 + lead_s + seconds
    on_start(t0)

    ran_out = threading.Event()

    def client():
        while common.now() < stop_at:
            with lock:
                rec = next(nxt, None)
            if rec is None:
                ran_out.set()
                return
            stream_one(handle, rec, get)

    pool = [threading.Thread(target=client, daemon=True, name=f"client-{i}")
            for i in range(clients)]
    for t in pool:
        t.start()
    time.sleep(max(0.0, stop_at - common.now()))
    if ran_out.is_set():
        raise RuntimeError("the mix's request list ran out before the "
                           "window closed: raise `requests` in its file")
    return [r for r in recs if r.sent]


def ttft_ms(recs) -> List[float]:
    """Due (or sent, in a closed loop) to first token, per request."""
    return [(r.token_times[0] - (r.due if r.due is not None else r.sent))
            * 1e3 for r in recs if r.token_times]


def tpot_ms(recs) -> List[float]:
    """(last token - first token) / (tokens - 1), per request."""
    return [(r.token_times[-1] - r.token_times[0]) * 1e3
            / (len(r.token_times) - 1)
            for r in recs if len(r.token_times) > 1]
