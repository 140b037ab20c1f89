"""State API: programmatic views of cluster state.

Reference: python/ray/util/state/api.py (list_actors:793, list_tasks:1020,
list_nodes, list_objects, list_placement_groups, list_jobs, summarize_*)
served by dashboard/modules/state/state_head.py over GcsTaskManager.  Here
the queries hit the driver runtime's controller + TaskEventBuffer directly
(or over the worker control channel when called inside a task/actor).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

from ray_tpu._private.api import _control
from ray_tpu.util import telemetry as _telemetry


def list_tasks(filters: Optional[List] = None,
               limit: int = 10000, stage: Optional[str] = None,
               min_stage_wait_s: Optional[float] = None,
               **_: Any) -> List[Dict[str, Any]]:
    """Task event records. ``filters`` is a list of (key, "=", value)
    triples like the reference's predicate filters.  ``stage`` (deps|
    queue|dispatch|startup|run) selects tasks by lifecycle stage, and
    ``min_stage_wait_s`` keeps only those that waited at least that
    long entering it — both pushed down server-side."""
    fd = None
    if filters:
        fd = {}
        for key, op, value in filters:
            if op not in ("=", "=="):
                raise ValueError(f"only equality filters supported, got {op}")
            fd[key] = value
    return _control("list_tasks", fd, limit, stage, min_stage_wait_s)


def list_actors(**_: Any) -> List[Dict[str, Any]]:
    return _control("list_actors")


def list_nodes(**_: Any) -> List[Dict[str, Any]]:
    return _control("nodes")


def list_objects(limit: int = 10000, **_: Any) -> List[Dict[str, Any]]:
    return _control("list_objects", limit)


def list_placement_groups(**_: Any) -> List[Dict[str, Any]]:
    return _control("list_placement_groups")


def list_jobs(**_: Any) -> List[Dict[str, Any]]:
    return _control("list_jobs")


def summarize_tasks(states: Optional[List[str]] = None,
                    limit: Optional[int] = None,
                    **_: Any) -> Dict[str, Dict[str, int]]:
    """name -> {state -> count} (reference: api.py summarize_tasks).
    ``states`` restricts to tasks currently in those states and
    ``limit`` caps the scan to the newest N records (server-side)."""
    if states is None and limit is None:
        return _control("summarize_tasks")
    return _control("summarize_tasks", states, limit)


def explain_task(task_id: str) -> Dict[str, Any]:
    """Why is this task still pending — unresolved deps by ObjectID,
    the closest-fit node and its resource gap, the drain fence or
    missing PG bundle that rejected it — or, once placed, why it landed
    on its node (the recorded scheduler decision).  ``task_id`` may be
    a prefix (`ray-tpu task why` rides this)."""
    return _control("explain_task", task_id)


def memory_summary(top_n: int = 10) -> Dict[str, Any]:
    """Cluster-wide object-store occupancy (reference: `ray memory`):
    per-node used/capacity/pinned/spilled bytes with op tallies, the
    directory's top objects by size attributed to their owner node and
    producing task, and leak candidates (sealed-never-read past the TTL,
    pinned by a dead worker incarnation)."""
    return _control("memory_summary", top_n)


def explain_object(object_id: str) -> Dict[str, Any]:
    """Why does this object look the way it does — where it lives
    (directory descriptor + owner node), which task produced it, and its
    store lifecycle from the event ring (spills/restores, pull cost,
    pins and who holds them).  ``object_id`` may be a prefix
    (`ray-tpu obj why` rides this)."""
    return _control("explain_object", object_id)


def store_events(object_id: Optional[str] = None,
                 limit: int = 200) -> Dict[str, Any]:
    """Head store event-ring snapshot: ``{"events", "stats"}`` with
    events newest-last (``objects.json`` in flight-recorder bundles is
    the same snapshot)."""
    return _control("store_events", object_id, limit)


def sched_stats() -> Dict[str, Any]:
    """Live control-plane stats: scheduler queue depths, decision
    totals + trailing decision rates, task-event buffer health."""
    return _control("sched_stats")


def sched_decisions(task_id: Optional[str] = None,
                    limit: int = 200) -> List[Dict[str, Any]]:
    """Recent scheduler decision records from the bounded ring
    (``sched_decisions.json`` in flight-recorder bundles is the same
    snapshot)."""
    return _control("sched_decisions", task_id, limit)


def metrics_query(name: str, window_s: float = 60.0, agg: str = "avg",
                  tags: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Windowed aggregate over the head's metrics time-series store
    (ray_tpu.metricsview): ``agg`` is ``rate | delta | avg | min | max |
    last | pNN`` (percentiles reconstruct from histogram bucket deltas,
    so ``p99`` is the *window's* p99, not the lifetime one).  Returns
    ``{"name", "agg", "window_s", "value", "series", "points"}``."""
    return _control("metrics_query", name, window_s, agg, tags)


def metrics_history(name: str, window_s: float = 300.0,
                    tags: Optional[Dict[str, str]] = None,
                    max_points: int = 240) -> Dict[str, Any]:
    """Recent stored points per matching series as ``[age_s, value]``
    sparkline rows (histograms render inter-point average latency)."""
    return _control("metrics_history", name, window_s, tags, max_points)


def metrics_series() -> List[str]:
    """Series names with history in the head's time-series store."""
    return _control("metrics_series")


def alerts(recent: int = 50) -> Dict[str, Any]:
    """SLO engine status: per-objective state (ok|pending|firing|
    resolved) with fast/slow burn rates, plus the recent transition
    ring (``ray-tpu alerts`` renders this)."""
    return _control("alerts", recent)


def slo_set(objectives: List[Dict[str, Any]]) -> int:
    """Replace the SLO objective set.  Each objective is a spec dict:
    ``{"name", "metric", "agg", "op", "threshold", "tags"?,
    "fast_window_s"?, "slow_window_s"?, "pending_for_s"?,
    "cooldown_s"?}`` (see ray_tpu.metricsview.SloObjective)."""
    return _control("slo_set", objectives)


def slo_list() -> List[Dict[str, Any]]:
    """The registered SLO objective specs."""
    return _control("slo_list")


def summarize_actors(**_: Any) -> Dict[str, Dict[str, int]]:
    out: Dict[str, Dict[str, int]] = {}
    for a in list_actors():
        per = out.setdefault(a.get("class_name") or "<unknown>", {})
        per[a["state"]] = per.get(a["state"], 0) + 1
    return out


def get_task(task_id: str) -> Optional[Dict[str, Any]]:
    """Point lookup: the id is pushed down as an equality filter so the
    control plane never ships the full task table to the client."""
    matches = _control("list_tasks", {"task_id": task_id}, 1)
    return matches[-1] if matches else None


def get_actor(actor_id: str) -> Optional[Dict[str, Any]]:
    """Point lookup via the server-side actor filter (see get_task)."""
    matches = _control("list_actors", {"actor_id": actor_id}, 1)
    return matches[-1] if matches else None


def list_stacks(timeout_s: Optional[float] = None) -> List[Dict[str, Any]]:
    """Cluster-wide stack capture (reference: ``ray stack``): every live
    worker (plus the driver) snapshots ``sys._current_frames()`` and the
    task each thread is executing.  Returns one record per process; use
    ``stack_dump()`` for the full result including unresponsive workers.
    """
    return stack_dump(timeout_s)["stacks"]


def stack_dump(timeout_s: Optional[float] = None) -> Dict[str, Any]:
    """Raw cluster stack dump: ``{"time", "stacks", "unresponsive"}``."""
    if timeout_s is None:
        return _control("stack_dump")
    return _control("stack_dump", timeout_s)


def debug_dump(reason: str = "manual") -> str:
    """Write a postmortem flight-recorder bundle (captured stacks, task
    event tail, export events, metrics snapshot, goodput breakdown) under
    ``<session>/debug/`` and return the bundle path."""
    return _control("debug_dump", reason)


def profile(duration_s: float = 2.0, hz: float = 67.0,
            jax_profile: bool = False,
            timeout_s: Optional[float] = None) -> Dict[str, Any]:
    """On-demand cluster profile (``ray-tpu profile``): every live
    worker plus the driver samples for ``duration_s``; returns
    ``{"path", "trace", "workers", "unresponsive", "num_events"}`` with
    the merged clock-aligned Chrome trace (see ray_tpu.profiler)."""
    return _control("profile", duration_s, hz, jax_profile, timeout_s)


class profile_span(_telemetry.profile_span):
    """Context manager recording a user span onto the timeline
    (reference: ray.profiling / ProfileEvent, core_worker/profile_event.h).

    The framework's own recorder (``telemetry.profile_span``) under the
    category ``"user"``: nesting-aware and re-entrant, buffered and
    shipped in batches from a worker, written to
    ``<session>/trace/spans.jsonl`` at shutdown.

    Example::

        with state.profile_span("load_batch", category="data"):
            ...
    """

    __slots__ = ()

    def __init__(self, name: str, category: str = "user",
                 extra: Optional[Dict[str, Any]] = None):
        super().__init__(name, category, extra)


def timeline(filename: Optional[str] = None) -> str:
    """Chrome-trace JSON of task execution (reference: `ray timeline`,
    _private/state.py:471 chrome_tracing_dump). Returns the JSON string and
    optionally writes it to ``filename``."""
    trace = _control("timeline")
    payload = json.dumps(trace)
    if filename:
        with open(filename, "w") as f:
            f.write(payload)
    return payload
