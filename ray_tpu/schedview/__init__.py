"""Control-plane telescope: scheduler decision tracing + explanations.

The scheduler is the one subsystem whose failures are invisible by
default: a task that never places just sits in a queue, and nothing in
the task table says WHY.  This package holds the always-on, bounded
instrumentation that answers the two operator questions the reference's
`ray status -v` / autoscaler debug strings answer (reference:
python/ray/autoscaler/_private/util.py demand summaries +
src/ray/raylet/scheduling/ cluster_lease_manager's internal state):

* "why is this task still pending?" — unresolved deps by ObjectID, or
  the closest-fit node and the exact resource gap, or the drain fence /
  missing PG bundle that rejected it;
* "why did it land on node X?" — the recorded placement decision:
  scheduling class, candidate count, per-reason rejection tallies, the
  policy that picked the node, and the attempt number.

Pieces:

* :class:`DecisionRing` — a bounded ring of scheduler decision records
  (hot path = one ``deque.append``; folding into per-task state happens
  lazily at read time, the same trick ``_private/events.py`` uses).
* Reason codes (``R_*``) — the closed vocabulary every rejection is
  tallied under; `ray-tpu task why`, ``state.explain_task()`` and the
  ``sched_decisions.json`` flight-recorder section all speak it.
* ``set_enabled()/enabled()`` — the instrumentation kill switch
  (``RAY_TPU_SCHED_TRACE=0`` for operators who want the last word).
"""

from .decisions import (DecisionRing, R_AFFINITY, R_BUNDLE, R_DRAINING,
                        R_INFEASIBLE, R_INSUFFICIENT, R_NO_NODES,
                        R_PENDING_DEPS, REASON_CODES, enabled, set_enabled)

__all__ = [
    "DecisionRing",
    "REASON_CODES",
    "R_AFFINITY",
    "R_BUNDLE",
    "R_DRAINING",
    "R_INFEASIBLE",
    "R_INSUFFICIENT",
    "R_NO_NODES",
    "R_PENDING_DEPS",
    "enabled",
    "set_enabled",
]
