"""Chaos SLA harness: scripted kill/preempt/add schedules against a
live cluster.

The missing piece between unit-level fault injection (kill one worker at
one hand-picked moment) and a production claim ("graceful drain loses
<= 25% of what an ungraceful kill loses"): a *schedule* of failures
replayed identically against different recovery strategies, so goodput
under preemption is a measured number, not an anecdote.

A :class:`ChaosSchedule` is a list of timed events:

* ``preempt`` — the spot-reclaim sequence: post a drain notice for the
  node, then SIGKILL it when the deadline expires (exactly what a cloud
  does: warning, grace window, gone).
* ``kill``    — ungraceful: SIGKILL the node with no warning.
* ``drain``   — notice only, no kill (maintenance that gets cancelled).
* ``add_node`` — capacity arrives mid-run (elastic upsize fodder).
* ``lose_instance`` — provider-level loss with NO runtime signal (the
  un-noticed spot reclaim): the cloud simply takes the host away.

:class:`ChaosRunner` replays the schedule on a background thread
(``sanitizer.spawn`` — the leak gate covers the harness itself) against
a ``cluster_utils.Cluster`` and/or an autoscaler provider; every applied
event lands in ``runner.log`` with its actual fire time, so a bench/test
can line events up against the goodput timeline.

Stochastic schedules: :meth:`ChaosSchedule.spot_fleet` generates the
continuous-churn spot-market environment from a seed — Poisson-arriving
preemptions with jittered drain deadlines, occasional no-notice kills,
and delayed capacity arrivals.  Events carry ``node=None`` (a symbolic
victim); the runner resolves a live worker at FIRE time, so the same
seeded schedule replays against clusters whose membership churns.

Used by the tier-1 drain-SLA chaos tests.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["ChaosEvent", "ChaosSchedule", "ChaosRunner"]


@dataclass
class ChaosEvent:
    """One scripted fault.  ``node`` is a ``cluster_utils.NodeHandle``
    for kill/preempt (the harness needs the process to SIGKILL), a
    node-id hex for drains/provider-backed kills, or None for "pick a
    live worker at fire time"; ``add_node`` ignores it;
    ``lose_instance`` targets ``cloud_id`` at the provider."""
    at_s: float
    action: str          # preempt | kill | drain | add_node | lose_instance
    node: Any = None
    deadline_s: float = 10.0       # preempt/drain: advertised grace
    reason: str = "chaos"
    num_cpus: float = 2.0          # add_node sizing
    resources: Optional[Dict[str, float]] = None
    cloud_id: Optional[str] = None  # lose_instance target


@dataclass
class ChaosSchedule:
    events: List[ChaosEvent] = field(default_factory=list)

    def preempt(self, at_s: float, node, deadline_s: float = 10.0,
                reason: str = "preemption") -> "ChaosSchedule":
        self.events.append(ChaosEvent(at_s, "preempt", node,
                                      deadline_s=deadline_s,
                                      reason=reason))
        return self

    def kill(self, at_s: float, node) -> "ChaosSchedule":
        self.events.append(ChaosEvent(at_s, "kill", node))
        return self

    def drain(self, at_s: float, node, deadline_s: float = 10.0,
              reason: str = "maintenance") -> "ChaosSchedule":
        self.events.append(ChaosEvent(at_s, "drain", node,
                                      deadline_s=deadline_s,
                                      reason=reason))
        return self

    def add_node(self, at_s: float, num_cpus: float = 2.0,
                 resources: Optional[Dict[str, float]] = None
                 ) -> "ChaosSchedule":
        self.events.append(ChaosEvent(at_s, "add_node", None,
                                      num_cpus=num_cpus,
                                      resources=resources))
        return self

    def lose_instance(self, at_s: float, cloud_id: str
                      ) -> "ChaosSchedule":
        """Provider-level host loss with no runtime signal — the spot
        reclaim that never sent its warning (wired to the provider's
        ``lose_instance``, e.g. FakeCloudProvider's)."""
        self.events.append(ChaosEvent(at_s, "lose_instance", None,
                                      cloud_id=cloud_id))
        return self

    @classmethod
    def spot_fleet(cls, seed: int, rate: float, horizon_s: float, *,
                   deadline_range: Tuple[float, float] = (4.0, 10.0),
                   no_notice_frac: float = 0.25,
                   add_rate: float = 0.0,
                   num_cpus: float = 2.0,
                   resources: Optional[Dict[str, float]] = None
                   ) -> "ChaosSchedule":
        """Seeded stochastic spot-market schedule: preemptions arrive as
        a Poisson process at ``rate`` events/s over ``horizon_s``, each
        with a drain deadline jittered in ``deadline_range``; a
        ``no_notice_frac`` fraction are kills with no warning at all
        (the reclaim whose metadata-server notice never fired); and
        (``add_rate`` > 0) delayed capacity arrivals land as their own
        Poisson stream.  Victims are symbolic (``node=None``) — resolved
        against the live cluster at fire time — so one seed replays
        identically against different recovery policies."""
        rng = random.Random(seed)
        sched = cls()
        if rate > 0:
            t = rng.expovariate(rate)
            while t < horizon_s:
                if rng.random() < no_notice_frac:
                    sched.kill(round(t, 3), None)
                else:
                    sched.preempt(
                        round(t, 3), None,
                        deadline_s=round(rng.uniform(*deadline_range), 3))
                t += rng.expovariate(rate)
        if add_rate > 0:
            t = rng.expovariate(add_rate)
            while t < horizon_s:
                sched.add_node(round(t, 3), num_cpus=num_cpus,
                               resources=resources)
                t += rng.expovariate(add_rate)
        sched.events.sort(key=lambda e: e.at_s)
        return sched


class _SharedVictim:
    """Fire-time victim slot shared by a symbolic preempt's drain and
    kill halves: the drain resolves a live worker and the kill, one
    deadline later, MUST hit the same node.  ``""`` marks "resolution
    skipped" so the kill half skips too."""
    __slots__ = ("hex",)

    def __init__(self):
        self.hex: Optional[str] = None


def _node_hex(node) -> Optional[str]:
    if node is None:
        return None
    if isinstance(node, str):
        return node
    if isinstance(node, _SharedVictim):
        return node.hex or None
    return getattr(node, "node_id", None)


class ChaosRunner:
    """Replays a :class:`ChaosSchedule` against a live cluster.

    ``start()`` arms the schedule (t=0 is the start call); ``stop()``
    cancels anything unfired and joins the harness thread (bounded) —
    chaos threads MUST not outlive the test, the runtime leak sanitizer
    gates on it.

    ``provider`` (an autoscaler NodeProvider / CloudProvider) extends
    the harness to autoscaler-managed fleets: symbolic kills SIGKILL the
    provider process matched by the victim's os_pid, ``lose_instance``
    events call the provider's no-signal loss, and ``add_node`` falls
    back to ``provider.create_node`` when no Cluster is attached.
    ``min_survivors`` spares the last worker(s) from symbolic victim
    picks so a hot schedule cannot erase the whole fleet.
    """

    def __init__(self, cluster, schedule: ChaosSchedule,
                 name: str = "chaos", provider=None,
                 victim_seed: int = 0, min_survivors: int = 1):
        self.cluster = cluster
        self.schedule = schedule
        self.name = name
        self.provider = provider
        self.min_survivors = min_survivors
        self._rng = random.Random(victim_seed)
        #: Applied events: {"at_s": planned, "fired_s": actual,
        #:  "action": ..., "node": hex|None, "ok": bool, "error": str,
        #:  "skipped": str|absent}.
        self.log: List[Dict[str, Any]] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ChaosRunner":
        if self._thread is not None:
            raise RuntimeError("chaos runner already started")
        from .._private import sanitizer
        self._thread = sanitizer.spawn(self._run,
                                       name=f"chaos-{self.name}")
        return self

    def stop(self, timeout: float = 15.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        self._thread = None

    def join(self, timeout: float = 120.0) -> bool:
        """Wait for the whole schedule to finish; True when it did."""
        t = self._thread
        if t is None:
            return True
        t.join(timeout=timeout)
        return not t.is_alive()

    def __enter__(self) -> "ChaosRunner":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- replay ------------------------------------------------------------

    def _expand(self) -> List[ChaosEvent]:
        """preempt = drain now + kill at the deadline: expand so the
        replay loop only handles primitive actions.  A symbolic preempt
        (node=None) gets ONE shared victim slot — whoever the drain
        resolves at fire time is who the kill takes down."""
        out: List[ChaosEvent] = []
        for ev in self.schedule.events:
            if ev.action == "preempt":
                node = _SharedVictim() if ev.node is None else ev.node
                out.append(ChaosEvent(ev.at_s, "drain", node,
                                      deadline_s=ev.deadline_s,
                                      reason=ev.reason))
                out.append(ChaosEvent(ev.at_s + ev.deadline_s, "kill",
                                      node, reason=ev.reason))
            else:
                out.append(ev)
        out.sort(key=lambda e: e.at_s)
        return out

    def _run(self) -> None:
        t0 = time.monotonic()
        for ev in self._expand():
            delay = ev.at_s - (time.monotonic() - t0)
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            rec = {"at_s": ev.at_s,
                   "fired_s": time.monotonic() - t0,
                   "action": ev.action,
                   "node": _node_hex(ev.node),
                   "ok": True, "error": None}
            try:
                info = self._apply(ev)
                if info:
                    rec.update(info)
            except Exception as e:  # noqa: BLE001 — logged, replay goes on
                rec["ok"] = False
                rec["error"] = f"{type(e).__name__}: {e}"
            rec["node"] = rec["node"] or _node_hex(ev.node)
            self.log.append(rec)

    # -- victim resolution ---------------------------------------------------

    def _pick_victim(self) -> Optional[str]:
        """A live, non-head, not-already-draining worker — chosen by the
        runner's own seeded rng over a SORTED id list, so (seed, cluster
        state) fully determines the pick.  None when taking one would
        leave fewer than ``min_survivors`` workers."""
        from .._private.api import _control
        cands = sorted(n["node_id"] for n in _control("nodes")
                       if n["alive"] and not n["is_head"]
                       and not n.get("draining"))
        if self.provider is not None:
            # The runtime's "alive" lags a kill by the reconnect grace
            # window; a ghost candidate would let the picker take the
            # TRUE last survivor.  Only provider-backed processes count.
            cands = [c for c in cands
                     if self._provider_pid_for(c) is not None]
        if len(cands) <= self.min_survivors:
            return None
        return self._rng.choice(cands)

    def _resolve(self, ev: ChaosEvent):
        """Fire-time target resolution: symbolic victims pick a live
        worker; a shared slot resolves once and pins."""
        node = ev.node
        if isinstance(node, _SharedVictim):
            if node.hex is None:
                node.hex = self._pick_victim() or ""
            return node.hex or None
        if node is None and ev.action in ("drain", "kill"):
            return self._pick_victim()
        return node

    def _provider_pid_for(self, hexid: str) -> Optional[str]:
        """Provider id of the node whose runtime registration carries
        the matching os_pid (how autoscaler-launched victims die)."""
        get_pid = getattr(self.provider, "node_os_pid", None)
        if self.provider is None or get_pid is None:
            return None
        from .._private.runtime import driver_runtime
        rt = driver_runtime()
        if rt is None:
            return None
        os_pid = 0
        for n in rt.controller.alive_nodes():
            if n.node_id.hex() == hexid:
                try:
                    os_pid = int(n.labels.get("os_pid", 0))
                except (TypeError, ValueError):
                    pass
                break
        if not os_pid:
            return None
        for pid in self.provider.non_terminated_nodes():
            if get_pid(pid) == os_pid:
                return pid
        return None

    def _apply(self, ev: ChaosEvent) -> Optional[Dict[str, Any]]:
        from .._private.api import _control
        target = self._resolve(ev)
        if ev.action == "drain":
            hexid = _node_hex(target)
            if ev.node is not None and not isinstance(
                    ev.node, _SharedVictim) and not hexid:
                raise ValueError("drain target has no node_id")
            if not hexid:
                return {"skipped": "no eligible victim"}
            if not _control("drain_node", hexid, ev.deadline_s,
                            ev.reason):
                raise RuntimeError(f"drain_node({hexid[:12]}) refused")
            return {"node": hexid}
        elif ev.action == "kill":
            # The cloud's reclaim: SIGKILL the node process group (takes
            # its workers with it) — no goodbye on any channel.
            if target is None:
                return {"skipped": "no eligible victim"}
            if isinstance(target, str):
                pid = self._provider_pid_for(target)
                if pid is not None:
                    self.provider.terminate_node(pid)
                    return {"node": target, "provider_id": pid}
                handle = self._cluster_handle_for(target)
                if handle is None:
                    return {"node": target,
                            "skipped": "victim already gone"}
                target = handle
            if target.alive:
                self.cluster.remove_node(target, wait_dead=True)
            return {"node": _node_hex(target)}
        elif ev.action == "add_node":
            if self.cluster is not None:
                self.cluster.add_node(num_cpus=ev.num_cpus,
                                      resources=ev.resources)
            elif self.provider is not None:
                res = dict(ev.resources or {})
                res.setdefault("CPU", ev.num_cpus)
                pid = self.provider.create_node("chaos-add", res)
                return {"provider_id": pid}
            else:
                raise ValueError("add_node needs a cluster or provider")
        elif ev.action == "lose_instance":
            lose = getattr(self.provider, "lose_instance", None)
            if lose is None:
                raise ValueError(
                    "lose_instance needs a provider exposing "
                    "lose_instance (FakeCloudProvider / "
                    "LocalSubprocessProvider)")
            cid = ev.cloud_id or _node_hex(ev.node)
            if not cid:
                raise ValueError("lose_instance target has no cloud_id")
            lose(cid)
            return {"cloud_id": cid}
        else:
            raise ValueError(f"unknown chaos action {ev.action!r}")
        return None

    def _cluster_handle_for(self, hexid: str):
        if self.cluster is None:
            return None
        for h in getattr(self.cluster, "_nodes", []):
            if h.node_id == hexid and h.alive:
                return h
        return None
