"""Goodput-driven autoscaling + spot-fleet elasticity.

Policy unit tests (pure decision logic), the seeded spot-market schedule
generator, the autoscaler's published status, and the declarative
InstanceManager's pre-buy timing.
"""

from __future__ import annotations

import json
import time

import pytest

from ray_tpu.autoscaler import (GoodputAutoscalePolicy,
                                GoodputPolicyConfig)


class TestGoodputPolicy:
    def test_prebuy_fires_once_per_victim(self):
        p = GoodputAutoscalePolicy(GoodputPolicyConfig(
            default_node_type="spot"))
        d1 = p.decide([("n1", "spot")], pending=0, now=0.0)
        assert len(d1) == 1
        assert d1[0].reason == "prebuy" and d1[0].victim == "n1"
        assert d1[0].node_type == "spot" and d1[0].count == 1
        # The notice repeats every tick until the node dies; the buy
        # must not.
        assert p.decide([("n1", "spot")], pending=1, now=0.5) == []
        assert p.decide([("n1", "spot")], pending=0, now=1.0) == []
        # Victim died (notice gone); a NEW victim buys again.
        d2 = p.decide([("n2", None)], pending=0, now=2.0)
        assert len(d2) == 1 and d2[0].victim == "n2"
        # node_type falls back to the configured default.
        assert d2[0].node_type == "spot"

    def test_notice_storm_bounded_by_max_pending(self):
        p = GoodputAutoscalePolicy(GoodputPolicyConfig(
            max_pending_prebuys=2))
        notices = [("a", None), ("b", None), ("c", None)]
        d = p.decide(notices, pending=0, now=0.0)
        assert len(d) == 2  # storm bound
        # Once those buys join (pending back to 0) the remaining victim,
        # still noticed, gets its replacement.
        d2 = p.decide([("c", None)], pending=0, now=1.0)
        assert len(d2) == 1 and d2[0].victim == "c"

    def test_cancelled_drain_can_rebuy_later(self):
        p = GoodputAutoscalePolicy(GoodputPolicyConfig())
        assert len(p.decide([("n1", None)], 0, now=0.0)) == 1
        # Notice vanishes (cancelled), then re-notices: buys again.
        assert p.decide([], 0, now=1.0) == []
        assert len(p.decide([("n1", None)], 0, now=2.0)) == 1

    def test_goodput_sag_buys_after_sustain_then_cooldown_gates(self):
        p = GoodputAutoscalePolicy(GoodputPolicyConfig(
            goodput_floor=0.5, sustain_s=2.0, cooldown_s=10.0,
            window_s=60.0, default_node_type="spot"))
        p.observe_goodput({"productive_s": 1.0, "total_s": 10.0},
                          now=0.0)
        p.observe_goodput({"productive_s": 2.0, "total_s": 20.0},
                          now=1.0)
        # Windowed goodput 0.1 < floor, but not yet sustained.
        assert p.decide([], 0, now=1.0) == []
        p.observe_goodput({"productive_s": 3.0, "total_s": 30.0},
                          now=3.5)
        d = p.decide([], 0, now=3.5)
        assert len(d) == 1 and d[0].reason == "goodput"
        # Cooldown gates the next goodput buy.
        p.observe_goodput({"productive_s": 4.0, "total_s": 40.0},
                          now=5.0)
        assert p.decide([], 0, now=5.0) == []
        # ... until it expires (sag still sustained).
        p.observe_goodput({"productive_s": 5.0, "total_s": 55.0},
                          now=14.0)
        assert len(p.decide([], 0, now=14.0)) == 1

    def test_healthy_goodput_never_buys(self):
        p = GoodputAutoscalePolicy(GoodputPolicyConfig(
            goodput_floor=0.5, sustain_s=0.0))
        p.observe_goodput({"productive_s": 9.0, "total_s": 10.0},
                          now=0.0)
        p.observe_goodput({"productive_s": 18.0, "total_s": 20.0},
                          now=1.0)
        assert p.decide([], 0, now=1.0) == []
        assert p.last_windowed_goodput == pytest.approx(0.9)

    def test_tracker_restart_resets_window(self):
        """A restarted GoodputTracker's cumulative counters reset; the
        negative deltas must start a fresh window, not a phantom sag."""
        p = GoodputAutoscalePolicy(GoodputPolicyConfig(
            goodput_floor=0.9, sustain_s=0.0))
        p.observe_goodput({"productive_s": 50.0, "total_s": 60.0},
                          now=0.0)
        p.observe_goodput({"productive_s": 1.0, "total_s": 2.0},
                          now=1.0)  # new tracker
        assert p.windowed_goodput() is None
        assert p.decide([], 0, now=1.0) == []

    def test_sustained_sag_requires_continuity(self):
        """Goodput recovering above the floor resets the sustain clock."""
        p = GoodputAutoscalePolicy(GoodputPolicyConfig(
            goodput_floor=0.5, sustain_s=5.0, window_s=60.0))
        p.observe_goodput({"productive_s": 0.0, "total_s": 10.0},
                          now=0.0)
        p.observe_goodput({"productive_s": 0.0, "total_s": 12.0},
                          now=2.0)
        assert p.decide([], 0, now=2.0) == []  # sag starts
        # Recovery: productive jumps.
        p.observe_goodput({"productive_s": 10.0, "total_s": 22.0},
                          now=4.0)
        assert p.decide([], 0, now=4.0) == []  # sag cleared
        p.observe_goodput({"productive_s": 10.0, "total_s": 30.0},
                          now=6.0)
        assert p.decide([], 0, now=6.0) == []  # new sag, not sustained


class TestSpotFleetSchedule:
    def test_seed_determinism_and_jitter_bounds(self):
        from ray_tpu.devtools.chaos import ChaosSchedule
        a = ChaosSchedule.spot_fleet(seed=5, rate=0.4, horizon_s=50.0,
                                     deadline_range=(3.0, 7.0),
                                     no_notice_frac=0.2, add_rate=0.1)
        b = ChaosSchedule.spot_fleet(seed=5, rate=0.4, horizon_s=50.0,
                                     deadline_range=(3.0, 7.0),
                                     no_notice_frac=0.2, add_rate=0.1)
        assert [(e.at_s, e.action, e.deadline_s) for e in a.events] == \
            [(e.at_s, e.action, e.deadline_s) for e in b.events]
        kinds = [e.action for e in a.events]
        assert "preempt" in kinds
        assert "add_node" in kinds
        for e in a.events:
            assert 0.0 <= e.at_s < 50.0
            if e.action == "preempt":
                assert 3.0 <= e.deadline_s <= 7.0
                assert e.node is None  # symbolic: resolved at fire time
        # Events are time-ordered (the runner replays them in order).
        assert [e.at_s for e in a.events] == \
            sorted(e.at_s for e in a.events)

    def test_different_seeds_differ(self):
        from ray_tpu.devtools.chaos import ChaosSchedule
        a = ChaosSchedule.spot_fleet(seed=1, rate=0.4, horizon_s=50.0)
        b = ChaosSchedule.spot_fleet(seed=2, rate=0.4, horizon_s=50.0)
        assert [(e.at_s, e.action) for e in a.events] != \
            [(e.at_s, e.action) for e in b.events]


class TestAutoscalerStatusPublish:
    def test_reconcile_publishes_prebuy_status_to_kv(self):
        """The reconcile loop drops its live view (pending pre-buys,
        prebuy total, policy state) into the head KV under
        AUTOSCALER_KV_KEY — what `ray-tpu status` and
        /api/cluster/status print next to the goodput line."""
        import ray_tpu
        from ray_tpu.autoscaler import (AUTOSCALER_KV_KEY, Autoscaler,
                                        AutoscalerConfig,
                                        LocalSubprocessProvider,
                                        NodeTypeConfig)
        rt = ray_tpu.init(num_cpus=0, num_tpus=0, head_port=0,
                          cluster_token=b"sptok")
        try:
            provider = LocalSubprocessProvider(
                rt.head_server.address, b"sptok")
            asc = Autoscaler(rt, provider, AutoscalerConfig(
                node_types={"spot": NodeTypeConfig(
                    resources={"CPU": 1}, max_workers=2)},
                update_interval_s=0.2,
                policy=GoodputAutoscalePolicy(GoodputPolicyConfig(
                    default_node_type="spot"))))
            try:
                doc = None
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline:
                    raw = rt.ctl_kv_get(AUTOSCALER_KV_KEY)
                    if raw:
                        doc = json.loads(raw)
                        break
                    time.sleep(0.1)
                assert doc is not None, "autoscaler status never published"
                assert doc["pending_prebuys"] == 0
                assert doc["prebuy_total"] == 0
                assert doc["policy"]["goodput_floor"] == 0.5
                assert "nodes_by_type" in doc
                st = asc.status()
                assert st["pending_prebuys"] == 0
                assert st["policy"] is not None
            finally:
                asc.stop()
                provider.shutdown()
        finally:
            ray_tpu.shutdown()


def _spotfleet_prebuy_timing() -> dict:
    """Deterministic pre-buy timing over the declarative layer: a
    FakeCloudProvider posts a preemption notice and the InstanceManager
    must REQUEST the replacement on its next pass and have it RUNNING
    before the victim's deadline (provisioning time << deadline here, as
    on a spot market with capacity)."""
    from ray_tpu.autoscaler.instance_manager import (FakeCloudProvider,
                                                     InstanceManager,
                                                     JOINED, RUNNING)

    provider = FakeCloudProvider(run_delay_s=0.4)
    mgr = InstanceManager(provider, drain_hook=lambda *a: None,
                          prebuy=True, max_pending_prebuys=2)
    desired = {"tpu": 2}
    deadline_s = 5.0
    # Converge to steady state.
    t_end = time.monotonic() + 10
    while time.monotonic() < t_end:
        mgr.reconcile(desired)
        insts = [i for i in mgr.store.alive() if i.status == RUNNING]
        if len(insts) == 2:
            break
        time.sleep(0.05)
    victim = next(i for i in mgr.store.alive() if i.status == RUNNING)
    n_before = len(provider.request_log)
    t_notice = time.monotonic()
    provider.preempt_notice(victim.cloud_id, deadline_s=deadline_s)
    t_request = t_running = None
    t_end = time.monotonic() + deadline_s + 5
    while time.monotonic() < t_end:
        mgr.reconcile(desired)
        if t_request is None and len(provider.request_log) > n_before:
            t_request = time.monotonic()
        fresh = [i for i in mgr.store.alive()
                 if i.status in (RUNNING, JOINED)
                 and i.cloud_id != victim.cloud_id
                 and i.instance_id != victim.instance_id
                 and i.request_id != victim.request_id]
        if t_request is not None and fresh:
            t_running = time.monotonic()
            break
        time.sleep(0.05)
    # The victim then actually dies; the fleet is already whole.
    provider.lose_instance(victim.cloud_id)
    mgr.reconcile(desired)
    return {
        "deadline_s": deadline_s,
        "notice_to_request_s": round(t_request - t_notice, 3)
        if t_request else None,
        "notice_to_running_s": round(t_running - t_notice, 3)
        if t_running else None,
        "replacement_running_before_deadline":
            t_running is not None
            and (t_running - t_notice) < deadline_s,
    }


class TestSpotfleetSmokeQuick:
    def test_prebuy_timing_scenario(self):
        """The declarative InstanceManager's pre-buy: replacement
        REQUESTED at notice time, RUNNING before the deadline."""
        out = _spotfleet_prebuy_timing()
        assert out["replacement_running_before_deadline"]
        assert out["notice_to_request_s"] is not None
        assert out["notice_to_request_s"] < 1.0
        assert out["notice_to_running_s"] < out["deadline_s"]
