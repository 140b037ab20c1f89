"""Goodput-driven autoscaling + spot-fleet elasticity.

Policy unit tests (pure decision logic), the seeded spot-market schedule
generator, the checked-in BENCH_spotfleet.json SLA gate, and the tier-1
smoke of ``bench.py --spec spotfleet --fast`` (bounded runtime).
"""

from __future__ import annotations

import json
import os
import sys

import pytest

from ray_tpu.autoscaler import (GoodputAutoscalePolicy,
                                GoodputPolicyConfig)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


class TestSpotfleetBenchGate:
    """The checked-in BENCH_spotfleet.json is the elasticity-SLA
    baseline: it must hold its own SLA, and the --compare gate must
    treat its metrics as gateable (directions resolve)."""

    def _load(self):
        path = os.path.join(REPO_ROOT, "BENCH_spotfleet.json")
        assert os.path.exists(path), \
            "BENCH_spotfleet.json baseline missing"
        with open(path) as f:
            return path, json.load(f)

    def test_checked_in_baseline_holds_sla(self):
        _path, doc = self._load()
        sla = doc["sla"]
        assert sla["pass"] is True
        assert sla["floor_held"] and sla["beats_naive_goodput"]
        assert sla["lost_under_budget"] and sla["beats_naive_lost_steps"]
        assert sla["prebuy_before_deadline"]
        assert sla["multislice_survivor_committed"]
        assert sla["multislice_zero_lost_steps"]
        g = doc["churn"]["graceful"]
        n = doc["churn"]["naive"]
        assert g["scaled_goodput"] > n["scaled_goodput"]
        assert g["lost_steps"] <= n["lost_steps"]
        assert g["prebuy_total"] >= 1

    def test_compare_gate_covers_spotfleet_metrics(self):
        sys.path.insert(0, REPO_ROOT)
        import bench
        path, doc = self._load()
        out = bench.compare_bench(path, path, threshold=0.10)
        assert not out["regressions"]
        # The SLA booleans and goodput numbers actually gate (present in
        # the checked set), so a silently eroded rerun would fail.
        flat = bench._flatten_bench(doc)
        gated = [p for p in flat
                 if bench._metric_direction(p) is not None]
        assert any("scaled_goodput" in p for p in gated)
        assert any(p.endswith("sla.pass") for p in gated)


class TestAutoscalerStatusPublish:
    def test_reconcile_publishes_prebuy_status_to_kv(self):
        """The reconcile loop drops its live view (pending pre-buys,
        prebuy total, policy state) into the head KV under
        AUTOSCALER_KV_KEY — what `ray-tpu status` and
        /api/cluster/status print next to the goodput line."""
        import time

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


class TestSpotfleetSmoke:
    # SLA axes that measure wall-clock goodput of the chaos scenarios.
    # On a loaded single-core host these dip without any code
    # regression (replacement boot + join competes with the training
    # loop for the same CPU), so they get ONE retry.  Everything else
    # in the SLA is deterministic and must hold on every attempt.
    _LOAD_SENSITIVE = ("floor_held",)

    def test_fast_bench_end_to_end(self, tmp_path):
        """`bench.py --spec spotfleet --fast` wired into tier-1 as a
        smoke: the full three-scenario run (churn graceful-vs-naive,
        pre-buy timing, 2-slice drain) in a SUBPROCESS with a hard wall
        bound, so even a pathological stall cannot eat the tier-1
        budget."""
        import subprocess

        out = str(tmp_path / "BENCH_spotfleet.json")
        code = (
            "import bench, json, sys\n"
            f"doc = bench.bench_spotfleet(fast=True, out_path={out!r})\n"
            "print('SLA_PASS', doc['sla']['pass'])\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
        for attempt in (1, 2):
            if os.path.exists(out):
                os.remove(out)  # never judge a stale doc
            proc = subprocess.run(
                [sys.executable, "-u", "-c", code], cwd=REPO_ROOT,
                env=env, capture_output=True, text=True, timeout=420)
            # bench_spotfleet raises SystemExit(1) on an SLA fail but
            # still writes the doc; anything else (crash, no doc) is a
            # hard failure with no retry.
            assert os.path.exists(out), \
                f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n" \
                f"{proc.stderr[-4000:]}"
            with open(out) as f:
                doc = json.load(f)
            sla = doc["sla"]
            assert doc["churn"]["graceful"]["completed"]
            assert doc["churn"]["naive"]["completed"]
            assert sla["lost_under_budget"], sla
            assert sla["prebuy_before_deadline"], sla
            assert sla["multislice_survivor_committed"], sla
            assert sla["multislice_zero_lost_steps"], sla
            if sla["pass"]:
                assert proc.returncode == 0, \
                    f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n" \
                    f"{proc.stderr[-4000:]}"
                break
            failed = [k for k in self._LOAD_SENSITIVE if not sla[k]]
            assert failed, f"SLA failed outside load-sensitive axes: {sla}"
            assert attempt == 1, \
                f"goodput SLA failed on both attempts: {sla}"
        assert "SLA_PASS True" in proc.stdout
        assert doc["sla"]["pass"] is True


class TestSpotfleetSmokeQuick:
    def test_prebuy_timing_scenario(self):
        """The deterministic slice of the bench (declarative
        InstanceManager pre-buy) runs in tier-1 directly: replacement
        REQUESTED at notice time, RUNNING before the deadline."""
        sys.path.insert(0, REPO_ROOT)
        import bench
        out = bench._spotfleet_prebuy_timing()
        assert out["replacement_running_before_deadline"]
        assert out["notice_to_request_s"] is not None
        assert out["notice_to_request_s"] < 1.0
        assert out["notice_to_running_s"] < out["deadline_s"]
