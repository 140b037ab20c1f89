"""Preemption-aware elastic training: drain protocol, restart
hardening, chaos SLA.

Covers the graceful half of elasticity end to end: the signal plane
(``ctl_drain_node`` -> unschedulable node), the train drain path (urgent
checkpoint flush -> planned downsize booking ~0 lost work), serve
replica evacuation, the restart-hardening knobs (rolling failure
window, bounded backoff, crash-loop circuit breaker), and the tier-1
drain SLA: under the same chaos schedule, a graceful drain loses <= 25%
of the work an ungraceful kill loses.
"""

from __future__ import annotations

import tempfile
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private.api import _control
from ray_tpu.cluster_utils import Cluster
from ray_tpu.devtools.chaos import ChaosRunner, ChaosSchedule
from ray_tpu.train import (CheckpointConfig, CrashLoopError, FailureConfig,
                           JaxTrainer, RunConfig, ScalingConfig)

WORKER_ENV = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}


# -- signal plane -----------------------------------------------------------


class TestDrainSignalPlane:
    def test_drain_makes_node_unschedulable_and_undrain_reverts(
            self, ray_start_isolated):
        rt = ray_start_isolated
        nodes = _control("nodes")
        assert len(nodes) == 1
        hexid = nodes[0]["node_id"]
        assert nodes[0]["draining"] is False
        assert ray_tpu.available_resources().get("CPU", 0) > 0

        assert _control("drain_node", hexid, 30.0, "test-preempt") is True
        rec = next(n for n in _control("nodes") if n["node_id"] == hexid)
        assert rec["draining"] is True
        assert rec["drain_reason"] == "test-preempt"
        assert 0 < rec["drain_remaining_s"] <= 30.0
        # Schedulable capacity excludes the draining node entirely.
        assert ray_tpu.available_resources().get("CPU", 0) == 0

        # New leases don't land on it: a task submitted now stays queued.
        @ray_tpu.remote
        def probe():
            return "ran"

        ref = probe.remote()
        done, _ = ray_tpu.wait([ref], num_returns=1, timeout=1.0)
        assert not done, "task was scheduled onto a draining node"

        # Undrain lifts the fence and the queued task runs.
        assert _control("undrain_node", hexid) is True
        assert ray_tpu.get(ref, timeout=30) == "ran"
        rec = next(n for n in _control("nodes") if n["node_id"] == hexid)
        assert rec["draining"] is False
        assert rt is not None

    def test_drain_refuses_unknown_node(self, ray_start_isolated):
        assert _control("drain_node", "00" * 16, 10.0, "x") is False
        assert _control("drain_node", "not-hex", 10.0, "x") is False
        assert _control("undrain_node", "00" * 16) is False


class TestDrainRestSurface:
    def test_drain_endpoint_round_trip(self, ray_start_isolated):
        """The REST surface `ray-tpu drain` drives: POST drain -> node
        DRAINING in /api/cluster/status with remaining budget, POST
        undrain reverts, unknown node -> 404."""
        import json
        import urllib.error
        import urllib.request

        from ray_tpu.job_submission.manager import JobManager
        from ray_tpu.job_submission.server import JobServer

        server = JobServer(JobManager(), port=0)
        try:
            base = server.address

            def status_nodes():
                with urllib.request.urlopen(
                        base + "/api/cluster/status") as r:
                    return json.load(r)["nodes"]

            hexid = status_nodes()[0]["node_id"]
            req = urllib.request.Request(
                base + "/api/cluster/drain_node?node_id="
                + hexid + "&deadline_s=20&reason=resttest",
                method="POST")
            with urllib.request.urlopen(req) as r:
                assert json.load(r) == {"ok": True}
            rec = status_nodes()[0]
            assert rec["draining"] is True
            assert rec["drain_reason"] == "resttest"
            assert 0 < rec["drain_remaining_s"] <= 20.0
            req = urllib.request.Request(
                base + "/api/cluster/drain_node?node_id="
                + hexid + "&undrain=1", method="POST")
            with urllib.request.urlopen(req) as r:
                assert json.load(r) == {"ok": True}
            assert status_nodes()[0]["draining"] is False
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    base + "/api/cluster/drain_node?node_id=ffff",
                    method="POST"))
            assert ei.value.code == 404
        finally:
            server.stop()


# -- restart hardening ------------------------------------------------------


def _dying_train_fn(config):
    """Reports a couple of steps, then dies — every incarnation — until
    the marker directory has ``survive_after`` corpses."""
    import os
    import time as _t

    import ray_tpu.train as train

    marker_dir = config["marker_dir"]
    for step in range(3):
        _t.sleep(config.get("step_time", 0.05))
        train.report({"step": step + 1})
    deaths = len(os.listdir(marker_dir))
    if deaths < config["die_times"]:
        open(os.path.join(marker_dir, f"d{deaths}"), "w").close()
        if config.get("sleep_before_death_s"):
            _t.sleep(config["sleep_before_death_s"])
        os._exit(1)


def _raising_train_fn(config=None):
    import ray_tpu.train as train
    train.report({"step": 1})
    raise ValueError("deterministic bug: tensor shape mismatch")


class TestRestartHardening:
    def _trainer(self, fn, config, failure_config, tmp):
        return JaxTrainer(
            fn, train_loop_config=config,
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="harden", storage_path=tmp,
                failure_config=failure_config))

    def test_failure_window_lets_spread_out_failures_pass(
            self, ray_start_isolated, tmp_path):
        """3 deaths with >~1.5s between them against max_failures=1 +
        failure_window_s=1.0: each failure ages out of the window before
        the next lands, so the run completes — where the lifetime
        counter would have killed it at death #2 (control case)."""
        marker = tmp_path / "m1"
        marker.mkdir()
        res = self._trainer(
            _dying_train_fn,
            {"marker_dir": str(marker), "die_times": 3,
             "step_time": 0.15, "sleep_before_death_s": 1.2},
            FailureConfig(max_failures=1, failure_window_s=1.0,
                          restart_backoff_initial_s=0.5,
                          restart_backoff_reset_s=0.0),
            str(tmp_path)).fit()
        assert res.error is None, res.error
        assert res.num_failures == 3  # total is still reported

        marker2 = tmp_path / "m2"
        marker2.mkdir()
        res2 = self._trainer(
            _dying_train_fn,
            {"marker_dir": str(marker2), "die_times": 3,
             "step_time": 0.15, "sleep_before_death_s": 1.2},
            FailureConfig(max_failures=1,
                          restart_backoff_initial_s=0.1),
            str(tmp_path)).fit()
        assert res2.error is not None  # lifetime budget: dead at #2
        assert res2.num_failures == 2

    def test_restart_backoff_is_bounded_exponential(
            self, ray_start_isolated, tmp_path):
        """Two restarts with initial=0.3 factor=2 cap=0.5: the observed
        backoff histogram must hold exactly [0.3, 0.5] (the second delay
        is CAPPED, not 0.6) — asserted from the telemetry series the
        catalog locks."""
        from ray_tpu.util import metrics as mmod

        def series(suffix):
            for line in mmod.prometheus_text().splitlines():
                if line.startswith(
                        "ray_tpu_train_restart_backoff_seconds" + suffix):
                    return float(line.split()[-1])
            return 0.0

        count0 = series("_count")
        sum0 = series("_sum")
        marker = tmp_path / "mb"
        marker.mkdir()
        res = self._trainer(
            _dying_train_fn,
            {"marker_dir": str(marker), "die_times": 2,
             "step_time": 0.05},
            FailureConfig(max_failures=2,
                          restart_backoff_initial_s=0.3,
                          restart_backoff_factor=2.0,
                          restart_backoff_max_s=0.5,
                          restart_backoff_reset_s=3600.0),
            str(tmp_path)).fit()
        assert res.error is None, res.error
        assert res.num_failures == 2
        assert series("_count") - count0 == 2
        assert series("_sum") - sum0 == pytest.approx(0.3 + 0.5, abs=0.01)

    def test_crash_loop_circuit_breaker_fails_fast_with_diagnosis(
            self, ray_start_isolated, tmp_path):
        """A deterministic exception recurring immediately must trip the
        breaker at the threshold — NOT burn the whole (large) failure
        budget — and surface a CrashLoopError naming the signature."""
        import os
        res = self._trainer(
            _raising_train_fn, None,
            FailureConfig(max_failures=50, crash_loop_threshold=2,
                          restart_backoff_initial_s=0.1),
            str(tmp_path)).fit()
        assert isinstance(res.error, CrashLoopError), res.error
        assert res.num_failures == 2  # threshold, not 51
        assert "ValueError" in res.error.signature
        assert "shape mismatch" in res.error.signature
        assert res.error.count == 2
        # The diagnosis bundle landed on disk with the crash-loop record.
        assert res.error.bundle_path and os.path.isdir(
            res.error.bundle_path)
        import json
        with open(os.path.join(res.error.bundle_path,
                               "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["extra"]["crash_loop"]["signature"] \
            == res.error.signature

    def test_formation_failure_is_restartable_not_fatal(
            self, ray_start_isolated, tmp_path, monkeypatch):
        """A group-formation crash (capacity vanished mid-formation) is
        a budgeted failure — fit() returns it in Result.error once the
        budget is gone, it does not raise out of the control loop."""
        from ray_tpu.train.controller import TrainController
        calls = {"n": 0}
        orig = TrainController._start_group

        def flaky(self, n=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("node died during gang formation")
            return orig(self, n)

        monkeypatch.setattr(TrainController, "_start_group", flaky)

        def ok_fn(config=None):
            import ray_tpu.train as train
            train.report({"step": 1})

        res = self._trainer(
            ok_fn, None,
            FailureConfig(max_failures=1, restart_backoff_initial_s=0.1),
            str(tmp_path)).fit()
        assert res.error is None, res.error
        assert res.num_failures == 1
        assert calls["n"] == 2


# -- watchdog drain suppression ---------------------------------------------


class TestWatchdogDrainSuppression:
    def test_draining_rank_never_trips_hang(self):
        from ray_tpu.train.watchdog import TrainWatchdog, WatchdogConfig
        wd = TrainWatchdog("run", WatchdogConfig(
            hang_deadline_s=0.3, poll_interval_s=0.05,
            capture_stacks=False, write_bundle=False))
        wd.start()
        try:
            wd.note_report(0, time.time(), pid=1,
                           report_mono=time.monotonic(), incarnation="a")
            wd.note_report(1, time.time(), pid=2,
                           report_mono=time.monotonic(), incarnation="b")
            # Rank 0's node is draining: its silence is planned.
            wd.note_drain([0], window_s=5.0)
            deadline = time.monotonic() + 2.5
            while time.monotonic() < deadline and wd.hang_count == 0:
                time.sleep(0.05)
            # Rank 1 (not draining) trips; rank 0 must not.
            assert wd.hang_count == 1
            assert wd.last_verdict["rank"] == 1
        finally:
            wd.stop()

    def test_draining_rank_never_flagged_straggler(self):
        from ray_tpu.train.watchdog import TrainWatchdog, WatchdogConfig
        wd = TrainWatchdog("run", WatchdogConfig(
            straggler_multiple=2.0, min_samples=2, capture_stacks=False,
            write_bundle=False, enabled=True))
        # Build baselines: two healthy ranks at ~0.1s intervals.
        t = 100.0
        for seq in range(4):
            for rank in (0, 1):
                wd.note_report(rank, time.time(), pid=rank,
                               report_mono=t, incarnation=f"i{rank}")
            t += 0.1
        wd.note_drain([0], window_s=30.0)
        before = wd.straggler_count
        # Rank 0 turns 20x slower — during its drain window.
        wd.note_report(0, time.time(), pid=0, report_mono=t + 2.0,
                       incarnation="i0")
        assert wd.straggler_count == before
        # An undrained rank with the same slowdown IS flagged.
        wd.note_report(1, time.time(), pid=1, report_mono=t + 2.0,
                       incarnation="i1")
        assert wd.straggler_count == before + 1


# -- train drain path: chaos SLA (tier-1, fast) -----------------------------


def _make_sla_train_fn():
    # Closure (not a module-level function): pickled by value, so node
    # SERVER workers — which cannot import the test module — can run it.
    def _sla_train_fn(config):
        import time as _t

        import numpy as np

        import ray_tpu.train as train
        from ray_tpu._private.api import _control

        ctx = train.get_context()
        world = ctx.get_world_size()

        def barrier(step):
            # Lockstep like a real SPMD step (collectives sync ranks):
            # without it ranks drift under load, and the all-rank commit
            # can only ever reach the SLOWEST rank's step — which would
            # make "lost work" measure drift, not recovery quality.
            prefix = f"tsync/{ctx.experiment_name}/{step}/"
            _control("kv_put", prefix + str(ctx.get_world_rank()), b"1")
            deadline = _t.monotonic() + 60
            while _t.monotonic() < deadline:
                if len(_control("kv_keys", prefix)) >= world:
                    return
                _t.sleep(0.02)

        state = train.load_checkpoint()
        start = 0 if state is None else int(state["step"])
        w = np.zeros((16,), np.float32) if state is None else state["w"]
        for step in range(start, config["steps"]):
            _t.sleep(config["step_time"])
            w = w + 1.0
            train.save_checkpoint({"w": w, "step": step + 1},
                                  metrics={"step": step + 1})
            train.report({"step": step + 1, "start": start})
            barrier(step)
    return _sla_train_fn


def _lost_steps(reports):
    from collections import Counter
    counts = Counter(r["metrics"]["step"] for r in reports
                     if r["rank"] == 0 and "step" in r["metrics"])
    return sum(c - 1 for c in counts.values() if c > 1)


def _run_with_chaos(cluster, victim, mode, steps, step_time,
                    write_delay, deadline_s, storage,
                    emergency_replica=False):
    """Drive one fit under a chaos schedule armed after real progress."""
    from ray_tpu.train.controller import TrainController
    env = dict(WORKER_ENV,
               RAY_TPU_CKPT_TEST_WRITE_DELAY_S=str(write_delay))
    trainer = JaxTrainer(
        _make_sla_train_fn(),
        train_loop_config={"steps": steps, "step_time": step_time},
        scaling_config=ScalingConfig(
            resources_per_worker={"CPU": 1}, min_workers=1,
            max_workers=2, elastic_check_interval_s=3600,
            env_per_worker=env),
        run_config=RunConfig(
            name=f"sla_{mode}", storage_path=storage,
            failure_config=FailureConfig(
                max_failures=1, restart_backoff_initial_s=0.2),
            checkpoint_config=CheckpointConfig(
                async_save=True, max_inflight=2,
                emergency_replica=emergency_replica)))
    controller = TrainController(trainer._train_fn, trainer._config,
                                 trainer._scaling, trainer._run_config)
    schedule = ChaosSchedule()
    if mode == "graceful":
        schedule.preempt(0.3, victim, deadline_s=deadline_s)
    else:
        schedule.kill(0.3, victim)
    runner = ChaosRunner(cluster, schedule, name=mode)
    box = {}

    def run():
        box["r"] = controller.run()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and t.is_alive():
        if any(r["metrics"].get("step", 0) >= 2
               for r in controller._reports):
            break
        time.sleep(0.1)
    runner.start()
    try:
        t.join(timeout=180)
        assert not t.is_alive(), f"{mode} run wedged"
    finally:
        runner.stop()
    return box["r"]


@pytest.fixture()
def chaos_cluster(monkeypatch):
    monkeypatch.setenv("RAY_TPU_NODE_RECONNECT_GRACE_S", "0")
    c = Cluster(head_num_cpus=0)
    yield c
    c.shutdown()


class TestDrainSLA:
    def test_graceful_drain_beats_ungraceful_kill(self, chaos_cluster):
        """The acceptance SLA at smoke scale: identical preemption
        schedule, graceful (drain notice) vs ungraceful (SIGKILL).
        Graceful must complete with error=None at the reduced world
        size, burn zero failure budget, book the event as a drain, and
        lose <= 25% of the work the kill loses."""
        c = chaos_cluster
        c.add_node(num_cpus=1)
        knobs = dict(steps=14, step_time=0.25, write_delay=0.35,
                     deadline_s=8.0)

        n2 = c.add_node(num_cpus=1)
        store = tempfile.mkdtemp(prefix="sla_g_")
        res_g = _run_with_chaos(c, n2, "graceful", storage=store, **knobs)
        assert res_g.error is None, res_g.error
        assert res_g.metrics["step"] == knobs["steps"]
        assert res_g.num_drains == 1, res_g
        assert res_g.num_failures == 0  # no budget burned
        assert res_g.world_size_history[0] == 2
        assert res_g.world_size_history[-1] == 1  # reduced world
        lost_g = _lost_steps(res_g.all_reports)
        # Urgent flush committed every submitted save: ~0 lost work,
        # booked as restart (planned resize), not "lost".
        assert res_g.goodput["phases_s"].get("lost", 0.0) == \
            pytest.approx(0.0, abs=0.05)

        n3 = c.add_node(num_cpus=1)
        store = tempfile.mkdtemp(prefix="sla_u_")
        res_u = _run_with_chaos(c, n3, "ungraceful", storage=store,
                                **knobs)
        assert res_u.error is None, res_u.error
        assert res_u.metrics["step"] == knobs["steps"]
        assert res_u.num_failures == 1
        lost_u = _lost_steps(res_u.all_reports)
        # The slowed async writer guarantees in-flight (uncommitted)
        # saves at the kill: the crash path must lose real work...
        assert lost_u >= 1
        assert res_u.goodput["phases_s"].get("lost", 0.0) > 0.0
        # ...and the drain SLA holds with margin.
        assert lost_g <= 0.25 * lost_u

    def test_preemption_mid_async_save_flush_and_replica_restore(
            self, chaos_cluster):
        """Satellite chaos case: the notice fires while an async save is
        mid-write (slowed writer).  The urgent flush must commit it
        BEFORE the kill — every manifest on disk verifies, nothing is
        lost — and the downsized restart restores from peer RAM."""
        import ray_tpu.checkpoint as ck
        from ray_tpu.checkpoint import replica as rmod
        from ray_tpu._private import sanitizer
        from ray_tpu.util import metrics as mmod

        c = chaos_cluster
        n1 = c.add_node(num_cpus=1, resources={"pin": 1})
        n2 = c.add_node(num_cpus=1)
        # Pin the replica holder to the SURVIVING node before the
        # controller's ensure_holder runs (get_if_exists finds this one):
        # its RAM must outlive the preempted node for the
        # restore-from-RAM assertion to be deterministic.
        sanitizer.session_scoped(rmod.holder_name("*"))
        holder_cls = ray_tpu.remote(rmod.ReplicaHolder)
        holder = holder_cls.options(name=rmod.holder_name("sla_graceful"),
                                    get_if_exists=True, num_cpus=0,
                                    resources={"pin": 0.001}).remote()
        ray_tpu.get(holder.stats.remote(), timeout=60)  # placed + live

        def replica_restores():
            for line in mmod.prometheus_text().splitlines():
                if line.startswith("ray_tpu_ckpt_replica_restores_total"):
                    return float(line.split()[-1])
            return 0.0

        before = replica_restores()
        store = tempfile.mkdtemp(prefix="sla_mid_")
        res = _run_with_chaos(
            c, n2, "graceful", steps=12, step_time=0.2,
            write_delay=0.4, deadline_s=8.0, storage=store,
            emergency_replica=True)
        assert res.error is None, res.error
        assert res.num_drains == 1, res
        assert res.metrics["step"] == 12
        # Zero re-executed steps: the mid-write save committed under the
        # urgent flush before the node died.
        assert _lost_steps(res.all_reports) == 0
        # Every directory claiming to be a checkpoint verifies deeply.
        import os
        run_dir = os.path.join(store, "sla_graceful")
        committed = [r for r in ck.scan_run_dir(run_dir, deep=True)
                     if r["committed"]]
        assert committed
        for rec in committed:
            assert rec["valid"], rec
        # The post-drain incarnation restored from the peer-RAM replica.
        assert replica_restores() > before, \
            "restore after drain did not prefer peer RAM"
        assert n1.alive


# -- serve replica evacuation ----------------------------------------------


class TestServeDrainEvacuation:
    def test_replicas_move_off_draining_node(self, chaos_cluster):
        """Drain a node hosting a serve replica: the controller must
        unpublish + replace it proactively (reusing the settle-kill
        drain path) on a non-draining node — no crash, no gap at the
        target replica count."""
        from ray_tpu import serve

        c = chaos_cluster
        nodes = [c.add_node(num_cpus=1) for _ in range(3)]

        @serve.deployment(name="echo", num_replicas=2, num_cpus=1)
        class Echo:
            def __call__(self, x):
                return x

        serve.run(Echo.bind(), name="echo")
        try:
            handle = serve.get_deployment_handle("echo")
            assert ray_tpu.get(handle.remote("hi"), timeout=30) == "hi"

            def replica_nodes():
                acts = _control("list_actors",
                                {"class_name": "_ReplicaActor",
                                 "state": "ALIVE"})
                return {a["actor_id"]: a["node_id"] for a in acts}

            # Both replicas ALIVE on distinct nodes (1-CPU nodes force a
            # spread).  Poll: a replica can still be binding/restarting
            # in the instant after serve.run returns under suite load.
            deadline = time.monotonic() + 30
            occupied: set = set()
            while time.monotonic() < deadline:
                occupied = set(replica_nodes().values())
                if len(occupied) == 2:
                    break
                time.sleep(0.2)
            assert len(occupied) == 2, replica_nodes()
            victim_hex = next(iter(occupied))
            victim = next(n for n in nodes if n.node_id == victim_hex)
            assert _control("drain_node", victim.node_id, 30.0,
                            "preemption") is True

            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                placed = replica_nodes()
                live_elsewhere = [a for a, n in placed.items()
                                  if n != victim_hex]
                if len(live_elsewhere) >= 2:
                    break
                time.sleep(0.2)
            placed = replica_nodes()
            assert len([a for a, n in placed.items()
                        if n != victim_hex]) >= 2, placed
            # Still serving through the whole evacuation.
            assert ray_tpu.get(handle.remote("again"), timeout=30) \
                == "again"
        finally:
            serve.shutdown()


# -- instance manager: provider notices -> drain hook ------------------------


class TestProviderPreemptionNotices:
    def _manager(self, provider, hook):
        from ray_tpu.autoscaler.instance_manager import InstanceManager
        return InstanceManager(provider, joined_pids=lambda: {},
                               drain_hook=hook)

    def test_notice_for_joined_instance_fires_drain_hook_once(self):
        from ray_tpu.autoscaler.instance_manager import (FakeCloudProvider,
                                                         JOINED)
        provider = FakeCloudProvider()
        calls = []
        mgr = self._manager(provider,
                            lambda nid, d, r: calls.append((nid, d, r)))
        mgr.reconcile({"tpu": 1})
        mgr.reconcile({"tpu": 1})
        inst = mgr.store.alive()[0]
        inst.ray_node_id = "node-abc"
        mgr.store.upsert(inst, JOINED)

        provider.preempt_notice(inst.cloud_id, deadline_s=25.0)
        mgr.reconcile({"tpu": 1})
        mgr.reconcile({"tpu": 1})  # notices repeat; the drain must not
        assert calls == [("node-abc", 25.0, "preemption")]

    def test_notice_during_boot_window_fires_after_join(self):
        """A reclaim warning landing while the instance is RUNNING (not
        yet JOINED) must not be swallowed: the hook retries until the
        node joins, then drains it — the graceful path survives the
        boot->join race."""
        from ray_tpu.autoscaler.instance_manager import (FakeCloudProvider,
                                                         JOINED, RUNNING)
        provider = FakeCloudProvider()
        calls = []
        mgr = self._manager(provider,
                            lambda nid, d, r: calls.append((nid, d, r)))
        mgr.reconcile({"tpu": 1})
        mgr.reconcile({"tpu": 1})
        inst = mgr.store.alive()[0]
        assert inst.status == RUNNING  # booted, not joined
        provider.preempt_notice(inst.cloud_id, deadline_s=30.0)
        mgr.reconcile({"tpu": 1})
        assert calls == []  # no join yet: nothing to drain
        inst.ray_node_id = "node-late"
        mgr.store.upsert(inst, JOINED)
        mgr.reconcile({"tpu": 1})
        mgr.reconcile({"tpu": 1})
        assert calls == [("node-late", 30.0, "preemption")]

    def test_cloud_lost_instance_counts_preempted(self):
        from ray_tpu.autoscaler import instance_manager as im
        from ray_tpu.autoscaler.instance_manager import (FakeCloudProvider,
                                                         JOINED,
                                                         TERMINATED)
        from ray_tpu.util import metrics as mmod

        def preempted_total():
            for line in mmod.prometheus_text().splitlines():
                if line.startswith("ray_tpu_node_preempted_total"):
                    return float(line.split()[-1])
            return 0.0

        provider = FakeCloudProvider()
        events = []
        mgr = self._manager(provider, lambda *a: None)
        old_export = im._export_node_event
        im._export_node_event = events.append
        try:
            mgr.reconcile({"tpu": 1})
            mgr.reconcile({"tpu": 1})  # second pass binds the cloud_id
            inst = mgr.store.alive()[0]
            assert inst.cloud_id
            inst.ray_node_id = "node-xyz"
            mgr.store.upsert(inst, JOINED)
            before = preempted_total()
            provider.lose_instance(inst.cloud_id)
            mgr.reconcile({"tpu": 1})
            assert inst.status == TERMINATED
            assert preempted_total() == before + 1
            preempt_events = [e for e in events
                              if e.get("state") == "PREEMPTED"]
            assert len(preempt_events) == 1
            assert preempt_events[0]["node_id"] == "node-xyz"
        finally:
            im._export_node_event = old_export

    def test_own_terminate_is_not_a_preemption(self):
        from ray_tpu.autoscaler.instance_manager import (FakeCloudProvider,
                                                         RUNNING)
        from ray_tpu.util import metrics as mmod

        def preempted_total():
            for line in mmod.prometheus_text().splitlines():
                if line.startswith("ray_tpu_node_preempted_total"):
                    return float(line.split()[-1])
            return 0.0

        provider = FakeCloudProvider()
        mgr = self._manager(provider, lambda *a: None)
        mgr.reconcile({"tpu": 1})
        while not any(i.status == RUNNING for i in mgr.store.alive()):
            mgr.reconcile({"tpu": 1})
        before = preempted_total()
        mgr.reconcile({"tpu": 0})  # scale to zero: WE terminate it
        for _ in range(3):
            mgr.reconcile({"tpu": 0})
        assert preempted_total() == before


# -- worker-death bundle tagging --------------------------------------------


class TestPreemptedDeathBundleTag:
    def test_death_on_draining_node_tagged_preempted(
            self, ray_start_isolated):
        """A worker dying on a draining node is the EXPECTED half of a
        preemption: the flight-recorder bundle must say so."""
        import glob
        import json
        import os

        @ray_tpu.remote
        def die_on_signal():
            import os as _os
            import time as _t

            from ray_tpu._private.api import _control as _c
            while _c("kv_get", "chaos/die") is None:
                _t.sleep(0.05)
            _os._exit(1)

        rt = ray_start_isolated
        hexid = _control("nodes")[0]["node_id"]
        # Start the task FIRST (a draining node takes no new leases),
        # then drain, then pull the trigger.
        ref = die_on_signal.remote()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if any(t.get("state") == "RUNNING"
                   for t in _control("list_tasks",
                                     {"name": "die_on_signal"})):
                break
            time.sleep(0.1)
        assert _control("drain_node", hexid, 30.0, "spot-reclaim")
        _control("kv_put", "chaos/die", b"1")
        try:
            with pytest.raises(Exception):
                ray_tpu.get(ref, timeout=60)
        finally:
            _control("kv_del", "chaos/die")
        session = _control("session_dir")
        deadline = time.monotonic() + 15
        bundles = []
        while time.monotonic() < deadline and not bundles:
            bundles = glob.glob(os.path.join(
                session, "debug", "*worker_death_preempted*"))
            time.sleep(0.2)
        assert bundles, "no preempted-tagged death bundle written"
        with open(os.path.join(bundles[0], "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["extra"]["reason"] == "preempted"
        assert manifest["extra"]["node_draining"] is True
        assert rt is not None
