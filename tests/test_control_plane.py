"""Control-plane telescope: scheduler decision ring, explain(), the
lifecycle stage attribution, the `ray-tpu sched` / `ray-tpu task why`
CLIs.

The offline harness half runs a REAL ClusterScheduler against fake
NodeInfos (no workers), so every reason code — pending_deps, infeasible,
draining, bundle_unavailable — is asserted end to end without a cluster;
the live half drives the same answers through the job-server REST
surface and the click CLIs.
"""

from __future__ import annotations

import json
import os
import time

import pytest


def _wait_for(predicate, timeout_s: float = 10.0, interval_s: float = 0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        out = predicate()
        if out:
            return out
        time.sleep(interval_s)
    raise AssertionError("condition not met within timeout")


class _SchedHarness:
    """Offline scheduler: a real ClusterScheduler + Controller with N
    **fake NodeInfos injected** — no worker processes, so what is asserted
    is pure control-plane (placement policy + queue machinery)."""

    def __init__(self, num_nodes: int, cpus_per_node: float = 16.0):
        from ray_tpu._private.controller import Controller, NodeInfo
        from ray_tpu._private.ids import NodeID
        from ray_tpu._private.resources import ResourceSet
        from ray_tpu._private.scheduler import ClusterScheduler
        self.num_nodes = num_nodes
        self.cpus_per_node = cpus_per_node
        self.pending_objects: set = set()  # ObjectIDs NOT yet ready
        self.controller = Controller()
        self.sched = ClusterScheduler(
            self.controller, lambda oid: oid not in self.pending_objects)
        self.node_ids = []
        for i in range(num_nodes):
            nid = NodeID((i + 1).to_bytes(NodeID.SIZE, "little"))
            self.node_ids.append(nid)
            self.sched.add_node(NodeInfo(
                nid, f"fake-{i}", ResourceSet({"CPU": cpus_per_node})))

    def make_spec(self, i: int, resources=None, deps=(), pg=None,
                  bundle_index=-1, name="bench_task"):
        from ray_tpu._private.ids import TaskID
        from ray_tpu._private.protocol import TaskSpec
        from ray_tpu._private.resources import ResourceSet
        return TaskSpec(
            task_id=TaskID((i + 1).to_bytes(TaskID.SIZE, "little")),
            name=name, fn_blob=None, method_name=None,
            arg_descs=[("ref", d) for d in deps], kwarg_descs={},
            return_ids=[],
            resources=ResourceSet(resources or {"CPU": 1.0}),
            placement_group=pg, bundle_index=bundle_index)

    def make_object_id(self, i: int):
        from ray_tpu._private.ids import ObjectID
        return ObjectID((i + 1).to_bytes(ObjectID.SIZE, "little"))

    def close(self):
        self.sched.stop()


@pytest.fixture()
def harness():
    made = []

    def make(num_nodes, cpus_per_node=4.0):
        h = _SchedHarness(num_nodes, cpus_per_node=cpus_per_node)
        made.append(h)
        return h

    yield make
    for h in made:
        h.close()


class TestDecisionRingAndExplain:
    def test_placed_task_records_decision(self, harness):
        h = harness(3)
        placed = []
        h.sched.submit(h.make_spec(1), lambda s, n: placed.append(n))
        _wait_for(lambda: placed)
        rec = h.sched.ring.latest_for(h.make_spec(1).task_id.hex())
        assert rec is not None
        assert rec["kind"] in ("inline", "loop")
        assert rec["node_id"] == placed[0].hex()
        assert rec["attempt"] == 1
        assert rec["candidates"] >= 1
        assert "CPU:1" in rec["sched_class"]

    def test_pending_deps_explains_unresolved_objects(self, harness):
        h = harness(2)
        dep = h.make_object_id(7)
        h.pending_objects.add(dep)
        spec = h.make_spec(1, deps=(dep,))
        h.sched.submit(spec, lambda s, n: None)
        out = h.sched.explain_task(spec.task_id)
        assert out["status"] == "pending_deps"
        assert out["reasons"] == ["pending_deps"]
        assert out["unresolved_deps"] == [dep.hex()]

    def test_infeasible_parks_and_explains_with_gap(self, harness):
        h = harness(2)  # 4 CPUs per node, no GPU anywhere
        spec = h.make_spec(1, resources={"CPU": 1.0, "GPU": 2.0})
        h.sched.submit(spec, lambda s, n: None)
        # The loop parks the class as infeasible (not rescanned per wake).
        _wait_for(lambda: h.sched.queue_depths()["infeasible"] == 1)
        out = h.sched.explain_task(spec.task_id)
        assert out["status"] == "infeasible"
        assert "infeasible" in out["reasons"]
        assert out["closest_fit"]["gap"] == {"GPU": 2.0}
        # The ring carries the reject + park decisions.
        rec = h.sched.ring.latest_for(spec.task_id.hex())
        assert rec["kind"] in ("reject", "infeasible")
        assert rec["rejected"].get("infeasible")

    def test_infeasible_revived_by_add_node(self, harness):
        from ray_tpu._private.controller import NodeInfo
        from ray_tpu._private.ids import NodeID
        from ray_tpu._private.resources import ResourceSet
        h = harness(1)
        spec = h.make_spec(1, resources={"GPU": 1.0})
        placed = []
        h.sched.submit(spec, lambda s, n: placed.append(n))
        _wait_for(lambda: h.sched.queue_depths()["infeasible"] == 1)
        h.sched.add_node(NodeInfo(
            NodeID(b"\x99" * NodeID.SIZE), "gpu-node",
            ResourceSet({"CPU": 4.0, "GPU": 2.0})))
        _wait_for(lambda: placed)
        assert placed[0].hex() == (b"\x99" * NodeID.SIZE).hex()

    def test_draining_rejection_reason(self, harness):
        from ray_tpu._private.scheduler import \
            NodeAffinitySchedulingStrategy
        h = harness(1)
        h.sched.set_draining(h.node_ids[0], True)
        # Hard affinity to the draining node: queued with the drain
        # fence named as the reason.
        spec = h.make_spec(1)
        spec.scheduling_strategy = NodeAffinitySchedulingStrategy(
            h.node_ids[0], soft=False)
        h.sched.submit(spec, lambda s, n: None)
        out = h.sched.explain_task(spec.task_id)
        assert "draining" in out["reasons"]
        assert "affinity_miss" in out["reasons"]
        # A plain task on a fully-draining cluster also names the fence.
        plain = h.make_spec(2)
        h.sched.submit(plain, lambda s, n: None)
        out = h.sched.explain_task(plain.task_id)
        assert out["rejected"].get("draining") == 1
        assert "draining" in out["reasons"]

    def test_pg_bundle_miss_reason(self, harness):
        from ray_tpu._private.controller import (BundleInfo,
                                                 PlacementGroupInfo)
        from ray_tpu._private.ids import PlacementGroupID
        from ray_tpu._private.resources import ResourceSet
        h = harness(2)  # 4 CPUs/node: a 64-CPU bundle can never commit
        pg = PlacementGroupInfo(
            PlacementGroupID(b"\x02" * PlacementGroupID.SIZE), "test_pg",
            "PACK", [BundleInfo(0, ResourceSet({"CPU": 64.0}))])
        assert h.sched.create_placement_group(pg) is False
        spec = h.make_spec(1, pg=pg.pg_id, bundle_index=0)
        h.sched.submit(spec, lambda s, n: None)
        out = h.sched.explain_task(spec.task_id)
        assert out["reasons"] == ["bundle_unavailable"]
        assert out["pg"]["committed_bundles"] == []
        # The PG's own failed prepare is on the ring too.
        rec = h.sched.ring.latest_for(pg.pg_id.hex())
        assert rec["kind"] == "pg_reject"
        assert rec["rejected"].get("bundle_unavailable") == 1

    def test_pg_commit_decision_recorded(self, harness):
        from ray_tpu._private.controller import (BundleInfo,
                                                 PlacementGroupInfo)
        from ray_tpu._private.ids import PlacementGroupID
        from ray_tpu._private.resources import ResourceSet
        h = harness(2)
        pg = PlacementGroupInfo(
            PlacementGroupID(b"\x03" * PlacementGroupID.SIZE), "ok_pg",
            "PACK", [BundleInfo(0, ResourceSet({"CPU": 2.0}))])
        assert h.sched.create_placement_group(pg) is True
        rec = h.sched.ring.latest_for(pg.pg_id.hex())
        assert rec["kind"] == "pg_commit"
        assert rec["node_id"]

    def test_ring_bounded_and_counts_drops(self):
        from ray_tpu.schedview import DecisionRing
        ring = DecisionRing(capacity=64)
        for i in range(300):
            ring.push("loop", f"{i:04x}", "t", None, 1, None, "n", 1)
        stats = ring.stats()
        assert stats["size"] == 64
        assert stats["num_dropped"] == 300 - 64
        assert stats["counts"]["loop"] == 300
        assert len(ring.snapshot(limit=1000)) == 64

    def test_ring_disabled_records_nothing(self, harness):
        from ray_tpu import schedview
        h = harness(2)
        schedview.set_enabled(False)
        try:
            placed = []
            h.sched.submit(h.make_spec(1), lambda s, n: placed.append(n))
            _wait_for(lambda: placed)
            assert h.sched.ring.stats()["total"] == 0
        finally:
            schedview.set_enabled(True)


class TestEventBufferStats:
    def test_dropped_and_backlog_visible(self):
        from ray_tpu._private.events import (FINISHED, RUNNING,
                                             TaskEventBuffer)
        buf = TaskEventBuffer(max_events=4)
        for i in range(10):
            buf.record(f"{i:02x}", RUNNING)
        buf._fold()
        stats = buf.stats()
        assert stats["num_events"] == 4
        assert stats["num_dropped"] == 6
        assert stats["fold_backlog"] == 0
        buf.record("ff", FINISHED)
        assert buf.stats()["fold_backlog"] == 1

    def test_monotonic_stage_waits(self):
        from ray_tpu._private.events import (FINISHED, PLACED, READY,
                                             RUNNING, SUBMITTED_TO_NODE,
                                             PENDING_ARGS,
                                             TaskEventBuffer)
        buf = TaskEventBuffer()
        buf.record("aa", PENDING_ARGS, name="t")
        time.sleep(0.02)
        buf.record("aa", READY)
        buf.record("aa", PLACED)
        buf.record("aa", SUBMITTED_TO_NODE)
        buf.record("aa", RUNNING)
        time.sleep(0.01)
        buf.record("aa", FINISHED)
        rec = buf.snapshot({"task_id": "aa"}, 1)[0]
        waits = rec["stage_waits"]
        assert waits["deps"] >= 0.015
        assert waits["run"] >= 0.005
        assert set(waits) == {"deps", "queue", "dispatch", "startup",
                              "run"}

    def test_filter_pushdown_and_limit(self):
        from ray_tpu._private.events import (FINISHED, RUNNING,
                                             TaskEventBuffer)
        buf = TaskEventBuffer()
        for i in range(50):
            buf.record(f"{i:02x}", RUNNING, name=f"fn{i % 2}")
        for i in range(10):
            buf.record(f"{i:02x}", FINISHED)
        out = buf.snapshot({"state": FINISHED}, limit=4)
        assert len(out) == 4
        assert all(e["state"] == FINISHED for e in out)
        # Summary with state filter + scan limit.
        summ = buf.summary(states=[FINISHED])
        assert sum(sum(v.values()) for v in summ.values()) == 10
        assert buf.summary(limit=5)
        # Stage-latency filter: only tasks that entered "run".
        out = buf.snapshot(stage="run", min_stage_wait_s=0.0, limit=100)
        assert len(out) == 10

    def test_find_ids_prefix(self):
        from ray_tpu._private.events import RUNNING, TaskEventBuffer
        buf = TaskEventBuffer()
        buf.record("abcd01", RUNNING)
        buf.record("abcd02", RUNNING)
        buf.record("ef99", RUNNING)
        assert set(buf.find_ids("abcd")) == {"abcd01", "abcd02"}
        assert buf.find_ids("zz") == []


class TestLiveExplainAndCLI:
    """End-to-end through a real runtime, the job-server REST surface
    and the click CLIs (`ray-tpu task why`, `ray-tpu sched`)."""

    @pytest.fixture()
    def server(self, ray_start_isolated):
        from ray_tpu.job_submission.manager import JobManager
        from ray_tpu.job_submission.server import JobServer
        server = JobServer(JobManager(), port=0)
        yield server
        server.stop()

    def _cli(self, args):
        from click.testing import CliRunner

        from ray_tpu.scripts.cli import cli
        return CliRunner().invoke(cli, args)

    def test_task_why_pending_deps_and_infeasible(self, server):
        import ray_tpu

        @ray_tpu.remote
        def _sleepy():
            time.sleep(6)
            return 1

        @ray_tpu.remote
        def _add(x, y=0):
            return x

        dep = _sleepy.remote()
        child = _add.remote(dep)
        gpu = _add.options(resources={"GPU": 1.0}).remote(1)
        time.sleep(0.4)
        addr = server.address

        child_tid = child._id.task_id().hex()
        r = self._cli(["task", "why", "--address", addr, child_tid])
        assert r.exit_code == 0, r.output
        assert "pending_deps" in r.output
        assert "waiting on object" in r.output

        # Prefix lookup: the first 12 chars resolve to the same task.
        gpu_tid = gpu._id.task_id().hex()
        r = self._cli(["task", "why", "--address", addr, gpu_tid])
        assert r.exit_code == 0, r.output
        assert "infeasible" in r.output
        assert "GPU" in r.output  # the named resource gap

        # Finished task: explains why it landed where it landed.
        done = _add.remote(1)
        ray_tpu.get(done)
        time.sleep(0.1)
        r = self._cli(["task", "why", "--address", addr,
                       done._id.task_id().hex()])
        assert r.exit_code == 0, r.output
        assert "status: finished" in r.output
        assert "last decision" in r.output

        # Unknown id exits non-zero with a readable message.
        r = self._cli(["task", "why", "--address", addr, "feedface"])
        assert r.exit_code == 1
        assert "no task" in r.output
        ray_tpu.get(dep)
        ray_tpu.get(child)

    def test_sched_cli_shows_rates_queues_and_buffer(self, server):
        import ray_tpu

        @ray_tpu.remote
        def _one():
            return 1

        ray_tpu.get([_one.remote() for _ in range(10)])
        r = self._cli(["sched", "--address", server.address, "-n", "5"])
        assert r.exit_code == 0, r.output
        assert "decisions/s" in r.output
        assert "queues:" in r.output
        assert "ready:" in r.output
        assert "task events:" in r.output
        assert "fold backlog" in r.output
        # -n 5 prints decision records.
        assert "[" in r.output and "cands=" in r.output

    def test_state_api_and_rest_surface(self, server):
        import urllib.request

        import ray_tpu
        from ray_tpu.util import state as rstate

        @ray_tpu.remote
        def _one():
            return 1

        ray_tpu.get(_one.remote())
        stats = rstate.sched_stats()
        assert stats["decisions"]["total"] >= 1
        assert "ready" in stats["queues"]
        assert rstate.sched_decisions(limit=5)

        with urllib.request.urlopen(
                server.address + "/api/cluster/sched?decisions=3") as resp:
            out = json.loads(resp.read())
        assert out["stats"]["decisions"]["total"] >= 1
        assert isinstance(out.get("decisions"), list)

    def test_debug_bundle_carries_sched_decisions(self, ray_start_isolated):
        import ray_tpu
        from ray_tpu.util import state as rstate

        @ray_tpu.remote
        def _one():
            return 1

        ray_tpu.get(_one.remote())
        path = rstate.debug_dump(reason="sched_test")
        fname = os.path.join(path, "sched_decisions.json")
        assert os.path.exists(fname)
        with open(fname) as f:
            doc = json.load(f)
        assert doc["stats"]["total"] >= 1
        assert "queues" in doc
        assert isinstance(doc["decisions"], list)
