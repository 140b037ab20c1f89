"""Data-plane telescope: object-lifecycle ring, unified store stats,
enriched ObjectStoreFullError, spill-file GC, the memory-summary /
explain-object control verbs, and cross-node transfer accounting.

Reference analogs: ``ray memory`` (python/ray/_private/state.py memory
summary) and the object-transfer accounting in
src/ray/object_manager/{pull_manager,push_manager}.h — but here the
lifecycle *history* is queryable, not just the instantaneous state.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from ray_tpu._private import object_store as store_mod
from ray_tpu._private.ids import JobID, ObjectID, TaskID
from ray_tpu._private.object_store import (ObjectStoreFullError,
                                           SharedMemoryStore,
                                           sweep_orphan_spills)
from ray_tpu.storeview import events as sv


def _oid(i: int) -> ObjectID:
    return ObjectID.of(TaskID.for_driver(JobID.next()), i)


def _wait_for(predicate, timeout_s: float = 30.0, interval_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        out = predicate()
        if out:
            return out
        time.sleep(interval_s)
    raise AssertionError("condition not met within timeout")


# ---------------------------------------------------------------------------
# StoreEventRing unit tests
# ---------------------------------------------------------------------------


class TestStoreEventRing:
    def test_lifecycle_fold_and_explain(self):
        ring = sv.StoreEventRing(capacity=256)
        key = _oid(1).binary()
        ring.push(sv.E_CREATE, key, 1000)
        ring.push(sv.E_SEAL, key)
        ring.push(sv.E_GET, key)
        ring.push(sv.E_GET, key)
        out = ring.explain(key.hex())
        assert out["status"] == "ok"
        assert out["state"] == "sealed"
        assert out["nbytes"] == 1000
        assert out["reads"] == 2
        assert [e["kind"] for e in out["events"]] == \
            ["create", "seal", "get", "get"]
        assert out["age_s"] >= 0.0
        ring.push(sv.E_DELETE, key)
        assert ring.explain(key.hex())["state"] == "deleted"

    def test_explain_unknown_and_ambiguous_prefix(self):
        ring = sv.StoreEventRing(capacity=256)
        a, b = _oid(1).binary(), _oid(2).binary()
        assert ring.explain("feedbeef")["status"] == "unknown"
        ring.push(sv.E_CREATE, a, 10)
        ring.push(sv.E_CREATE, b, 10)
        # Both ids share the leading task-id bytes? No — different jobs.
        # Force ambiguity with the empty prefix (matches everything).
        amb = ring.explain("")
        assert amb["status"] == "ambiguous"
        assert len(amb["matches"]) == 2
        # An exact full id resolves.
        assert ring.explain(a.hex())["status"] == "ok"

    def test_bounded_ring_counts_drops(self):
        ring = sv.StoreEventRing(capacity=64)
        key = _oid(1).binary()
        for _ in range(300):
            ring.push(sv.E_GET, key)
        st = ring.stats()
        assert st["counts"]["get"] == 300
        assert st["total"] == 300
        assert st["size"] <= st["capacity"] == 64
        assert st["num_dropped"] > 0
        assert st["tracked"] == 1

    def test_pin_accounting_and_top_pinned(self):
        ring = sv.StoreEventRing(capacity=256)
        small, big = _oid(1).binary(), _oid(2).binary()
        ring.push(sv.E_CREATE, small, 100)
        ring.push(sv.E_PIN, small, detail="worker_a")
        ring.push(sv.E_CREATE, big, 9000)
        ring.push(sv.E_PIN, big, detail="ckpt_pin")
        ring.push(sv.E_PIN, big, detail="worker_b")
        top = ring.top_pinned(2)
        assert top[0]["object_id"] == big.hex()
        assert top[0]["pins"] == 2
        assert set(top[0]["pinners"]) == {"ckpt_pin", "worker_b"}
        assert ring.pinners_of(small) == ["worker_a"]
        # Unpinning the last pin clears the pinner list.
        ring.push(sv.E_UNPIN, small, detail="worker_a")
        assert ring.pinners_of(small) == []
        assert ring.top_pinned(5)[0]["object_id"] == big.hex()
        assert len(ring.top_pinned(5)) == 1

    def test_leak_candidates_sealed_never_read(self):
        ring = sv.StoreEventRing(capacity=256)
        leaked, read_obj = _oid(1).binary(), _oid(2).binary()
        for key in (leaked, read_obj):
            ring.push(sv.E_CREATE, key, 500)
            ring.push(sv.E_SEAL, key)
        ring.push(sv.E_GET, read_obj)
        time.sleep(0.05)
        leaks = ring.leak_candidates(ttl_s=0.01)
        assert [r["object_id"] for r in leaks] == [leaked.hex()]
        assert leaks[0]["reason"] == "sealed_never_read"
        # A later read clears the candidate.
        ring.push(sv.E_GET, leaked)
        assert ring.leak_candidates(ttl_s=0.01) == []

    def test_leak_candidates_dead_incarnation(self):
        ring = sv.StoreEventRing(capacity=256)
        dead, label = _oid(1).binary(), _oid(2).binary()
        dead_token = "ab" * 14  # 28 hex chars: a worker-id incarnation
        for key in (dead, label):
            ring.push(sv.E_CREATE, key, 500)
            ring.push(sv.E_SEAL, key)
            ring.push(sv.E_GET, key)  # reads exempt the TTL rule
        ring.push(sv.E_PIN, dead, detail=dead_token)
        # Descriptive labels are not incarnations: never counted dead.
        ring.push(sv.E_PIN, label, detail="ckpt_pin")
        leaks = ring.leak_candidates(ttl_s=3600.0, live_tokens={"cafe" * 7})
        assert [r["object_id"] for r in leaks] == [dead.hex()]
        assert leaks[0]["reason"] == "pinned_by_dead_incarnation"
        # The same pin is healthy while its incarnation is alive.
        assert ring.leak_candidates(ttl_s=3600.0,
                                    live_tokens={dead_token}) == []

    def test_enable_switch_defaults_on(self):
        assert sv.enabled()
        sv.set_enabled(False)
        try:
            assert not sv.enabled()
        finally:
            sv.set_enabled(True)
        assert sv.enabled()


# ---------------------------------------------------------------------------
# Store-level behaviors: unified stats, enriched full error, spill events
# ---------------------------------------------------------------------------


class TestUnifiedStoreStats:
    EXPECTED = {"num_objects", "used_bytes", "capacity_bytes",
                "pinned_bytes", "spilled_bytes", "num_spilled",
                "num_restored", "num_evictions", "num_in_memory",
                "num_pinned", "native"}

    def test_python_store_keys(self):
        s = SharedMemoryStore(capacity_bytes=1 << 20)
        try:
            assert set(s.stats()) == self.EXPECTED
            assert s.stats()["native"] == 0
        finally:
            s.shutdown()

    def test_native_store_keys_match(self, tmp_path):
        from ray_tpu._native import load_store_library
        from ray_tpu._private.object_store import NativeArenaStore
        if load_store_library() is None:
            pytest.skip("no C++ toolchain")
        s = NativeArenaStore(capacity_bytes=1 << 20,
                             spill_dir=str(tmp_path / "spill"))
        try:
            assert set(s.stats()) == self.EXPECTED
            assert s.stats()["native"] == 1
        finally:
            s.shutdown()


class TestStoreFullErrorEnrichment:
    def test_message_names_top_pinned_and_pinners(self):
        s = SharedMemoryStore(capacity_bytes=1 << 20)
        try:
            hog = _oid(1)
            view = s.create(hog, 700_000)
            view.release()
            s.seal(hog)
            s.pin(hog, pinner="ckpt_pin")
            with pytest.raises(ObjectStoreFullError) as ei:
                s.create(_oid(2), 700_000)
            msg = str(ei.value)
            assert "top pinned" in msg
            assert hog.hex()[:12] in msg
            assert "ckpt_pin" in msg
            s.unpin(hog, pinner="ckpt_pin")
        finally:
            s.shutdown()


class TestSpillLifecycleEvents:
    def test_spill_then_restore_records_ring_evidence(self, tmp_path):
        s = SharedMemoryStore(capacity_bytes=1 << 20,
                              spill_dir=str(tmp_path / "spill"))
        try:
            oids = [_oid(i) for i in range(3)]
            for oid in oids:  # 3 x 500KB > 1MB: first object spills
                view = s.create(oid, 500_000)
                view[:] = b"\xaa" * 500_000
                view.release()
                s.seal(oid)
            stats = s.stats()
            assert stats["num_spilled"] >= 1
            assert stats["spilled_bytes"] >= 500_000
            out = s.view.explain(oids[0].hex())
            assert out["state"] == "spilled"
            assert out["spills"] == 1 and out["spilled"]
            # Reading the spilled object restores it; both halves of the
            # round trip land in the ring, and counts agree with stats.
            view, _keep = s.get_buffer(oids[0])
            assert bytes(view[:4]) == b"\xaa" * 4
            view.release()
            out = s.view.explain(oids[0].hex())
            assert out["restores"] == 1 and not out["spilled"]
            kinds = [e["kind"] for e in out["events"]]
            assert kinds.index("spill") < kinds.index("restore")
            rc = s.view.stats()["counts"]
            assert rc["spill"] == s.stats()["num_spilled"]
            assert rc["restore"] == s.stats()["num_restored"]
        finally:
            s.shutdown()


class TestSameHostPullDedupe:
    def test_put_raw_reuses_producer_segment(self, tmp_path):
        """shm names are host-global (`rt_<oid>`): when the producer of a
        pulled object lives on the same host, the puller's put_raw must
        hand back a descriptor onto the live segment instead of crashing
        on the name collision (FileExistsError)."""
        from ray_tpu._private.object_store import RemoteObjectReader

        producer = SharedMemoryStore(capacity_bytes=1 << 20,
                                     spill_dir=str(tmp_path / "p"))
        puller = SharedMemoryStore(capacity_bytes=1 << 20,
                                   spill_dir=str(tmp_path / "q"))
        try:
            oid = _oid(1)
            producer.put(oid, {"blob": b"\xbc" * 4096})
            payload = producer.read_raw_by_key(oid.binary())
            assert payload is not None

            desc = puller.put_raw(oid, payload)
            assert desc is not None and desc[0] == "shm"
            assert desc[2] == len(payload)
            # The descriptor resolves to the producer's live segment.
            got, shm = RemoteObjectReader.read(desc[1], desc[2])
            try:
                assert got["blob"] == b"\xbc" * 4096
                assert producer.contains(oid)
                # No duplicate entry was cached in the pulling store.
                assert not puller.contains(oid)
            finally:
                shm.close()
        finally:
            producer.shutdown()
            puller.shutdown()


class TestSpillFileGC:
    def test_sweep_reclaims_only_dead_pid_dirs(self, tmp_path):
        root = str(tmp_path / "spill_root")
        # A pid that existed and is now dead (spawn + reap).
        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        proc.wait()
        dead_pid = proc.pid
        for name, nbytes in ((str(dead_pid), 1000),
                             (f"arena_{dead_pid}", 2000),
                             (str(os.getpid()), 4000),   # live: ours
                             ("not_a_pid", 8000)):       # unrelated
            d = os.path.join(root, name)
            os.makedirs(d)
            with open(os.path.join(d, "obj"), "wb") as f:
                f.write(b"\0" * nbytes)
        reclaimed = sweep_orphan_spills(root=root)
        assert reclaimed == 3000
        assert not os.path.exists(os.path.join(root, str(dead_pid)))
        assert not os.path.exists(os.path.join(root, f"arena_{dead_pid}"))
        assert os.path.exists(os.path.join(root, str(os.getpid())))
        assert os.path.exists(os.path.join(root, "not_a_pid"))
        # Idempotent: nothing left to reclaim.
        assert sweep_orphan_spills(root=root) == 0

    def test_shutdown_sweeps_own_default_spill_dir(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setattr(store_mod, "SPILL_ROOT",
                            str(tmp_path / "spill_root"))
        own = os.path.join(store_mod.SPILL_ROOT, str(os.getpid()))
        os.makedirs(own)
        with open(os.path.join(own, "orphan"), "wb") as f:
            f.write(b"\0" * 512)
        s = SharedMemoryStore(capacity_bytes=1 << 20)  # default spill dir
        s.shutdown()
        assert not os.path.exists(own)


# ---------------------------------------------------------------------------
# Live runtime: memory summary, explain_object, leak candidates, gauges
# ---------------------------------------------------------------------------


@pytest.fixture()
def small_store_runtime(monkeypatch):
    """Isolated runtime whose head store is a 4 MiB *Python* store, so
    spill pressure is cheap to provoke and every lifecycle event (spill
    decisions included) lands in the ring."""
    monkeypatch.setenv("RAY_TPU_OBJECT_STORE_MEMORY", str(4 << 20))
    monkeypatch.setenv("RAY_TPU_USE_NATIVE_STORE", "0")
    import ray_tpu
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1)
    yield
    ray_tpu.shutdown()


class TestMemoryIntrospectionLive:
    def test_summary_explain_spill_pin_and_events(self, small_store_runtime):
        import ray_tpu
        from ray_tpu._private.api import _control
        from ray_tpu.util import state

        a = ray_tpu.put(np.zeros(1_500_000, dtype=np.uint8))
        b = ray_tpu.put(np.ones(1_500_000, dtype=np.uint8))
        c = ray_tpu.put(np.full(1_500_000, 2, dtype=np.uint8))

        # 4.5MB into a 4MiB store: the LRU head (a) spilled.
        out = state.explain_object(a.hex())
        assert out["status"] == "ok"
        assert out["directory"]["state"] == "shm"
        assert out["directory"]["error"] is False
        assert out["local"]["spills"] >= 1 and out["local"]["spilled"]

        summary = state.memory_summary(top_n=5)
        assert summary["totals"]["capacity_bytes"] == 4 << 20
        assert summary["totals"]["num_spilled"] >= 1
        assert summary["totals"]["spilled_bytes"] >= 1_500_000
        assert summary["num_directory_objects"] >= 3
        assert len(summary["nodes"]) >= 1
        top_ids = [o["object_id"] for o in summary["top_objects"]]
        assert b.hex() in top_ids and c.hex() in top_ids

        # Reading the spilled object restores it (visible in explain).
        arr = ray_tpu.get(a)
        assert arr.nbytes == 1_500_000
        out = state.explain_object(a.hex())
        assert out["local"]["restores"] >= 1
        assert not out["local"]["spilled"]

        # Pin via the checkpoint pin verb: explain names the pinner.
        assert _control("pin_object", a.binary()) is True
        out = state.explain_object(a.hex())
        assert out["local"]["pins"] >= 1
        assert "ckpt_pin" in out["local"]["pinners"]
        assert _control("unpin_object", a.binary()) is True

        # The raw event tail carries the whole story, filterable by id.
        ev = state.store_events(object_id=a.hex(), limit=100)
        kinds = [e["kind"] for e in ev["events"]]
        for expected in ("create", "seal", "spill", "restore", "pin",
                         "unpin"):
            assert expected in kinds, (expected, kinds)
        assert ev["stats"]["counts"]["spill"] >= 1

        # Prefix queries resolve; garbage ids answer unknown, not raise.
        assert state.explain_object(a.hex()[:16])["status"] in \
            ("ok", "ambiguous")
        assert state.explain_object("feedbeefcafe")["status"] == "unknown"
        del b, c

    def test_leak_candidate_surfaces_in_summary(self, small_store_runtime,
                                                monkeypatch):
        import ray_tpu
        from ray_tpu.util import state

        monkeypatch.setattr(sv, "LEAK_TTL_S", 0.05)
        leaked = ray_tpu.put(np.zeros(300_000, dtype=np.uint8))
        time.sleep(0.2)

        def leaked_reported():
            leaks = state.memory_summary()["leak_candidates"]
            return [r for r in leaks if r["object_id"] == leaked.hex()]

        rec = _wait_for(leaked_reported, timeout_s=10.0)[0]
        assert rec["reason"] == "sealed_never_read"
        assert rec["nbytes"] >= 300_000  # serialized payload: data + meta
        assert "node_id" in rec
        # Reading it clears the candidate.
        ray_tpu.get(leaked)
        _wait_for(lambda: not leaked_reported(), timeout_s=10.0)

    def test_store_gauges_queryable_via_metrics_path(self,
                                                     small_store_runtime):
        import ray_tpu
        from ray_tpu._private import runtime as rt_mod
        from ray_tpu.util import state

        ref = ray_tpu.put(np.zeros(500_000, dtype=np.uint8))
        summary = state.memory_summary()  # forces the gauge publisher
        assert summary["totals"]["used_bytes"] >= 500_000
        rt_mod.driver_runtime().metricsview.refresh(force=True)
        # Tag-filter to this runtime's head node: the process-global
        # registry keeps node-tagged gauge series from earlier inits in
        # the same pytest process, and an unfiltered multi-series match
        # would fold those stale nodes in.
        head_hex = rt_mod.driver_runtime().node_id.hex()
        q = state.metrics_query("ray_tpu_store_used_bytes",
                                window_s=300.0, agg="last",
                                tags={"node": head_hex})
        assert q["value"] is not None and q["value"] >= 500_000
        q = state.metrics_query("ray_tpu_store_ops_total",
                                window_s=300.0, agg="last",
                                tags={"op": "create"})
        assert q["value"] is not None and q["value"] >= 1
        del ref


# ---------------------------------------------------------------------------
# Cross-node: remote attribution + transfer accounting
# ---------------------------------------------------------------------------


class TestCrossNodeTransfer:
    def test_remote_object_attributed_and_pull_accounted(self):
        import ray_tpu
        from ray_tpu._private import runtime as rt_mod
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.util import state

        with Cluster(head_num_cpus=0) as cluster:
            cluster.add_node(num_cpus=2)
            cluster.add_node(num_cpus=2)
            rt = cluster.runtime
            head_hex = rt.node_id.hex()

            @ray_tpu.remote(num_cpus=1)
            def produce():
                return np.full(300_000, 7, dtype=np.uint8)

            ref = produce.remote()

            # The directory attributes the result to its OWNER node (the
            # worker node that produced it), not the head.
            def owned_remotely():
                recs = [r for r in state.list_objects()
                        if r["object_id"] == ref.hex()]
                if recs and recs[0].get("node_id") not in (None, head_hex):
                    return recs[0]
                return None

            rec = _wait_for(owned_remotely)
            owner_hex = rec["node_id"]
            assert rec["size_bytes"] > 100 * 1024  # too big to inline

            out = state.explain_object(ref.hex())
            assert out["status"] == "ok"
            assert out["directory"]["node_id"] == owner_hex

            # Driver get = cross-node pull through the data plane: the
            # head ring records it, with latency + the peer node.
            arr = ray_tpu.get(ref)
            assert arr[0] == 7 and arr.nbytes == 300_000
            out = state.explain_object(ref.hex())
            assert out["local"]["pulls"] >= 1
            assert out["local"]["pull_bytes"] >= 300_000
            assert out["local"]["pull_avg_ms"] >= 0.0
            assert out["local"]["last_peer"] == owner_hex[:16]
            ev = state.store_events(object_id=ref.hex())
            assert "pull" in [e["kind"] for e in ev["events"]]

            # The memory summary eventually shows the owner node's store
            # occupancy (synced view) alongside the head's.
            def summary_covers_owner():
                nodes = state.memory_summary()["nodes"]
                sub = nodes.get(owner_hex)
                return sub if sub and sub.get("num_objects", 0) >= 1 \
                    else None
            _wait_for(summary_covers_owner)

            # And the transfer series are queryable through the
            # production metrics path on the head.
            rt.metricsview.refresh(force=True)
            q = state.metrics_query("ray_tpu_store_transfer_bytes_total",
                                    window_s=300.0, agg="last",
                                    tags={"direction": "pull"})
            assert q["value"] is not None and q["value"] >= 300_000
            qh = state.metrics_query("ray_tpu_store_transfer_seconds",
                                     window_s=300.0, agg="last")
            assert qh["value"] is not None
