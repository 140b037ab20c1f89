"""Collective group tests: KV backend (pure python) + XLA-gloo backend
(2 worker processes, each its own jax CPU world member).

Mirrors the reference's CPU collective tests (reference:
python/ray/util/collective/tests/single_node_cpu_tests/,
distributed_cpu_tests/test_distributed_allreduce.py)."""

import numpy as np
import pytest

import ray_tpu


@ray_tpu.remote
class KVCollectiveWorker:
    def __init__(self, rank, world):
        self.rank, self.world = rank, world

    def setup(self, group):
        from ray_tpu import collective as col
        col.init_collective_group(self.world, self.rank, backend="kv",
                                  group_name=group)
        return True

    def run_ops(self, group):
        from ray_tpu import collective as col
        out = {}
        x = np.full(4, float(self.rank + 1), np.float32)
        out["allreduce"] = col.allreduce(x, group)
        out["allgather"] = col.allgather(
            np.array([self.rank], np.float32), group)
        out["broadcast"] = col.broadcast(
            np.full(2, float(self.rank), np.float32), src_rank=1,
            group_name=group)
        rs_in = np.arange(self.world * 2, dtype=np.float32)
        out["reducescatter"] = col.reducescatter(rs_in, group)
        col.barrier(group)
        out["rank"] = col.get_rank(group)
        return out

    def p2p(self, group):
        from ray_tpu import collective as col
        if self.rank == 0:
            col.send(np.array([42.0]), dst_rank=1, group_name=group)
            return None
        return col.recv((1,), np.float32, src_rank=0, group_name=group)


class TestKVBackend:
    def test_all_ops(self, ray_start):
        world = 3
        workers = [KVCollectiveWorker.remote(r, world) for r in range(world)]
        assert all(ray_tpu.get(
            [w.setup.remote("g1") for w in workers], timeout=60))
        results = ray_tpu.get(
            [w.run_ops.remote("g1") for w in workers], timeout=60)
        for r, res in enumerate(results):
            np.testing.assert_allclose(res["allreduce"], np.full(4, 6.0))
            np.testing.assert_allclose(res["allgather"], [[0], [1], [2]])
            np.testing.assert_allclose(res["broadcast"], [1.0, 1.0])
            np.testing.assert_allclose(
                res["reducescatter"],
                3 * np.arange(world * 2, dtype=np.float32)[r * 2:(r + 1) * 2])
            assert res["rank"] == r

    def test_p2p(self, ray_start):
        workers = [KVCollectiveWorker.remote(r, 2) for r in range(2)]
        try:
            ray_tpu.get([w.setup.remote("g2") for w in workers], timeout=120)
            out = ray_tpu.get([w.p2p.remote("g2") for w in workers],
                              timeout=120)
        except Exception:
            # Rare full-suite-only flake under investigation: dump the
            # control-plane state so the next occurrence is actionable.
            rt = ray_start
            print("DIAG actors:", rt.ctl_list_actors())
            print("DIAG kv:", rt.ctl_kv_keys("collective/"))
            print("DIAG tasks:", rt.ctl_summarize_tasks())
            print("DIAG pending:", rt.scheduler.num_pending())
            raise
        np.testing.assert_allclose(out[1], [42.0])


@ray_tpu.remote
class XlaCollectiveWorker:
    """Each worker is a separate process with its own 1-device jax CPU
    runtime; the group forms a 2-process gloo world."""

    def __init__(self, rank, world):
        self.rank, self.world = rank, world

    def setup_and_allreduce(self, group):
        from ray_tpu import collective as col
        col.init_collective_group(self.world, self.rank, backend="xla",
                                  group_name=group)
        grad = np.full((8,), float(self.rank + 1), np.float32)
        reduced = col.allreduce(grad, group)
        gathered = col.allgather(np.array([self.rank], np.int32), group)
        col.barrier(group)
        return reduced, gathered


class TestXlaBackend:
    def test_two_process_gloo_allreduce(self, ray_start):
        world = 2
        env = {"env_vars": {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}}
        workers = [
            XlaCollectiveWorker.options(runtime_env=env).remote(r, world)
            for r in range(world)]
        results = ray_tpu.get(
            [w.setup_and_allreduce.remote("xg1") for w in workers],
            timeout=180)
        for reduced, gathered in results:
            np.testing.assert_allclose(reduced, np.full((8,), 3.0))
            np.testing.assert_allclose(np.asarray(gathered).ravel(), [0, 1])
