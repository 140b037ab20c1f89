"""Elastic training tests: resize-on-failure and upsize-on-capacity over a
multi-node cluster (reference analogs: train/v2 elastic scaling policy
scaling_policy/elastic.py + release/train_tests/elastic_training, and
test_jax_elastic_e2e.py)."""

from __future__ import annotations

import tempfile
import threading
import time

import pytest

from ray_tpu.cluster_utils import Cluster
from ray_tpu.train import (JaxTrainer, RunConfig, FailureConfig,
                           ScalingConfig)


def make_train_fn(total_steps: int, step_time: float):
    def train_fn(config=None):
        import os
        import tempfile as _tf
        import time as _time

        import ray_tpu.train as train

        ctx = train.get_context()
        rank = ctx.get_world_rank()
        world = ctx.get_world_size()
        start = 0
        ckpt = ctx.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "step.txt")) as f:
                start = int(f.read())
        for step in range(start, total_steps):
            _time.sleep(step_time)
            if rank == 0:
                d = _tf.mkdtemp(prefix="elastic_ck_")
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step + 1))
                train.report({"step": step + 1, "start": start,
                              "world": world},
                             checkpoint=train.Checkpoint(d))
            else:
                train.report({"step": step + 1, "start": start,
                              "world": world})
    return train_fn


@pytest.fixture()
def cluster(monkeypatch):
    # Elastic failover tests assert on PROMPT node-death handling; the
    # reconnect grace window (node_reconnect_grace_s, test_reconnect.py)
    # would let the collective-free toy train fn run to completion before
    # the death fan-out fires, changing what the assertions measure.
    monkeypatch.setenv("RAY_TPU_NODE_RECONNECT_GRACE_S", "0")
    c = Cluster(head_num_cpus=0)  # init re-resolves Config from env
    yield c
    c.shutdown()


class TestElasticTrain:
    def test_downscale_after_node_death(self, cluster):
        n1 = cluster.add_node(num_cpus=2)
        n2 = cluster.add_node(num_cpus=2)
        trainer = JaxTrainer(
            make_train_fn(total_steps=14, step_time=0.4),
            scaling_config=ScalingConfig(
                resources_per_worker={"CPU": 1},
                min_workers=1, max_workers=4,
                elastic_check_interval_s=3600,  # no upsize in this test
                env_per_worker={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}),
            run_config=RunConfig(
                storage_path=tempfile.mkdtemp(prefix="elastic_"),
                failure_config=FailureConfig(max_failures=3)))

        killed = {"done": False}

        def killer():
            # Wait until training reported progress, then take a node down.
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if any(r["metrics"].get("step", 0) >= 2
                       for r in trainer_result_probe()):
                    break
                time.sleep(0.2)
            cluster.remove_node(n2)
            killed["done"] = True

        controller_holder = {}

        def trainer_result_probe():
            c = controller_holder.get("c")
            return c._reports if c is not None else []

        # Run fit() on a thread so the test can inject the node death.
        from ray_tpu.train.controller import TrainController
        controller = TrainController(
            trainer._train_fn, trainer._config, trainer._scaling,
            trainer._run_config)
        controller_holder["c"] = controller
        result_box = {}

        def run():
            import ray_tpu
            if not ray_tpu.is_initialized():
                ray_tpu.init()
            result_box["r"] = controller.run()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        t.join(timeout=240)
        assert not t.is_alive(), "training did not finish"
        r = result_box["r"]
        assert r.error is None
        assert killed["done"]
        # First incarnation used all 4 slots; post-death incarnation 2.
        assert r.world_size_history[0] == 4
        assert r.world_size_history[-1] == 2
        assert r.metrics["step"] == 14
        # The restart resumed from a checkpoint, not step 0.
        assert r.metrics["start"] > 0

    def test_upscale_when_capacity_appears(self, cluster):
        cluster.add_node(num_cpus=2)
        from ray_tpu.train.controller import TrainController
        trainer = JaxTrainer(
            make_train_fn(total_steps=12, step_time=0.5),
            scaling_config=ScalingConfig(
                resources_per_worker={"CPU": 1},
                min_workers=1, max_workers=4,
                elastic_check_interval_s=1.0,
                env_per_worker={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}),
            run_config=RunConfig(
                storage_path=tempfile.mkdtemp(prefix="elastic_"),
                failure_config=FailureConfig(max_failures=2)))
        controller = TrainController(
            trainer._train_fn, trainer._config, trainer._scaling,
            trainer._run_config)
        result_box = {}

        def run():
            import ray_tpu
            if not ray_tpu.is_initialized():
                ray_tpu.init()
            result_box["r"] = controller.run()

        t = threading.Thread(target=run, daemon=True)
        t.start()

        def grower():
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if any(r["metrics"].get("step", 0) >= 2
                       for r in controller._reports):
                    break
                time.sleep(0.2)
            cluster.add_node(num_cpus=2)

        g = threading.Thread(target=grower, daemon=True)
        g.start()
        t.join(timeout=240)
        assert not t.is_alive(), "training did not finish"
        r = result_box["r"]
        assert r.error is None
        assert r.world_size_history[0] == 2
        assert max(r.world_size_history) == 4  # upsized mid-run
        assert r.metrics["step"] == 12
