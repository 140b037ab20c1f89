"""The xing4 model's train step (its state and report, what the trainer
records) and a CPU rehearsal of its benchmark cell.  (Cut from
``tests/test_xing4.py``, PR 59.)"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import xing4

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


# ------------------------------------------------------ the train step

def test_train_step_trains_through_model_module_and_reports():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import (StepState, make_lm_train_step,
                                       model_module)
    cfg = xing4.xing4_tiny(experts_held=4, held_start=4, remat=True,
                           layer_rows=1, loss_chunks=4)
    assert model_module(cfg) is xing4
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh,
                                                 learning_rate=1e-3)
    params, state = init_fn(jax.random.key(0))
    assert isinstance(state, StepState)
    assert state.model["bias"].shape == (3, 8)      # 2 layers + the module
    rng = np.random.default_rng(0)
    batch = place({"tokens": rng.integers(0, 256, (2, 64), dtype=np.int32),
                   "loss_mask": np.ones((2, 64), np.int32)})
    first = None
    for _ in range(3):
        params, state, m = step_fn(params, state, batch)
        first = first or m
    assert float(m["loss"]) < float(first["loss"])
    assert abs(float(first["loss"]) - float(
        first["main_loss"] + 0.3 * first["mtp_loss"])) < 1e-5
    assert first["moe_choices"].shape == (3, 128, 4)
    assert float(first["moe_dropped"]) == 0.0
    assert 0 <= float(first["hc_sinkhorn_residual"]) < 1e-3
    assert float(jnp.abs(state.model["bias"]).max()) > 0


def test_report_records_the_module_s_loss_and_the_residual():
    from ray_tpu.train import _context
    got = _context._loop_readings({"mtp_loss": jnp.float32(9.5), "loss": 1.0,
                                   "hc_sinkhorn_residual": jnp.float32(1e-6)})
    assert got == {"ray_tpu_lm_mtp_loss": 9.5,
                   "ray_tpu_hc_sinkhorn_residual": pytest.approx(1e-6)}


def test_benchmark_cell_rehearses_on_the_cpu():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "xing4.0-29b-a4b.train-mhc8k", "--seed", str(2 ** 31 + 5),
         "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and "hc_sinkhorn_residual.mhc8k" in \
        last["metrics_named"]
