"""The motif model (Motif-3-Beta's block) through the train step and the
benchmark: its state and report, the scopes and counters of its compiled
step, the published stack and the cell's cut, and a CPU rehearsal of its
benchmark cell.  (A file beside ``tests/test_motif.py``, which holds the
model against its reference: ``--dist loadfile`` keeps a file on one
worker, and the two together were the suite's longest.)"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import _lm, motif, xing4
from ray_tpu.ops import hyper
from ray_tpu.ops.norms import rms_norm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_motif as ref  # noqa: E402
from benchmark.archs import Motif as arch  # noqa: E402


@pytest.fixture(autouse=True)
def no_mesh_left_by_another_file():
    """``build_mesh`` sets the process's global mesh, and a test file that
    ran before this one in the same worker may have left one of several
    devices, which the model refuses by name: every test here starts
    without one and hands back what it found."""
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    before = get_global_mesh()
    set_global_mesh(None)
    yield
    set_global_mesh(before)


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers, "first_layer": cfg.first_layer,
            "H": cfg.heads, "Hkv": cfg.kv_heads,
            "noise": cfg.num_noise_heads, "rq": cfg.q_lora_rank,
            "rkv": cfg.kv_lora_rank, "dn": cfg.qk_nope_head_dim,
            "dr": cfg.qk_rope_head_dim, "dv": cfg.v_head_dim,
            "W": cfg.sliding_window, "period": cfg.sliding_window_period,
            "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "Ms": cfg.moe_mlp_dim * cfg.num_shared_experts,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "n": cfg.hc_mult,
            "hc_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
            "hc_lo": cfg.hc_clamp[0], "hc_hi": cfg.hc_clamp[1],
            "poly_scale": cfg.polynorm_output_scale,
            "poly_clamp": cfg.polynorm_bias_clamp,
            "hidden_clamp": cfg.hidden_clamp, "mtp": cfg.mtp_layers,
            "mtp_weight": cfg.mtp_loss_weight, "theta": cfg.rope_theta,
            "eps": cfg.norm_eps}



def test_train_step_trains_through_model_module_and_reports():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import (StepState, make_lm_train_step,
                                       model_module)
    from ray_tpu.train import _context
    cfg = motif.motif_tiny(experts_held=4, held_start=4, remat=True,
                           layer_rows=1, loss_chunks=4)
    assert model_module(cfg) is motif
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh,
                                                 learning_rate=1e-3)
    params, state = init_fn(jax.random.key(0))
    assert isinstance(state, StepState)
    assert state.model["bias"].shape == (5, 8)      # 4 layers + the module
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
    assert first["moe_choices"].shape == (5, 128, 4)
    assert float(first["moe_dropped"]) == 0.0
    assert 0 <= float(first["hc_sinkhorn_residual"]) < 1e-3
    assert 0.3 < float(first["gdla_lambda_mean"]) < 0.7
    # The bias moves by the published rate's size.
    # (three steps of the sign rule at 1e-4, less the mean of each)
    assert 0 < float(jnp.abs(state.model["bias"]).max()) <= 6e-4
    assert _context._loop_readings({"gdla_lambda_mean": jnp.float32(0.5),
                                    "loss": 1.0}) == {
        "ray_tpu_gdla_lambda_mean": 0.5}


def test_compiled_step_names_the_scopes_and_counts_the_geometry(monkeypatch):
    """What ``gdla_device_share``, ``polynorm_device_share`` and the two
    rooflines find in the compiled step's text: the differential combine,
    the gate, both kinds of kernel call under scopes of their own, PolyNorm
    in all three kinds of feed-forward; and the geometry counter's new
    tags."""
    from benchmark import scopes
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    from ray_tpu.util import telemetry
    seen = []
    monkeypatch.setattr(
        telemetry, "inc", lambda name, value=1.0, tags=None: seen.append(
            tags) if name == "ray_tpu_mla_call_geometry_total" else None)
    cfg = motif.motif_tiny(experts_held=4, held_start=4, remat=True,
                           layer_rows=1, hidden=128, mtp_layers=0,
                           attention_impl="flash_interpret")
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh, learning_rate=1e-3)
    params, state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((1, 64), jnp.int32)
             for k in ("tokens", "loss_mask")}
    names = list(scopes.op_names(step_fn.lower(params, state, batch)
                                 .compile().as_text()).values())
    by = {"scopes": dict.fromkeys({scopes.scope_path(n) for n in names},
                                  1.0)}
    from benchmark.layer_metrics.conv_device_share import seconds_under
    for scope in ("block/attn/mla/q", "block/attn/mla/kv_b",
                  "block/attn/mla/diff", "block/attn/mla/gate",
                  "block/attn/mla/out", "block/attn_window",
                  "block/attn_full", "block/mlp/polynorm",
                  "block/moe/shared/polynorm", "block/moe/experts/polynorm",
                  "block/hc/collect", "polynorm"):
        assert seconds_under(by, scope) > 0, scope
    windowed = [n for n in names if "flash_fwd_d192v128_w16" in n]
    assert windowed and all("block/attn_window" in n for n in windowed)
    full = [n for n in names if "flash_fwd_d192v128/" in n]
    assert full and all("block/attn_full" in n for n in full)
    keys = telemetry.CATALOG["ray_tpu_mla_call_geometry_total"]["tag_keys"]
    assert seen and {tuple(t[k] for k in keys) for t in seen} == {
        ("10", "128", "64", "128", "48", "1", "64", "2", "2", "16")}


def test_published_stack_is_built_but_not_run():
    cfg = motif.MotifConfig()
    shapes = jax.eval_shape(
        lambda k: motif.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        motif.num_params(cfg)
    assert shapes["moe"]["w_gate"].shape == (51, 384, 4096, 1280)
    assert shapes["moe"]["wkv_b"].shape == (51, 512, 16, 256)
    assert shapes["dense"]["wq_b"].shape == (2, 1024, 80, 192)
    assert shapes["dense"]["wo"].shape == (2, 64, 128, 4096)
    assert shapes["moe"]["w_lambda"].shape == (51, 4096, 64)
    assert sum(cfg.full(i) for i in range(53)) == 13
    # The benchmark's cut: its layout is the program's, its count the
    # issue's (1,412 M) less the leading dense layer.
    with open(os.path.join(
            ROOT, "benchmark/configs/motif-3-beta.json")) as f:
        config = json.load(f)
    s = arch.sizes_of(config)
    cut = arch.program_config(s, 8192, config["train"])
    shape_of = lambda tree: jax.tree.map(lambda x: x[0], tree,
                                         is_leaf=_lm.is_shape)
    assert shape_of(arch.shapes(s)) == shape_of(motif.param_shapes(cut))
    assert arch.parameters(s)["held"] == motif.num_params(cut) == \
        config["parameters"] == 1168156920
    # with the leading dense layer the issue planned (dropped: the step
    # then held 99.8 % of the chip), its 1,412 M to the parameter
    assert motif.num_params(cut.replace(layers=5, num_dense_layers=1)) == \
        1411698482
    assert s == {**_sizes(cut), "bias_update_rate": cut.bias_update_rate}
    # published layers 2-5: window, full, window, window
    assert [cut.full(i) for i in range(4)] == [False, True, False, False]
    assert shapes["dense"]["wq_a"].shape[0] == 2 and jax.eval_shape(
        lambda k: motif.init_params(cut, k), jax.random.key(0))[
            "dense"]["wq_a"].shape == (0, 4096, 1024)
    # The three stacks that shared the latent code before keep their tree.
    assert "w_lambda" not in xing4.param_shapes(xing4.xing4_tiny())["moe"]


@pytest.mark.slow
def test_benchmark_cell_rehearses_on_the_cpu():
    """Marked slow (90 s: a cluster, a compile and the check at toy sizes):
    the driver's run of this suite stood at 1,400 of its 1,470 s with it
    here; ``benchmark/tests/test_gdla.py`` rehearses the cell too and reads
    every entry's line."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "motif-3-beta.train-gdla8k", "--seed", str(2 ** 31 + 5),
         "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and "hc_sinkhorn_residual.mhc8k" in \
        last["metrics_named"]
    assert "[gdla] " in done.stdout
