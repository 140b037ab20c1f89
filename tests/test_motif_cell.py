"""The motif model (Motif-3-Beta's block) beside its reference's whole
(``tests/test_motif.py``, which is the suite's longest file with two cases
alone: ``--dist loadfile`` keeps a file on one worker): what a wrong reading
of the noise head, the window or the clamps would leave standing, the
kernels under remat, rows at a time and loss chunks, the share of an
expert-parallel layer, what is refused; the train step's state and report,
the scopes and counters of its compiled step, the published stack and the
cell's cut, and a CPU rehearsal of its benchmark cell."""

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
from motif_cases import _setup, _sizes  # noqa: E402


pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


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


# ---------------------------------------------- the pieces it brought

def test_the_noise_head_the_window_and_the_clamps_are_seen():
    """What a wrong reading would leave standing: with every layer full,
    with PolyNorm's bias unclamped or its scale left out, or with a dead
    pair the loss is another number (the tolerances above are 1e-5)."""
    cfg, params, bias, batch = _setup(mtp_layers=0, layers=3,
                                      sliding_window_period=2)
    run = jax.jit(lambda p, c: motif.loss_fn(p, batch, c, {"bias": bias}),
                  static_argnums=1)
    loss = lambda c, p=params: float(run(p, c))
    base = loss(cfg)
    assert abs(loss(cfg.replace(sliding_window=32)) - base) > 1e-4
    assert abs(loss(cfg.replace(polynorm_bias_clamp=5.0,
                                polynorm_output_scale=1.0)) - base) > 1e-4
    dead = jax.tree_util.tree_map_with_path(
        lambda path, a: a - 50.0 if "w_lambda" in str(path[-1]) else a,
        params)        # sigmoid(lambda) -> 0 wherever h's entries sum > 0
    assert abs(loss(cfg, dead) - base) > 1e-4
    tiny_clamp = loss(cfg.replace(hidden_clamp=1e-3))
    assert np.isfinite(tiny_clamp) and abs(tiny_clamp - base) > 1e-4


def test_kernels_remat_rows_at_a_time_and_loss_chunks_change_nothing():
    """The model on the flash kernels (interpreted: the call in parts with a
    group of five, windowed and full under one scanned body's ``lax.cond``)
    under the remat, a row of a layer at a time and the loss in chunks,
    against itself on the ``jnp`` attention, whole: the loss to 1e-5, the
    loads and ``gdla_lambda`` the same, every gradient to 1e-2 of its
    leaf."""
    cfg, params, bias, batch = _setup(rows=2, seq=64, hidden=128,
                                      mtp_layers=0, layers=3,
                                      sliding_window_period=2)
    run = jax.jit(jax.value_and_grad(lambda p, c: motif.loss_and_report(
        p, batch, c, {"bias": bias}), has_aux=True), static_argnums=1)
    (want, want_report), want_grads = run(params, cfg)
    (got, report), grads = run(params, cfg.replace(
        attention_impl="flash_interpret", remat=True, loss_chunks=4,
        layer_rows=1))
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    for name in ("counts", "dropped", "top"):
        np.testing.assert_array_equal(report[name], want_report[name])
    np.testing.assert_allclose(report["gdla_lambda"],
                               want_report["gdla_lambda"], atol=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(g - w)) < 1e-2 * float(
            jnp.linalg.norm(w)) + 1e-7
    three = {k: jnp.concatenate([v, v[:1]]) for k, v in batch.items()}
    with pytest.raises(ValueError, match="layer_rows=2"):
        motif.loss_fn(params, three, cfg.replace(layer_rows=2))


def test_two_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """Each share writes back through the same maps and adds the shared
    expert and its own experts' part: at 16 experts in 2 shares of 8 the
    routed parts of both shares, with the shared expert and the
    hyper-connection's write-back counted once, are the uncut reference
    layer (PolyNorm's numbers the same on every share)."""
    cfg, params, _, _ = _setup(num_experts=16, top_k=4, mtp_layers=0)
    s = _sizes(cfg)
    layer = jax.tree.map(lambda a: a[0], params["moe"])
    bias = 0.3 * jax.random.normal(jax.random.key(2), (16,))
    X = jax.random.normal(jax.random.key(3), (2, 4, 32, cfg.hidden))
    Xr = jnp.swapaxes(X, 1, 2)
    # The sublayer's reading and maps, which every share computes alike.
    H_pre, H_post, H_res = hyper.hc_maps(
        X, layer["hc_mlp_phi"], layer["hc_mlp_b"], layer["hc_mlp_alpha"],
        20, 1e-6, cfg.hc_clamp, cfg.norm_eps)
    h = rms_norm(hyper.hc_collect(X, H_pre), layer["mlp_norm"], cfg.norm_eps)
    weights = motif._poly_weights(cfg)
    shared = motif.afmoe._feed_forward(
        h, layer["shared_gate"], layer["shared_up"], layer["shared_down"],
        cfg.dtype, "poly_norm", weights(layer["shared_poly"]))
    routed, held = 0.0, 0
    for share in range(2):
        mine = cfg.replace(experts_held=8, held_start=8 * share)
        part = {k: (v[8 * share:8 * share + 8]
                    if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in layer.items()}
        out, loads = motif._moe(mine, h, part, bias, "poly_norm",
                                act_weights=weights)
        routed = routed + out - shared
        held += int(loads["counts"][8 * share:8 * share + 8].sum())
    assert held == 64 * 4                      # every assignment, once
    got = hyper.hc_deposit(X, H_res, H_post, shared + routed)
    want = ref.sublayer(
        Xr, layer, "mlp", lambda h: ref.feed_forward(h, layer, bias, s)[0], s)
    np.testing.assert_allclose(jnp.swapaxes(got, 1, 2), want, atol=3e-5)


def test_a_mesh_and_a_pipeline_are_refused_by_name():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    cfg = motif.motif_tiny()
    params = jax.eval_shape(lambda k: motif.init_params(cfg, k),
                            jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    with pytest.raises(NotImplementedError, match="pp_microbatches"):
        jax.eval_shape(lambda p, b: motif.loss_fn(
            p, b, cfg.replace(pp_microbatches=2)), params, batch)
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(fsdp=2),
                                   devices=jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="motif on a mesh"):
            jax.eval_shape(lambda p, b: motif.loss_fn(p, b, cfg), params,
                           batch)
    finally:
        set_global_mesh(before)
    with pytest.raises(ValueError, match="noise head"):
        motif.motif_tiny(num_noise_heads=5)


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
