"""The motif model (Motif-3-Beta's block) against its plain reference, and
the pieces it brought: window and full layers on the one latent stack under
a key head shared by five query heads, the differential pair and its gate,
PolyNorm in all three kinds of feed-forward, the share of an expert-parallel
layer.  (The train step, its scopes and counters, the published stack and
the cell's rehearsal: ``tests/test_motif_cell.py``.)"""

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


def _setup(seed=0, rows=2, seq=32, **kw):
    cfg = motif.motif_tiny(**kw)
    params = motif.init_params(cfg, jax.random.key(seed))
    # Norm weights away from one, maps that differ between tokens and lanes
    # (gains of 1, a random b), PolyNorm's numbers of every sign with a bias
    # that passes its clamp in some modules, and a selection bias large
    # enough to change which experts are chosen.
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        name = str(path[-1])
        if "alpha" in name:
            return jnp.ones_like(a)
        if name.endswith("_b']") or "poly" in name:
            return jax.random.normal(next(keys), a.shape)
        if "norm" in name:
            return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
        return a

    params = jax.tree_util.tree_map_with_path(shake, params)
    bias = 0.3 * jax.random.normal(
        next(keys), (cfg.expert_layers + cfg.mtp_layers, cfg.num_experts))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (rows, seq), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (rows, seq),
                                              dtype=np.int32))}
    return cfg, params, bias, batch


@pytest.mark.parametrize("module", [1, 0])
def test_model_matches_reference_loss_and_every_gradient(module):
    """The program's loss, its parts and its gradients, on a share of the
    experts (2 of 8 from the fifth), window (16 of 32 tokens) and full
    layers both in the stack (one scanned body, the kind a traced flag),
    with and without the prediction module, against the reference's walk
    in blocks (the judged leaves of every kind; what the chip's check
    runs) and, without the module, against ``jax.grad`` of the reference's
    pieces put together (EVERY leaf; with the module that one compile is
    two minutes of this suite, and the module's own leaves are Xing4.0's,
    judged there).  Float32 on both sides; two evaluations of the reference
    alone differ by up to 1e-3 in a gradient here (the walk against the
    whole), so a leaf is held to 3e-2 of its norm and the judged tree to
    1e-2, as Xing4.0's; PolyNorm's four numbers a module to 1e-1: each is
    one sum over every token and channel of its module of products of
    mixed sign, where float32's order of summation shows (the reference's
    whole, jitted and not, differs from itself by 2.2e-2 of ``mlp_poly``'s
    norm with the module in the stack; alone the activation's gradients
    are exact, ``tests/test_ops.py``)."""
    cfg, params, bias, batch = _setup(experts_held=2, held_start=4,
                                      mtp_layers=module, layers=3,
                                      sliding_window_period=2)
    assert params["moe"]["w_gate"].shape[1] == 2
    # window, full, window (and the module's full): three layers, as
    # Xing4.0's test has, because the stream amplifies float32's rounding
    # about fivefold a layer (a relative 1e-6 in the embedding is 2e-4 after
    # three layers and 5e-4 after five; Xing4.0's reference 1e-4 after
    # three), and five layers' gradients differ between two evaluations of
    # the reference alone by more than 3e-2 of a leaf.
    assert [cfg.full(i) for i in range(4)] == [False, True, False, True]
    s = _sizes(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda p: motif.loss_and_report(p, batch, cfg, {"bias": bias}),
        has_aux=True))(params)
    want, parts, judged, tops = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for name in ("main_loss", "mtp_loss"):
        assert abs(float(report[name]) - float(parts[name])) < 1e-5 * max(
            float(parts[name]), 1.0), name
    assert (float(parts["mtp_loss"]) > 0) == bool(module)
    assert float(ref.relative_distance(arch.judged_of(grads), judged)) < 1e-2
    for alone in (arch.norms_of, arch.maps_of, arch.polys_of,
                  arch.lambdas_of):
        assert float(ref.relative_distance(alone(grads), alone(judged))) \
            < 1e-2, alone.__name__
    np.testing.assert_array_equal(tops, report["top"])
    if module:
        return

    def whole(params):
        X = ref._lanes(params["embed"][batch["tokens"]], s["n"])
        for w, b, full in ref._stack(params, bias, s):
            X, _ = ref.layer(X, w, b, s, full)
        return ref.tail(jnp.sum(X, axis=2), params["final_norm"],
                        params["lm_head"], params["embed"], None, bias[-1],
                        batch["tokens"], batch["loss_mask"], s)[0]

    whole_loss, want_grads = jax.jit(jax.value_and_grad(whole))(params)
    assert abs(float(whole_loss) - float(want)) < 1e-5 * float(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and float(jnp.linalg.norm(w)) > 0, path
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(g - w)) < (
            1e-1 if "poly" in name else 3e-2) * float(
            jnp.linalg.norm(w)), name
    np.testing.assert_array_equal(
        ref.routing(params, bias, batch["tokens"], s), tops)
    # Next-token logits too (the module is a training part).
    np.testing.assert_allclose(
        motif.forward(params, batch["tokens"], cfg, {"bias": bias}),
        ref.logits(params, bias, batch["tokens"], s), atol=2e-3)
    # The int8 control is another function: it fails where rounding passes.
    _, _, control, _ = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s, quant="int8")
    assert float(ref.relative_distance(control, judged)) > 3e-2


def test_the_noise_head_the_window_and_the_clamps_are_seen():
    """What a wrong reading would leave standing: with every layer full,
    with PolyNorm's bias unclamped or its scale left out, or with a dead
    pair the loss is another number (the tolerances above are 1e-5)."""
    cfg, params, bias, batch = _setup(mtp_layers=0, layers=3,
                                      sliding_window_period=2)
    loss = lambda c, p=params: float(motif.loss_fn(p, batch, c,
                                                   {"bias": bias}))
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
