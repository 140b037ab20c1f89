"""The pieces of the xing4 model (Xing4.0's block): the hyper-connection
passes and Sinkhorn's maps against the reference, one lane as the pre-norm
layer, the share of an expert-parallel layer, and remat, rows at a time and
loss chunks changing nothing.  (Cut from ``tests/test_xing4.py``, PR 59.)"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import xing4
from ray_tpu.ops import hyper

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_xing4 as ref  # noqa: E402

from xing4_cases import _setup, _sizes  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def test_remat_rows_at_a_time_and_loss_chunks_do_not_change_the_loss():
    cfg, params, bias, batch = _setup(seq=32)
    run = jax.jit(jax.value_and_grad(lambda p, c: xing4.loss_and_report(
        p, batch, c, {"bias": bias}), has_aux=True), static_argnums=1)
    (want, want_report), want_grads = run(params, cfg)
    (got, report), grads = run(params, cfg.replace(remat=True, loss_chunks=4,
                                                   layer_rows=1))
    assert abs(float(got) - float(want)) < 1e-5
    for name in ("counts", "dropped", "top"):
        np.testing.assert_array_equal(report[name], want_report[name])
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(g - w)) < 1e-2 * float(
            jnp.linalg.norm(w))
    three = {k: jnp.concatenate([v, v[:1]]) for k, v in batch.items()}
    with pytest.raises(ValueError, match="layer_rows=2"):
        xing4.loss_fn(params, three, cfg.replace(layer_rows=2))


# --------------------------------------------------- hyper-connections

def test_sinkhorn_is_doubly_stochastic_from_clamped_extremes():
    """Twenty iterations from logits at both ends of the clamp: rows and
    columns sum to one to 1e-5."""
    rng = np.random.default_rng(0)
    R = rng.choice([-30.0, 30.0, 0.0, 3.0], size=(3, 4, 4, 64)
                   ).astype(np.float32)
    # A matrix that is one permutation's at the extremes stays one; mixed
    # ones converge.
    R[0] = np.where(np.eye(4)[:, :, None] > 0, 30.0, -30.0)
    M = hyper.sinkhorn(jnp.asarray(R), 20, 1e-6)
    assert float(hyper.sinkhorn_residual(M[0])) < 1e-5
    rows, cols = jnp.sum(M, axis=-2), jnp.sum(M, axis=-3)
    well = np.abs(np.asarray(rows) - 1).max(axis=1) < 1e-5
    assert well.mean() > 0.6 and float(jnp.abs(cols[0] - 1).max()) < 1e-5
    assert np.isfinite(np.asarray(M)).all() and float(M.min()) >= 0


def test_sinkhorn_gradient_is_the_plain_loop_s():
    R = jax.random.normal(jax.random.key(0), (2, 4, 4, 8))
    weigh = jax.random.normal(jax.random.key(1), (2, 8, 4, 4))
    got = jax.grad(lambda R: jnp.sum(
        jnp.moveaxis(hyper.sinkhorn(R, 20, 1e-6), -1, 1) * weigh))(R)
    want = jax.grad(lambda R: jnp.sum(ref.sinkhorn(R, 20, 1e-6) * weigh))(
        jnp.moveaxis(R, -1, 1))
    np.testing.assert_allclose(jnp.moveaxis(got, -1, 1), want, atol=1e-6)


def test_maps_collect_and_deposit_match_the_reference():
    cfg, params, _, _ = _setup()
    s, w = _sizes(cfg), jax.tree.map(lambda a: a[0], params["dense"])
    X = jax.random.normal(jax.random.key(5), (2, 4, 16, cfg.hidden))
    y = jax.random.normal(jax.random.key(6), (2, 16, cfg.hidden))
    H_pre, H_post, H_res = hyper.hc_maps(
        X, w["hc_attn_phi"], w["hc_attn_b"], w["hc_attn_alpha"], 20, 1e-6,
        (-30.0, 30.0), cfg.norm_eps)
    Xr = jnp.swapaxes(X, 1, 2)                          # [B, S, n, C]
    r_pre, r_post, r_res = ref.maps(Xr, w, "attn", s)
    np.testing.assert_allclose(jnp.swapaxes(H_pre, 1, 2), r_pre, atol=1e-5)
    np.testing.assert_allclose(jnp.swapaxes(H_post, 1, 2), r_post, atol=1e-5)
    np.testing.assert_allclose(jnp.moveaxis(H_res, -1, 1), r_res, atol=1e-5)
    # The maps differ between tokens and between lanes.
    assert float(jnp.std(H_pre, axis=2).min()) > 0.01
    assert float(jnp.std(H_pre, axis=1).min()) > 0.01
    assert float(hyper.sinkhorn_residual(H_res)) < 1e-3
    np.testing.assert_allclose(
        hyper.hc_collect(X, H_pre),
        jnp.einsum("bsj,bsjc->bsc", r_pre, Xr), atol=1e-5)
    np.testing.assert_allclose(
        jnp.swapaxes(hyper.hc_deposit(X, H_res, H_post, y), 1, 2),
        jnp.einsum("bsij,bsjc->bsic", r_res, Xr)
        + r_post[..., None] * y[:, :, None], atol=1e-5)


def test_static_maps_are_the_same_for_every_token():
    """With the gains at 0 the maps are sigmoid(b), 2 sigmoid(b) and
    Sinkhorn(b): the static hyper-connection."""
    X = jax.random.normal(jax.random.key(0), (1, 4, 8, 32))
    phi = jax.random.normal(jax.random.key(1), (128, 24))
    b = jax.random.normal(jax.random.key(2), (24,))
    H_pre, H_post, H_res = hyper.hc_maps(X, phi, b, jnp.zeros(3), 20, 1e-6,
                                         (-30.0, 30.0))
    np.testing.assert_allclose(H_pre[0, :, 0], jax.nn.sigmoid(b[:4]),
                               atol=1e-6)
    assert float(jnp.std(H_pre, axis=2).max()) < 1e-6
    assert float(jnp.std(H_res, axis=3).max()) < 1e-6
    np.testing.assert_allclose(H_post[0, :, 3], 2 * jax.nn.sigmoid(b[4:8]),
                               atol=1e-6)


def test_one_lane_is_the_pre_norm_layer():
    """``hc_mult`` 1: no map is computed, no hyper-connection weight exists,
    and a layer is x + F(N(x))."""
    from ray_tpu.ops.norms import rms_norm
    from ray_tpu.ops.rope import rope_lane_tables
    cfg = xing4.xing4_tiny(hc_mult=1)
    params = jax.jit(lambda key: xing4.init_params(cfg, key))(
        jax.random.key(0))
    assert not [k for k in params["dense"] if k.startswith("hc_")]
    w = jax.tree.map(lambda a: a[0], params["dense"])
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.hidden))
    tables = rope_lane_tables(cfg.qk_rope_head_dim, 64, cfg.rope_theta,
                              cfg.yarn)
    got, report = xing4._layer(cfg, *tables, x[:, None], w)
    a = x + xing4._mla(cfg, *tables, rms_norm(x, w["attn_norm"], 1e-6), w)
    want = a + xing4._swiglu(rms_norm(a, w["mlp_norm"], 1e-6), w["w_gate"],
                             w["w_up"], w["w_down"], cfg.dtype)
    np.testing.assert_allclose(got[:, 0], want, atol=1e-5)
    assert float(report["hc_residual"]) == 0.0
    loss = xing4.loss_fn(params, {"tokens": jnp.zeros((1, 16), jnp.int32)},
                         cfg)
    assert np.isfinite(float(loss))


# ------------------------------------------------------------ the share

def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """Each share writes back through the same maps and adds the shared
    expert and its own experts' part: the routed parts of all 8 shares,
    with the shared expert and the hyper-connection's write-back counted
    once, are the uncut reference layer."""
    cfg, params, _, _ = _setup(num_experts=16, top_k=4)
    s = _sizes(cfg)
    layer = jax.tree.map(lambda a: a[0], params["moe"])
    bias = 0.3 * jax.random.normal(jax.random.key(2), (16,))
    X = jax.random.normal(jax.random.key(3), (2, 4, 32, cfg.hidden))
    Xr = jnp.swapaxes(X, 1, 2)
    # The sublayer's reading and maps, which every share computes alike.
    H_pre, H_post, H_res = hyper.hc_maps(
        X, layer["hc_mlp_phi"], layer["hc_mlp_b"], layer["hc_mlp_alpha"],
        20, 1e-6, (-30.0, 30.0), cfg.norm_eps)
    from ray_tpu.ops.norms import rms_norm
    h = rms_norm(hyper.hc_collect(X, H_pre), layer["mlp_norm"], cfg.norm_eps)
    shared = xing4._swiglu(h, layer["shared_gate"], layer["shared_up"],
                           layer["shared_down"], cfg.dtype)
    routed, held = 0.0, 0
    for share in range(8):
        mine = cfg.replace(experts_held=2, held_start=2 * share)
        part = {k: (v[2 * share:2 * share + 2]
                    if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in layer.items()}
        out, loads = xing4._moe(mine, h, part, bias)
        routed = routed + out - shared
        held += int(loads["counts"][2 * share:2 * share + 2].sum())
    assert held == 64 * 4                      # every assignment, once
    got = hyper.hc_deposit(X, H_res, H_post, shared + routed)
    want = ref.sublayer(
        Xr, layer, "mlp", lambda h: ref.feed_forward(h, layer, bias, s)[0], s)
    np.testing.assert_allclose(jnp.swapaxes(got, 1, 2), want, atol=3e-5)
