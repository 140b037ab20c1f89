"""The xing4 model (Xing4.0's block) against its plain reference, and the
pieces it brought: flash attention with a value head size of its own, the
hyper-connection passes and Sinkhorn's maps, yarn's rotary table, the
prediction module's targets, the share of an expert-parallel layer, the
train step's state and report, and a CPU rehearsal of its benchmark cell."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import _lm, xing4
from ray_tpu.ops import hyper
from ray_tpu.ops.attention import flash_attention, reference_attention
from ray_tpu.ops.rope import rope_frequencies

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_xing4 as ref  # noqa: E402
from benchmark.archs import xing4_0 as arch  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    y = cfg.yarn
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers, "H": cfg.heads,
            "rq": cfg.q_lora_rank, "rkv": cfg.kv_lora_rank,
            "dn": cfg.qk_nope_head_dim, "dr": cfg.qk_rope_head_dim,
            "dv": cfg.v_head_dim, "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "Ms": cfg.moe_mlp_dim * cfg.num_shared_experts,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "n": cfg.hc_mult,
            "hc_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
            "hc_lo": cfg.hc_clamp[0], "hc_hi": cfg.hc_clamp[1],
            "mtp_weight": cfg.mtp_loss_weight, "theta": cfg.rope_theta,
            "yarn_factor": y.factor,
            "yarn_original": y.original_max_position_embeddings,
            "yarn_beta_fast": y.beta_fast, "yarn_beta_slow": y.beta_slow,
            "yarn_mscale": y.mscale, "yarn_mscale_all_dim": y.mscale_all_dim,
            "eps": cfg.norm_eps}


def _setup(seed=0, rows=2, seq=48, **kw):
    cfg = xing4.xing4_tiny(**kw)
    params = xing4.init_params(cfg, jax.random.key(seed))
    # Norm weights away from one, maps that differ between tokens and lanes
    # (gains of 1, a random b), and a selection bias large enough to change
    # which experts are chosen.
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        name = str(path[-1])
        if "alpha" in name:
            return jnp.ones_like(a)
        if name.endswith("_b']"):
            return jax.random.normal(next(keys), a.shape)
        if "norm" in name:
            return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
        return a

    params = jax.tree_util.tree_map_with_path(shake, params)
    bias = 0.3 * jax.random.normal(
        next(keys), (cfg.expert_layers + 1, cfg.num_experts))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (rows, seq), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (rows, seq),
                                              dtype=np.int32))}
    return cfg, params, bias, batch


def test_model_matches_reference_both_losses_and_every_gradient():
    """The program's loss, its two parts and the gradient of every leaf, on a
    share of the experts (2 of 8 from the fifth), against ``jax.grad`` of
    the reference's pieces put together (every leaf) and against the
    reference's walk in blocks (the judged leaves; what the chip's check
    runs).  Float32 on both sides; two evaluations of the reference alone
    differ by up to 1e-3 in a gradient here (the walk against the whole), so
    a leaf is held to 3e-2 of its norm and the judged tree to 1e-2."""
    cfg, params, bias, batch = _setup(seq=32, experts_held=2, held_start=4)
    assert params["moe"]["w_gate"].shape[1] == 2
    s = _sizes(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda p: xing4.loss_and_report(p, batch, cfg, {"bias": bias}),
        has_aux=True))(params)

    def whole(params):
        X = ref._lanes(params["embed"][batch["tokens"]], s["n"])
        for w, b in ref._stack(params, bias, s):
            X, _ = ref.layer(X, w, b, s)
        return ref.tail(jnp.sum(X, axis=2), params["final_norm"],
                        params["lm_head"], params["embed"], params["mtp"],
                        bias[-1], batch["tokens"], batch["loss_mask"], s)

    (want, (main, module, _)), want_grads = jax.jit(jax.value_and_grad(
        whole, has_aux=True))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert abs(float(report["main_loss"]) - float(main)) < 1e-5 * float(main)
    assert abs(float(report["mtp_loss"]) - float(module)) < 1e-5 * float(
        module)
    assert abs(float(loss) - float(main + 0.3 * module)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and float(jnp.linalg.norm(w)) > 0, path
        assert float(jnp.linalg.norm(g - w)) < 3e-2 * float(
            jnp.linalg.norm(w)), jax.tree_util.keystr(path)
    w_loss, parts, judged, tops = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert abs(float(w_loss) - float(want)) < 1e-5 * float(want)
    assert abs(float(parts["mtp_loss"]) - float(module)) < 1e-5
    assert float(ref.relative_distance(arch.judged_of(grads), judged)) < 1e-2
    np.testing.assert_array_equal(tops, report["top"])
    np.testing.assert_array_equal(
        ref.routing(params, bias, batch["tokens"], s), tops)
    # The int8 control is another function: it fails where rounding passes.
    _, _, control, _ = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s, quant="int8")
    assert float(ref.relative_distance(control, judged)) > 3e-2


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


# ------------------------------------------------- flash at 192 / 128

def _qkv(D, Dv, B=1, H=2, Hkv=2, S=256, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, H, S, D)),
            jax.random.normal(ks[1], (B, Hkv, S, D)),
            jax.random.normal(ks[2], (B, Hkv, S, Dv)),
            jax.random.normal(ks[3], (B, H, S, Dv)))


@pytest.mark.parametrize("D,Dv,Hkv", [(192, 128, 2), (192, 128, 1),
                                      (128, 128, 2), (64, 128, 2)])
def test_flash_with_a_value_head_size_of_its_own(D, Dv, Hkv):
    """Forward and the three gradients in interpret mode against
    ``reference_attention``: at latent attention's 192 / 128, under a
    group, at a key narrower than the value, and unchanged at 128 / 128."""
    q, k, v, do = _qkv(D, Dv, Hkv=Hkv)
    scale = 0.11
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=scale, block_q=128, block_k=128,
        interpret=True)
    plain = lambda q, k, v: reference_attention(q, k, v, causal=True,
                                                scale=scale)
    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    assert out.shape == (1, 2, 256, Dv)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for g, w in zip(vjp(do), want_vjp(do)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)


def _parts(B=1, H=2, S=256, Dn=128, Dr=64, Dv=128, seed=0):
    """A call in parts as ``xing4._mla`` hands it: (q_n [B, S, H, Dn], q_r
    [B, H, S, Dr]), (kv [B, S, H, Dn + Dv], the ONE k_r [B, 1, S, Dr]) and
    the result's cotangent [B, S, H, Dv]."""
    ks = jax.random.split(jax.random.key(seed), 5)
    return ((jax.random.normal(ks[0], (B, S, H, Dn)),
             jax.random.normal(ks[1], (B, H, S, Dr))),
            (jax.random.normal(ks[2], (B, S, H, Dn + Dv)),
             jax.random.normal(ks[3], (B, 1, S, Dr))),
            jax.random.normal(ks[4], (B, S, H, Dv)))


def _concatenated(q, k):
    """The 192-wide operands the parts stand for, head-major: q, k with the
    one rotary key head under every head, and v."""
    (q_n, q_r), (kv, k_r) = q, k
    Dn, H = q_n.shape[-1], q_n.shape[2]
    turn = lambda x: jnp.swapaxes(x, 1, 2)
    return (jnp.concatenate([turn(q_n), q_r], axis=-1),
            jnp.concatenate([turn(kv[..., :Dn]),
                             jnp.repeat(k_r, H, axis=1)], axis=-1),
            turn(kv[..., Dn:]))


@pytest.mark.parametrize("B,H,S,Dv,blocks", [
    (1, 2, 256, 128, 128), (2, 2, 256, 128, 128), (1, 4, 128, 256, None)],
    ids=["192v128", "two_rows", "192v256_default_blocks"])
def test_flash_in_parts_is_the_reference_on_the_concatenated_operands(
        B, H, S, Dv, blocks):
    """The score product in the parts the projections write, in interpret
    mode against ``reference_attention`` on the concatenated operands: the
    result, dq in both parts, dk without position and dv side by side as
    ``kv`` came, and the ONE rotary key head's gradient summed over the
    query heads; with a second batch element (the shared head's index map
    takes the row's batch element and no head)."""
    q, k, do = _parts(B, H, S, Dv=Dv)
    scale = 0.11
    flash = lambda q, k: flash_attention(
        q, k, None, causal=True, scale=scale, block_q=blocks, block_k=blocks,
        interpret=True)

    def plain(q, k):
        return jnp.swapaxes(reference_attention(
            *_concatenated(q, k), causal=True, scale=scale), 1, 2)

    out, vjp = jax.vjp(flash, q, k)
    want, want_vjp = jax.vjp(plain, q, k)
    assert out.shape == (B, S, H, Dv)
    np.testing.assert_allclose(out, want, atol=2e-5)
    (dq_n, dq_r), (dkv, dk_r) = vjp(do)
    (wq_n, wq_r), (wkv, wk_r) = want_vjp(do)
    assert dk_r.shape == (B, 1, S, 64) and dkv.shape == (B, S, H, 128 + Dv)
    for got, w in ((dq_n, wq_n), (dq_r, wq_r), (dkv[..., :128],
                   wkv[..., :128]), (dkv[..., 128:], wkv[..., 128:]),
                   (dk_r, wk_r)):
        assert got.shape == w.shape
        np.testing.assert_allclose(got, w, atol=2e-4)


def test_parts_off_the_lane_tiles_and_on_the_reference_are_put_together():
    """A call in parts may always say what it holds: where a part is not
    whole lane tiles the call is put together and goes the 192-wide way,
    and ``attention``'s reference path takes the same call."""
    from ray_tpu.ops.attention import attention
    q, k, do = _parts(H=2, S=128, Dn=64, Dr=32, Dv=64)
    want = jnp.swapaxes(reference_attention(*_concatenated(q, k)), 1, 2)
    np.testing.assert_allclose(
        flash_attention(q, k, None, interpret=True), want, atol=2e-5)
    np.testing.assert_allclose(
        attention(q, k, None, impl="reference"), want, atol=2e-6)
    with pytest.raises(ValueError, match="a call in parts takes"):
        flash_attention(q, k, do, interpret=True)


def test_flash_names_and_counts_both_head_sizes(monkeypatch):
    """A 192 / 128 call says its sizes in its kernels' names and in the
    geometry counter's tags, and in parts the parts and which operands lay
    as rows too, under the same names; a 128 / 128 call says what it said
    before."""
    from ray_tpu.util import telemetry
    seen = []
    monkeypatch.setattr(telemetry, "inc",
                        lambda name, value=1.0, tags=None: seen.append(
                            (name, tags)))
    for D in (192, 128):
        q, k, v, do = _qkv(D, 128, S=128)
        jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, interpret=True) * do), argnums=(0, 1, 2))).lower(q, k, v)
    q, k, do = _parts(S=128)
    jax.jit(jax.grad(lambda q, k: jnp.sum(flash_attention(
        q, k, None, interpret=True) * do), argnums=(0, 1))).lower(q, k)
    tags = [t for name, t in seen
            if name == "ray_tpu_flash_step_geometry_total"]
    # a key head a query head on the causal square: the backward is the one
    # pass (PR 54), two kernels a call and not three
    wide, plain, parts = tags[:2], tags[2:4], tags[4:]
    names = [f"flash_{k}_d192v128" for k in ("fwd", "bwd")]
    assert [t["kernel"] for t in wide] == names
    assert all(t["d_qk"] == "192" and t["d_v"] == "128" for t in wide)
    assert not any("parts" in t or "rows" in t for t in wide + plain)
    assert [sorted(t) for t in plain] == [
        ["block_k", "block_q", "heads_a_step", "kernel", "scores"]] * 2
    assert [t["kernel"] for t in plain] == ["flash_fwd", "flash_bwd"]
    assert [t["kernel"] for t in parts] == names
    assert all(t["parts"] == "128+64" and t["rows"] == "qkvo"
               and t["d_qk"] == "192" and t["d_v"] == "128"
               and t["heads_a_step"] == "1" for t in parts)
    # but for the two new tags a call in parts counts what a 192-wide does
    assert [{k: v for k, v in t.items() if k not in ("parts", "rows")}
            for t in parts] == wide


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
    params = xing4.init_params(cfg, jax.random.key(0))
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


# ------------------------------------------------ the share, rope, targets

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


def test_yarn_table_is_the_closed_form_at_three_positions():
    cfg = xing4.Xing4Config()
    cos, sin = rope_frequencies(64, 8192, 10000.0, cfg.yarn)
    lo = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                    / (2 * math.log(10000)))
    hi = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                   / (2 * math.log(10000)))
    assert (lo, hi) == (10, 23)
    for pos in (1, 777, 8191):
        for i in (0, 9, 10, 16, 23, 31):
            theta = 10000.0 ** (-2 * i / 64)
            ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
            freq = theta * (1 - ramp) + theta / 64 * ramp
            assert abs(float(cos[pos, i]) - math.cos(pos * freq)) < 2e-3
            assert abs(float(sin[pos, i]) - math.sin(pos * freq)) < 2e-3
    # mscale = mscale_all_dim: the tables are not scaled, the scores are.
    assert cfg.yarn.table_scale == 1.0
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 1.41589 ** 2) < 1e-5
    np.testing.assert_allclose(jnp.cos(ref.yarn_angles(64, _sizes(cfg))),
                               cos[:64], atol=2e-3)


def test_module_is_judged_on_token_t_plus_2_up_to_s_minus_3():
    tokens = jnp.arange(10, 18)[None]                   # S = 8
    targets, mask, _ = _lm.targets_and_mask({"tokens": tokens})
    t2, m2 = xing4.mtp_targets_and_mask(targets, mask)
    np.testing.assert_array_equal(t2[0, :6], tokens[0, 2:])
    np.testing.assert_array_equal(m2[0], [1, 1, 1, 1, 1, 1, 0, 0])
    # Under a mask with holes the module follows the main loss's, a
    # position on.
    mask = jnp.asarray([[1, 0, 1, 1, 0, 1, 1, 0]], jnp.float32)
    np.testing.assert_array_equal(
        xing4.mtp_targets_and_mask(targets, mask)[1][0],
        [0, 1, 1, 0, 1, 1, 0, 0])


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


def test_a_mesh_and_a_pipeline_are_refused_by_name():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    cfg = xing4.xing4_tiny()
    params = jax.eval_shape(lambda k: xing4.init_params(cfg, k),
                            jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    with pytest.raises(NotImplementedError, match="pp_microbatches"):
        jax.eval_shape(lambda p, b: xing4.loss_fn(
            p, b, cfg.replace(pp_microbatches=2)), params, batch)
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(fsdp=2),
                                   devices=jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="xing4 on a mesh"):
            jax.eval_shape(lambda p, b: xing4.loss_fn(p, b, cfg), params,
                           batch)
    finally:
        set_global_mesh(before)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_compiled_step_names_the_scopes_the_benchmark_sums(path, monkeypatch):
    """``hc_stream_roofline``, ``hc_device_share`` and ``mtp_device_share``
    are what ``benchmark/scopes.py`` finds under ``block/hc`` and ``mtp`` in
    the compiled step's text, forward and backward alike; on the kernel
    path (interpreted here) every one of the five kernels' operations is
    under a pass's scope, the module's under ``mtp`` too."""
    from benchmark import scopes
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    from ray_tpu.util import telemetry
    seen = []
    monkeypatch.setattr(
        telemetry, "inc", lambda name, value=1.0, tags=None: seen.append(
            tags["path"]) if name == "ray_tpu_hc_path_total" else None)
    cfg = xing4.xing4_tiny(experts_held=4, held_start=4, remat=True,
                           layer_rows=1)
    if path == "kernel":
        cfg = cfg.replace(hidden=128, attention_impl="flash_interpret")
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh, learning_rate=1e-3)
    params, state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "loss_mask")}
    names = list(scopes.op_names(step_fn.lower(params, state, batch)
                                 .compile().as_text()).values())
    assert seen and set(seen) == {path}
    paths = {scopes.scope_path(n) for n in names}
    by = {"scopes": dict.fromkeys(paths, 1.0)}
    for scope in ("block/hc/maps", "block/hc/collect", "block/hc/deposit",
                  "block/attn/mla", "block/moe/experts", "mtp",
                  "mtp/block/hc", "mtp/block/moe"):
        assert scopes.seconds_under(by, scope) > 0, scope
    assert any("mtp" in n and "transpose(jvp(" in n for n in names)
    assert not any("jvp" in p or "while" in p for p in paths)
    if path == "kernel":
        for kernel, scope in (("hc_collect_n4", "collect"),
                              ("hc_pre_bwd_n4", "collect"),
                              ("hc_collect_bwd_n4", "collect"),
                              ("hc_deposit_n4", "deposit"),
                              ("hc_deposit_bwd_n4", "deposit")):
            mine = [n for n in names if f"/{kernel}/" in n]
            assert mine and all(f"block/hc/{scope}/{kernel}/" in n
                                for n in mine), kernel
            assert any("/mtp/" in n for n in mine), kernel
            assert any("transpose(jvp(" in n for n in mine), kernel
            # Forward (under the jvp), and recomputed under the remat.
            if not kernel.endswith("bwd_n4"):
                assert any("rematted_computation" in n for n in mine), kernel
                assert any("transpose(" not in n for n in mine), kernel


def test_published_stack_is_built_but_not_run():
    cfg = xing4.Xing4Config()
    shapes = jax.eval_shape(
        lambda k: xing4.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        xing4.num_params(cfg)
    assert shapes["moe"]["w_gate"].shape == (38, 64, 3584, 1024)
    assert shapes["moe"]["hc_attn_phi"].shape == (38, 14336, 24)
    assert shapes["dense"]["wq_b"].shape == (2, 768, 32, 192)
    # The benchmark's cut: its layout is the program's, its count the
    # issue's.
    with open(os.path.join(
            ROOT, "benchmark/configs/xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    s = arch.sizes_of(config)
    cut = arch.program_config(s, 8192, config["train"])
    shape_of = lambda tree: jax.tree.map(lambda x: x[0], tree,
                                         is_leaf=_lm.is_shape)
    assert shape_of(arch.shapes(s)) == shape_of(xing4.param_shapes(cut))
    assert arch.parameters(s)["held"] == xing4.num_params(cut) == \
        config["parameters"] == 913473348
    assert s == {**_sizes(cut),
                 "bias_update_rate": cut.bias_update_rate}


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
