"""The lfm2 model (LFM2-24B-A2B's block) against its plain reference, and the
pieces it brought: the double-gated short convolution and its written-out
backward, flash attention and the rotary placement at a head size of 64, an
expert layer with no shared expert, the router's epsilon, the tied head, the
share of an expert-parallel layer, and the train step's state and report."""

import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import afmoe, lfm2
from ray_tpu.ops import moe, ssm
from ray_tpu.ops.attention import flash_attention, reference_attention
from ray_tpu.ops.rope import apply_rope, rope_lane_tables, rotate_heads

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_lfm2 as ref  # noqa: E402
from benchmark.archs import lfm2_moe as arch  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers,
            "kinds": "".join(arch.LETTER[k] for k in cfg.kinds),
            "H": cfg.heads, "Hkv": cfg.kv_heads, "D": cfg.head_dim,
            "K": cfg.conv_kernel, "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "route_eps": cfg.route_eps,
            "theta": cfg.rope_theta, "eps": cfg.norm_eps}


@functools.lru_cache(maxsize=None)
def _setup(seed=0, rows=2, seq=40, **kw):
    """Tiny widths that keep a head size that is not 128, four query heads a
    key head, 8 experts with 4 a token and no shared one, both kinds of
    operator (``c a c c a``) and one dense layer.  Made once a configuration
    of this module (nothing writes into what it returns), the parameters
    under one ``jax.jit``: run eagerly the initialisation is one program a
    leaf shape."""
    cfg = lfm2.lfm2_tiny(**kw)

    @jax.jit
    def make(key, shake_key):
        params = lfm2.init_params(cfg, key)
        keys = iter(jax.random.split(shake_key, 64))

        def shake(path, a):
            if "norm" in str(path[-1]):             # away from one
                return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
            return a

        params = jax.tree_util.tree_map_with_path(shake, params)
        # A selection bias large enough to change which experts are chosen.
        bias = 0.3 * jax.random.normal(
            next(keys), (cfg.expert_layers, cfg.num_experts))
        return params, bias

    params, bias = make(jax.random.key(seed), jax.random.key(seed + 1))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (rows, seq), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (rows, seq),
                                              dtype=np.int32))}
    return cfg, params, bias, batch


@functools.lru_cache(maxsize=None)
def _loss_and_grads(**kw):
    """(loss, gradients) of ``_setup(experts_held=4)``'s case under ``kw``
    of the configuration, computed once: the plain one is what every variant
    below and the tied leaf's test compare against."""
    cfg, params, bias, batch = _setup(experts_held=4)
    cfg = cfg.replace(**kw)
    return jax.jit(jax.value_and_grad(
        lambda p: lfm2.loss_fn(p, batch, cfg, {"bias": bias})))(params)


def _whole_loss(params, bias, batch, s):
    """The reference's pieces put together: the loss of its ``logits``."""
    lg = ref.logits(params, bias, batch["tokens"], s)
    t = batch["tokens"]
    targets = jnp.concatenate([t[:, 1:], jnp.zeros_like(t[:, :1])], 1)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, targets[..., None], -1)[..., 0]
    mask = batch["loss_mask"].astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.sum(mask)


# ------------------------------------------------------------- the model

def test_model_matches_reference_loss_and_every_gradient():
    """The program's logits, loss and the gradient of every leaf (the
    convolutions' taps, the q / k head norms and the tied embedding among
    them), on a share of the experts (2 of 8 from the fifth), against
    ``jax.grad`` of the reference's pieces put together and against the
    reference's walk in blocks (the judged leaves and the embedding; what
    the chip's check runs).  Float32 on both sides."""
    cfg, params, bias, batch = _setup(experts_held=2, held_start=4)
    assert "lm_head" not in params
    assert params["layers"][1]["w_up"].shape[0] == 2
    assert "shared_up" not in params["layers"][1]
    assert "router" not in params["layers"][0]
    s = _sizes(cfg)
    got = lfm2.forward(params, batch["tokens"], cfg, {"bias": bias})
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.logits(params, bias, batch["tokens"],
                                               s)), atol=2e-4)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda p: lfm2.loss_and_report(p, batch, cfg, {"bias": bias}),
        has_aux=True))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: _whole_loss(p, bias, batch, s)))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and float(jnp.linalg.norm(w)) > 0, path
        assert float(jnp.linalg.norm(g - w)) < 1e-3 * float(
            jnp.linalg.norm(w)), jax.tree_util.keystr(path)
    w_loss, judged, tops = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert abs(float(w_loss) - float(want)) < 1e-5 * float(want)
    embed = judged.pop("embed")
    assert float(ref.relative_distance(arch.judged_of(grads), judged)) < 1e-3
    assert float(ref.relative_distance(arch.taps_of(grads),
                                       arch.taps_of(judged))) < 1e-3
    assert float(ref.relative_distance(grads["embed"], embed)) < 1e-3
    assert len(arch.taps_of(judged)) == 3
    assert set(arch.norms_of(grads)["layers"][1]) == {
        "op_norm", "ffn_norm", "q_norm", "k_norm"}
    # The step's report holds the routers' choices the reference makes.
    assert report["top"].shape == tops.shape == (4, 2 * 40, 4)
    assert float(ref.routing_mismatch_share(report["top"], tops, 8)) == 0


@pytest.mark.parametrize("variant", [
    dict(remat="full"), dict(layer_rows=1), dict(loss_chunks=4),
    dict(remat="full", layer_rows=1, loss_chunks=4)],
    ids=["remat", "rows_at_a_time", "loss_chunks", "all_three"])
def test_remat_rows_at_a_time_and_loss_chunks_do_not_change_the_loss(variant):
    (plain, g0), (other, g1) = _loss_and_grads(), _loss_and_grads(**variant)
    assert abs(float(plain) - float(other)) < 1e-5 * float(plain)
    assert float(ref.relative_distance(g1, g0)) < 1e-4


def test_the_tied_leafs_gradient_is_the_sum_of_an_untied_pairs():
    """The one ``embed`` leaf's gradient equals the lookup's gradient plus
    the head's of the same loss with the two uses on separate leaves."""
    cfg, params, bias, batch = _setup(experts_held=4)

    def untied(embed, head):
        x, _ = lfm2._forward_hidden({**params, "embed": embed},
                                    {"bias": bias}, batch["tokens"], cfg)
        return lfm2._lm.next_token_loss(x, head.T, batch, 0, cfg.dtype)

    g_lookup, g_head = jax.jit(jax.grad(untied, (0, 1)))(params["embed"],
                                                         params["embed"])
    tied = _loss_and_grads()[1]["embed"]
    assert float(jnp.linalg.norm(g_lookup)) > 0
    assert float(jnp.linalg.norm(g_head)) > 0
    np.testing.assert_allclose(np.asarray(tied),
                               np.asarray(g_lookup + g_head), atol=1e-6)
    # the head's part alone is not the leaf's gradient
    assert float(ref.relative_distance(g_head, tied)) > 0.1


def test_the_tied_matrix_counts_once_and_the_published_count_is_the_cards():
    cfg = lfm2.lfm2_tiny()
    params = jax.jit(lambda key: lfm2.init_params(cfg, key))(
        jax.random.key(0))
    assert lfm2.num_params(cfg) == sum(a.size for a in jax.tree.leaves(params))
    whole = lfm2.Lfm2Config()
    assert whole.kinds.count(lfm2.FULL) == 10
    assert [i for i, k in enumerate(whole.kinds) if k == lfm2.FULL][:3] == \
        [2, 6, 10]
    assert lfm2.num_params(whole) == (
        2 * 89_139_200 + 10 * 614_600_832 + 28 * 620_898_304 + 134_217_728
        + 2048)
    # the benchmark's share: 9 layers, 8 of 64 experts, 1/8 of the vocabulary
    share = whole.replace(layers=9, num_dense_layers=1, experts_held=8,
                          vocab_size=8192, layer_types=whole.kinds[1:10])
    assert lfm2.num_params(share) == 832_651_520
    assert share.kinds.count(lfm2.CONV) == 7


def test_train_step_trains_through_model_module_and_reports():
    """``make_lm_train_step`` finds the model by its configuration's module,
    carries the selection bias as state, reports the loads, and holds one
    pair of moments for the tied matrix."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import (StepState, make_lm_train_step,
                                       model_module)
    cfg = lfm2.lfm2_tiny(experts_held=4, held_start=2, layer_rows=1,
                         remat="full", loss_chunks=2)
    assert model_module(cfg) is lfm2
    init_fn, step_fn, place = make_lm_train_step(
        cfg, build_mesh(MeshSpec(), devices=jax.devices()[:1]),
        learning_rate=1e-3)
    params, state = init_fn(jax.random.key(0))
    assert isinstance(state, StepState)
    assert state.model["bias"].shape == (4, 8)
    assert "lm_head" not in params
    batch = place({"tokens": np.random.default_rng(0).integers(
        0, 256, (2, 40), dtype=np.int32)})
    losses = []
    for _ in range(3):
        params, state, m = step_fn(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[2] < losses[0]
    assert m["moe_choices"].shape == (4, 80, 4)
    assert float(m["moe_dropped"]) == 0
    assert float(jnp.max(jnp.abs(state.model["bias"]))) > 0


def test_a_mesh_and_a_pipeline_are_refused_by_name():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    cfg, params, bias, batch = _setup()
    with pytest.raises(NotImplementedError, match="pp_microbatches.*M4"):
        lfm2.loss_fn(params, batch, cfg.replace(pp_microbatches=2))
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(fsdp=2),
                                   devices=jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="lfm2 on a mesh.*M8"):
            lfm2.loss_fn(params, batch, cfg)
    finally:
        set_global_mesh(before)


# ------------------------------------------- the gated short convolution

def _conv_inputs(seed=0, rows=2, S=19, Ch=6, K=3):
    k = jax.random.split(jax.random.key(seed), 5)
    B, C, u = (jax.random.normal(k[i], (rows, S, Ch)) for i in range(3))
    return B, C, u, jax.random.normal(k[3], (K, Ch)), \
        jax.random.normal(k[4], (rows, S, Ch))


@pytest.mark.parametrize("K", [3, 2, 4])
def test_gated_convs_written_out_backward_is_what_jax_derives(K):
    """Forward against the reference's loop over the taps, and the written-out
    backward (dB, dC, du, dw) against what JAX derives from that loop."""
    B, C, u, w, g = _conv_inputs(K=K)
    np.testing.assert_allclose(
        np.asarray(ssm.gated_short_conv(B, C, u, w)),
        np.asarray(ref.short_conv(B, C, u, w)), atol=1e-5)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * g),
                               (0, 1, 2, 3))(B, C, u, w)
    for got, want in zip(grads(ssm.gated_short_conv), grads(ref.short_conv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


@pytest.mark.parametrize("K,rows,S,Ch", [(3, 2, 1024, 256), (4, 1, 512, 128),
                                         (2, 3, 512, 384)])
def test_gated_conv_kernels_are_the_loop_in_interpret_mode(K, rows, S, Ch):
    """The Pallas pair in interpret mode (tiles of 512 / 256 tokens: the
    tail a tile hands on, the rows read from the tile after, the taps'
    gradient added up over tiles and rows) against the reference's loop and
    what JAX derives from it."""
    B, C, u, w, g = _conv_inputs(rows=rows, S=S, Ch=Ch, K=K)
    kernel = lambda *a: ssm.gated_short_conv(*a, impl="kernel_interpret")
    np.testing.assert_allclose(np.asarray(kernel(B, C, u, w)),
                               np.asarray(ref.short_conv(B, C, u, w)),
                               atol=2e-5)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * g),
                               (0, 1, 2, 3))(B, C, u, w)
    for got, want in zip(grads(kernel), grads(ref.short_conv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-5)
    # rows are independent and a row starts from zeros, across tiles too
    y = kernel(B, C, u.at[0].add(1.0), w)
    np.testing.assert_array_equal(np.asarray(y[1:]),
                                  np.asarray(kernel(B, C, u, w)[1:]))


def test_gated_conv_takes_the_jnp_form_where_the_shapes_do_not_tile():
    from ray_tpu.util import metrics as metrics_mod
    att = importlib.import_module("ray_tpu.ops.attention")
    B, C, u, w, _ = _conv_inputs(S=19, Ch=6)
    metrics_mod._reset_for_tests()
    on_tpu = att._on_tpu
    att._on_tpu = lambda: True
    try:
        ssm.gated_short_conv(B, C, u, w)        # 19 tokens: no tile
        assert ssm._gc_tile(8192, 2048, False) == (512, 512)
        assert ssm._gc_tile(8192, 2048, True) == (256, 512)
        assert ssm._gc_tile(19, 6, False) is None
    finally:
        att._on_tpu = on_tpu
    text = metrics_mod.prometheus_text()
    assert 'ray_tpu_gated_conv_path_total{path="xla",taps="3"}' in text \
        or 'taps="3",path="xla"' in text, text[-400:]
    metrics_mod._reset_for_tests()


def test_gated_conv_in_bfloat16_sums_in_float32_and_rounds_once():
    B, C, u, w, _ = _conv_inputs(S=64, Ch=16)
    b16 = lambda a: a.astype(jnp.bfloat16)
    got = ssm.gated_short_conv(b16(B), b16(C), b16(u), w)
    assert got.dtype == jnp.bfloat16
    want = ref.short_conv(*(b16(a).astype(jnp.float32) for a in (B, C, u)),
                          w).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gated_conv_sees_nothing_after_t_and_nothing_of_another_row():
    B, C, u, w, _ = _conv_inputs()
    y = ssm.gated_short_conv(B, C, u, w)
    t = 7
    # token t + 1 moved: tokens 0..t stay to the bit, t + 1 moves
    for i, a in enumerate((B, C, u)):
        moved = [B, C, u]
        moved[i] = a.at[:, t + 1].add(1.0)
        y2 = ssm.gated_short_conv(*moved, w)
        np.testing.assert_array_equal(np.asarray(y2[:, :t + 1]),
                                      np.asarray(y[:, :t + 1]))
        assert float(jnp.max(jnp.abs(y2[:, t + 1] - y[:, t + 1]))) > 0
    # a token reaches K - 1 = 2 tokens ahead and no further
    y2 = ssm.gated_short_conv(B, C, u.at[:, t].add(1.0), w)
    assert float(jnp.max(jnp.abs(y2[:, t + 2] - y[:, t + 2]))) > 0
    np.testing.assert_array_equal(np.asarray(y2[:, t + 3:]),
                                  np.asarray(y[:, t + 3:]))
    # rows are independent, and a row starts from zeros
    y2 = ssm.gated_short_conv(B, C, u.at[0].add(1.0), w)
    np.testing.assert_array_equal(np.asarray(y2[1]), np.asarray(y[1]))
    np.testing.assert_allclose(np.asarray(y[:, 0]),
                               np.asarray(C[:, 0] * w[-1] * B[:, 0] * u[:, 0]),
                               atol=1e-6)


# --------------------------------------------------- the expert layer

@functools.lru_cache(maxsize=None)
def _layer_inputs(cfg, seed=9):
    layer = jax.jit(lambda key: lfm2.init_params(cfg, key)["layers"][1])(
        jax.random.key(seed))
    h = jax.random.normal(jax.random.key(seed + 1), (2, 24, cfg.hidden))
    bias = 0.3 * jax.random.normal(jax.random.key(seed + 2),
                                   (cfg.num_experts,))
    return layer, h, bias


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """The held parts of all 8 shares of one expert layer (one expert each)
    sum to the uncut reference layer's routed result: nothing is counted
    once here, there is no shared expert."""
    cfg = lfm2.lfm2_tiny()
    layer, h, bias = _layer_inputs(cfg)
    s = _sizes(cfg)
    flat = h.reshape(-1, cfg.hidden)
    top, wts = ref.route(flat, layer["router"], bias, s)
    want = ref.held_experts(flat, top, wts, layer["w_gate"], layer["w_up"],
                            layer["w_down"], 0).reshape(h.shape)
    total = jnp.zeros_like(h)
    for e in range(cfg.num_experts):
        share = cfg.replace(experts_held=1, held_start=e)
        part = {**layer, **{n: layer[n][e:e + 1]
                            for n in ("w_gate", "w_up", "w_down")}}
        out, loads = afmoe._moe(share, h, part, bias,
                                route_eps=cfg.route_eps)
        total = total + out
        assert int(loads["counts"].sum()) == 2 * 24 * cfg.top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-4)


@pytest.mark.parametrize("shared", [False, True],
                         ids=["no_shared_expert", "shared_expert"])
def test_moe_with_and_without_a_shared_expert(shared):
    """``afmoe._moe`` on a layer without ``shared_up`` is the routed part
    alone; with one it is that plus the shared expert."""
    cfg = lfm2.lfm2_tiny()
    layer, h, bias = _layer_inputs(cfg)
    routed, loads = afmoe._moe(cfg, h, layer, bias)
    if not shared:
        s = {**_sizes(cfg), "route_eps": 1e-20}
        flat = h.reshape(-1, cfg.hidden)
        top, wts = ref.route(flat, layer["router"], bias, s)
        want = ref.held_experts(flat, top, wts, layer["w_gate"],
                                layer["w_up"], layer["w_down"], 0)
        np.testing.assert_allclose(np.asarray(routed.reshape(want.shape)),
                                   np.asarray(want), atol=2e-4)
        return
    k = jax.random.split(jax.random.key(3), 3)
    extra = {"shared_gate": jax.random.normal(k[0], (cfg.hidden, 24)) * 0.1,
             "shared_up": jax.random.normal(k[1], (cfg.hidden, 24)) * 0.1,
             "shared_down": jax.random.normal(k[2], (24, cfg.hidden)) * 0.1}
    both, _ = afmoe._moe(cfg, h, {**layer, **extra}, bias)
    want = afmoe._swiglu(h, extra["shared_gate"], extra["shared_up"],
                         extra["shared_down"], cfg.dtype) + routed
    np.testing.assert_array_equal(np.asarray(both), np.asarray(want))


@pytest.mark.parametrize("caller", ["afmoe", "xing4", "nemotron_h"])
def test_the_routers_epsilon_leaves_the_three_callers_results_bit_equal(
        caller):
    """The three callers leave the epsilon at 1e-20: their weights are to
    the bit what ``w / (sum + 1e-20)`` gives, at each one's own scale and
    experts a token; LFM2's 1e-6 gives another."""
    k, scale = {"afmoe": (8, 2.826), "xing4": (8, 2.5),
                "nemotron_h": (6, 2.5)}[caller]
    x = jax.random.normal(jax.random.key(0), (40, 16))
    rw = jax.random.normal(jax.random.key(1), (16, 32))
    bias = 0.1 * jax.random.normal(jax.random.key(2), (32,))
    r = moe.sigmoid_routing(x, rw, bias, k, scale)
    s = jax.nn.sigmoid(x @ rw)
    w = jnp.take_along_axis(s, r.expert_index, axis=-1)
    want = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale
    np.testing.assert_allclose(np.asarray(r.weights), np.asarray(want),
                               rtol=2e-6)
    same = moe.sigmoid_routing(x, rw, bias, k, scale, True, 1e-20)
    np.testing.assert_array_equal(np.asarray(r.weights),
                                  np.asarray(same.weights))
    other = moe.sigmoid_routing(x, rw, bias, k, scale, eps=1e-6)
    np.testing.assert_array_equal(np.asarray(other.expert_index),
                                  np.asarray(r.expert_index))
    assert float(jnp.max(jnp.abs(other.weights - r.weights))) > 0


# ------------------------------------------------ head size 64 kernels

@pytest.mark.parametrize("S,block", [(256, None), (512, 256)])
def test_flash_at_thirty_two_on_eight_heads_of_64(S, block):
    """Flash in interpret mode at LFM2's heads (32 on 8, head size 64: four
    query heads stacked a key head) against ``reference_attention``: forward
    and the three gradients."""
    B, H, Hkv, D = 1, 32, 8, 64
    k = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(k[0], (B, H, S, D))
    kk, v = (jax.random.normal(k[i], (B, Hkv, S, D)) for i in (1, 2))
    do = jax.random.normal(k[3], q.shape)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, block_q=block, block_k=block)
    plain = lambda q, k, v: reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(flash(q, kk, v)),
                               np.asarray(plain(q, kk, v)), atol=2e-3)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * do), (0, 1, 2))(
        q, kk, v)
    for g, w in zip(grads(flash), grads(plain)):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-3 * max(
            1.0, float(jnp.max(jnp.abs(w))))


def test_a_64_wide_call_says_so_in_its_names_and_tags():
    att = importlib.import_module("ray_tpu.ops.attention")
    assert att._kernel_name("flash_fwd", None, 64, 64) == "flash_fwd_d64"
    assert att._kernel_name("flash_dkv", 2048, 64, 64) == "flash_dkv_d64_w2048"
    # 128 and 192 / 128 keep the names they had
    assert att._kernel_name("flash_fwd", None, 128, 128) == "flash_fwd"
    assert att._kernel_name("flash_dq", 2048, 128, 128) == "flash_dq_w2048"
    assert att._kernel_name("flash_fwd", None, 192, 128) == \
        "flash_fwd_d192v128"
    # LFM2's backward is the one pass under its group of four (PR 60)
    assert att._kernel_name("flash_bwd", None, 64, 64) == "flash_bwd_d64"
    from ray_tpu.util import metrics as metrics_mod
    metrics_mod._reset_for_tests()
    x = jnp.ones((1, 4, 64, 64), jnp.float32)
    jax.grad(lambda x: jnp.sum(flash_attention(
        x, x[:, :1], x[:, :1], interpret=True)))(x)
    y = jnp.ones((1, 4, 128, 128), jnp.float32)
    flash_attention(y, y[:, :1], y[:, :1], interpret=True)
    text = metrics_mod.prometheus_text()
    lines = [l for l in text.splitlines()
             if l.startswith("ray_tpu_flash_step_geometry_total{")]
    bwd, = [l for l in lines if "flash_fwd" not in l]
    assert 'kernel="flash_bwd_d64"' in bwd and 'shares="4"' in bwd
    assert 'd="64"' in bwd and 'heads_a_step="1"' in bwd
    d64 = [l for l in lines if 'kernel="flash_fwd_d64"' in l]
    d128 = [l for l in lines if 'kernel="flash_fwd"' in l]
    assert d64 and all('d="64"' in l for l in d64)
    assert d128 and not any('d="' in l.replace('d_qk="', "").replace(
        'd_v="', "") and 'd="128"' in l for l in d128)
    metrics_mod._reset_for_tests()


@pytest.mark.parametrize("kind", ["fwd", "dq", "dkv"])
def test_tiles_at_128_and_over_keep_their_answers(kind):
    att = importlib.import_module("ray_tpu.ops.attention")
    t = att._tiles(kind, 8192, 8192, 128, 16)
    assert t.heads == 8 and t.block_q == 512
    assert t.block_k == (256 if kind == "fwd" else 512)
    assert att._tiles(kind, 8192, 8192, 128, 8) == t
    assert att._tiles(kind, 4096, 4096, 128, 1).block_q == 1024
    # over 128: 512 x 512 and a head a step, eight tiles a grid step (PR 52)
    assert att._tiles(kind, 8192, 8192, 192, 1) == att.Tiles(
        512, 512, 1, "kq" if kind == "dkv" else "qk", 8)
    # LFM2's call: four query heads stacked a key head
    assert att._tiles(kind, 8192, 8192, 64, 4).heads == 4


@pytest.mark.parametrize("H,kernel", [(32, True), (8, True), (4, True),
                                      (8, False), (3, True)])
def test_rotate_heads_at_64_is_apply_rope(H, kernel):
    """``rotate_heads`` at a head size of 64 against ``apply_rope`` on the
    transposed input, and its gradient: the kernel pair in interpret mode
    (two heads a tile of 128 lanes, the half-turn inside each 64), and
    ``apply_rope`` behind a transpose where there is no chip or the heads
    do not pair (3)."""
    from ray_tpu.ops.rope import rope_frequencies
    from ray_tpu.util import metrics as metrics_mod
    B, S, D = 2, 48, 64
    x = jax.random.normal(jax.random.key(H), (B, S, H, D))
    cos2, sin2 = rope_lane_tables(D, 64, 1e6)
    cos, sin = rope_frequencies(D, 64, 1e6)
    rotate = lambda x: rotate_heads(x, cos2, sin2, interpret=kernel)
    metrics_mod._reset_for_tests()
    got = rotate(x)
    path = "kernel" if kernel and H % 2 == 0 else "xla"
    assert f'path="{path}"' in "".join(
        line for line in metrics_mod.prometheus_text().splitlines()
        if line.startswith("ray_tpu_rope_path_total{"))
    metrics_mod._reset_for_tests()
    want = apply_rope(jnp.swapaxes(x, 1, 2), cos, sin)
    assert got.shape == (B, H, S, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    g = jax.random.normal(jax.random.key(1), got.shape)
    got_g = jax.grad(lambda x: jnp.sum(rotate(x) * g))(x)
    want_g = jax.grad(lambda x: jnp.sum(
        apply_rope(jnp.swapaxes(x, 1, 2), cos, sin) * g))(x)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                               atol=1e-6)
    # the reference's rotation on split halves is the same one
    np.testing.assert_allclose(
        np.asarray(jnp.swapaxes(got, 1, 2)),
        np.asarray(ref._rope(x, 1e6)), atol=1e-5)
