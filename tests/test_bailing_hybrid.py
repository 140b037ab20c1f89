"""``models/bailing_hybrid.py`` (Ling-3.0-flash's block) against the plain
reference ``benchmark/reference_bailing_hybrid.py`` at a small size with
seeded random weights: loss and every gradient over dense and sparse layers
of both mixers, the share of an expert-parallel group and group-limited
routing.  That the programs which share code with it did not change:
``tests/test_deepseek_v3.py``'s pinned jaxprs."""

from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import _lm, afmoe, bailing_hybrid as M
from ray_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_bailing_hybrid as ref  # noqa: E402
from benchmark.archs import bailing_hybrid as arch  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def _sizes(cfg):
    """The reference's sizes of a program configuration."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers, "kinds": cfg.kinds, "H": cfg.heads,
            "D": cfg.head_dim, "K": cfg.conv_kernel,
            "bound": cfg.kda_lower_bound, "rkv": cfg.kv_lora_rank,
            "dn": cfg.qk_nope_head_dim, "dr": cfg.qk_rope_head_dim,
            "dv": cfg.v_head_dim, "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "Ms": cfg.moe_mlp_dim * cfg.num_shared_experts,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "route_scale": cfg.route_scale, "theta": cfg.rope_theta,
            "eps": cfg.norm_eps, "Q": cfg.kda_chunk,
            "first_layer": cfg.first_layer, "group": cfg.layer_group_size,
            "dt_min": cfg.time_step_min, "dt_max": cfg.time_step_max,
            "dt_floor": cfg.time_step_floor,
            "bias_update_rate": cfg.bias_update_rate}


@functools.lru_cache(maxsize=None)
def _case(seed=0, **kw):
    """Made once a configuration of this module (nothing writes into what it
    returns), under one ``jax.jit``: run eagerly the initialisation is one
    program a leaf shape."""
    cfg = M.bailing_hybrid_tiny(**kw)

    @jax.jit
    def make():
        params = M.init_params(cfg, jax.random.key(seed))
        # norms and the convolution away from their constant starts
        leaves, tree = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
        params = jax.tree.unflatten(tree, [
            a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
            for a, k in zip(leaves, keys)])
        bias = 0.02 * jax.random.normal(jax.random.key(seed + 2),
                                        (cfg.expert_layers, cfg.num_experts))
        tokens = jax.random.randint(jax.random.key(seed + 3), (2, 48), 0,
                                    cfg.vocab_size)
        return params, bias, tokens

    params, bias, tokens = make()
    mask = jnp.ones((2, 48), jnp.int32).at[:, -1].set(0)
    return cfg, params, bias, tokens, mask


def test_the_tiny_stack_has_both_mixers_dense_and_sparse():
    cfg = M.bailing_hybrid_tiny()
    assert cfg.kinds == ("kda", "kda", "mla", "kda")
    assert cfg.num_dense_layers == 1
    # the published stack: five KDA layers to one of latent attention
    full = M.BailingHybridConfig()
    assert full.kinds[:6] == ("kda",) * 5 + ("mla",)
    assert full.kinds.count("mla") == 7 and len(full.kinds) == 42
    # a pipeline stage that starts at published layer 1
    assert full.replace(layers=7, first_layer=1).kinds == (
        "kda", "kda", "kda", "kda", "mla", "kda", "kda")


@pytest.mark.parametrize("held", [None, 8])
def test_loss_and_every_gradient_against_the_reference(held):
    """Dense and sparse layers of both mixers, all experts held or a share
    of 8 of 16 from expert 4."""
    cfg, params, bias, tokens, mask = _case(
        experts_held=held, held_start=4 if held else 0, loss_chunks=2)
    batch = {"tokens": tokens, "loss_mask": mask}
    with jax.default_matmul_precision("highest"):
        got, ggot = jax.jit(jax.value_and_grad(lambda p: M.loss_fn(
            p, batch, cfg, {"bias": bias})))(params)
        want, gwant = jax.jit(jax.value_and_grad(lambda p: ref.loss(
            p, bias, tokens, mask, _sizes(cfg))))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(ggot)
    for (path, a), b in zip(flat_got, jax.tree.leaves(gwant)):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-8)
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-3 * scale, \
            jax.tree_util.keystr(path)


def test_logits_against_the_reference_with_a_remat_and_row_groups():
    cfg, params, bias, tokens, _ = _case(seed=3, remat=True, layer_rows=1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: M.forward(p, tokens, cfg, {"bias": bias}))(
            params)
        want = jax.jit(lambda p: ref.logits(p, bias, tokens, _sizes(cfg)))(
            params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


def test_the_shares_of_an_expert_parallel_group_add_up():
    """The share test: the parts that all 16 / ``experts_held`` shares of an
    expert layer give, the shared expert counted once, add up to the uncut
    reference's layer."""
    cfg, params, bias, _, _ = _case(seed=5)
    layer = params["layers"][1]                      # sparse
    h = jax.random.normal(jax.random.key(9), (2, 24, cfg.hidden))
    with jax.default_matmul_precision("highest"):
        whole, top = ref.feed_forward(h, layer, bias[0], _sizes(cfg))
        shared = afmoe._swiglu(h, layer["shared_gate"], layer["shared_up"],
                               layer["shared_down"], jnp.float32)
        total = 0
        for start in range(0, cfg.num_experts, 4):
            part = cfg.replace(experts_held=4, held_start=start)
            held = {**layer, **{n: layer[n][start:start + 4]
                                for n in ("w_gate", "w_up", "w_down")}}
            out, loads = afmoe._moe(part, h, held, bias[0])
            total = total + (out - shared)
            np.testing.assert_array_equal(np.sort(np.asarray(loads["top"])),
                                          np.sort(np.asarray(top)))
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("groups", [(4, 2), (8, 4), (2, 1)])
def test_group_limited_routing_against_the_reference(groups):
    """Seeded scores without ties: the program's choice (``ops/moe``) and
    weights against the reference's, written from DeepSeek-V3's description;
    every chosen expert lies in a kept group."""
    n, keep = groups
    T, E, X, k = 64, 32, 64, 4
    x = jax.random.normal(jax.random.key(11), (T, E))
    w = jax.random.normal(jax.random.key(12), (E, X)) * E ** -0.5
    bias = 0.05 * jax.random.normal(jax.random.key(13), (X,))
    got = moe.sigmoid_routing(x, w, bias, k, 2.5, True, 1e-20, n, keep)
    top, wts = ref.route(x, w, bias, {"n_group": n, "topk_group": keep,
                                      "k": k, "route_scale": 2.5})
    np.testing.assert_array_equal(np.sort(np.asarray(got.expert_index)),
                                  np.sort(np.asarray(top)))
    np.testing.assert_allclose(
        np.sort(np.asarray(got.weights)), np.sort(np.asarray(wts)),
        rtol=1e-5)
    assert int(got.counts.sum()) == T * k
    assert all(len(set(np.asarray(row) // (X // n))) <= keep
               for row in got.expert_index)
    # what the groups exclude: some token's unrestricted choice
    free = moe.sigmoid_routing(x, w, bias, k, 2.5)
    if keep < n:
        assert not np.array_equal(np.sort(np.asarray(free.expert_index)),
                                  np.sort(np.asarray(top)))


def test_one_group_is_today_s_routing_bit_for_bit():
    x = jax.random.normal(jax.random.key(21), (32, 16))
    w = jax.random.normal(jax.random.key(22), (16, 8))
    bias = 0.1 * jax.random.normal(jax.random.key(23), (8,))
    base = lambda x, w, b: moe.sigmoid_routing(x, w, b, 2, 2.0)
    one = lambda x, w, b: moe.sigmoid_routing(x, w, b, 2, 2.0, n_group=1,
                                              topk_group=1)
    assert str(jax.make_jaxpr(base)(x, w, bias)) == \
        str(jax.make_jaxpr(one)(x, w, bias))
    # every group kept: the same choice by the long way
    all_kept = moe.sigmoid_routing(x, w, bias, 2, 2.0, n_group=4,
                                   topk_group=4)
    for a, b in zip(base(x, w, bias), all_kept):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_train_step_moves_the_bias_and_reports_the_carry():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    cfg = M.bailing_hybrid_tiny(experts_held=4, held_start=4, remat=True,
                                layer_rows=1, loss_chunks=4)
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh,
                                                 learning_rate=1e-3,
                                                 param_dtype=jnp.bfloat16)
    params, state = init_fn(jax.random.key(0))
    kda_layer = params["layers"][0]
    # float32 whatever the other parameters are
    assert kda_layer["A_log"].dtype == kda_layer["dt_bias"].dtype == \
        jnp.float32 and kda_layer["w_a"].dtype == jnp.bfloat16
    batch = place({"tokens": np.asarray(jax.random.randint(
        jax.random.key(1), (2, 64), 0, 256)),
        "loss_mask": np.ones((2, 64), np.int32)})
    losses = []
    for _ in range(3):
        params, state, m = step_fn(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert 0 < float(m["kda_chunk_carry"]) < 1
    assert m["moe_choices"].shape == (3, 128, 4)
    assert float(jnp.abs(state.model["bias"]).max()) == pytest.approx(
        3e-3, rel=0.5)
    assert float(m["moe_dropped"]) == 0


def test_a_mesh_and_a_pipeline_are_refused():
    from ray_tpu.parallel.mesh import (MeshSpec, build_mesh, get_global_mesh,
                                       set_global_mesh)
    cfg, params, bias, tokens, _ = _case()
    with pytest.raises(NotImplementedError, match="ROADMAP M4"):
        M.forward(params, tokens, cfg.replace(pp_microbatches=2))
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(tp=2), jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="ROADMAP M3, M8"):
            M.forward(params, tokens, cfg)
    finally:
        set_global_mesh(before)


def test_the_arch_module_makes_the_program_s_tree_at_the_cell_s_sizes():
    """Shapes and counts at the published widths, nothing allocated."""
    with open(os.path.join(
            ROOT, "benchmark/configs/ling-3.0-flash.json")) as f:
        config = json.load(f)
    s = arch.sizes_of(config)
    cut = arch.program_config(s, 8192, config["train"])
    assert cut.kinds == s["kinds"] == ("kda",) * 4 + ("mla",) + ("kda",) * 2
    shape_of = lambda tree: jax.tree.map(lambda x: x[0], tree,
                                         is_leaf=_lm.is_shape)
    assert shape_of(arch.shapes(s)) == shape_of(M.param_shapes(cut))
    assert arch.parameters(s)["held"] == M.num_params(cut) == \
        config["parameters"]
    assert _sizes(cut) == s
    logical = M.param_logical_axes(cut)
    assert jax.tree.structure(
        logical, is_leaf=lambda x: isinstance(x, tuple)) == \
        jax.tree.structure(M.param_shapes(cut), is_leaf=_lm.is_shape)
    # the published defaults are the catalog's row
    full = M.BailingHybridConfig()
    pub = config["published"]
    assert (full.layers, full.num_dense_layers, full.num_experts,
            full.vocab_size) == tuple(pub[k] for k in config["reduced"])
    assert (full.hidden, full.heads, full.head_dim, full.mlp_dim,
            full.moe_mlp_dim, full.top_k, full.n_group, full.topk_group,
            full.kv_lora_rank, full.layer_group_size) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "n_group", "topk_group", "kv_lora_rank",
            "layer_group_size"))
