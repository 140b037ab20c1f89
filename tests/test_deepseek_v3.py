"""The deepseek_v3 model (kanana-2-30b-a3b: DeepSeek-V3's block) against its
plain reference, through the one latent attention, expert layer and
layer-rows loop that ``models/xing4.py`` has: logits, loss and every
gradient; the query projection with and without a bottleneck; the flash
kernels in parts (interpreted) against the reference attention; the
selection bias's rule; the share of an expert-parallel layer; the train
step's state and report; and that Xing4.0's own program did not change."""

import functools
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import _lm, afmoe, deepseek_v3, xing4

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_deepseek_v3 as ref  # noqa: E402
from benchmark.archs import deepseek_v3 as arch  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers, "H": cfg.heads,
            "rkv": cfg.kv_lora_rank, "dn": cfg.qk_nope_head_dim,
            "dr": cfg.qk_rope_head_dim, "dv": cfg.v_head_dim,
            "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "Ms": cfg.moe_mlp_dim * cfg.num_shared_experts,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "theta": cfg.rope_theta,
            "eps": cfg.norm_eps}


@functools.lru_cache(maxsize=None)
def _setup(seed=0, rows=2, seq=48, **kw):
    """Made once a configuration of this module (nothing writes into what it
    returns), the parameters under one ``jax.jit``: run eagerly the
    initialisation is one program a leaf shape."""
    cfg = deepseek_v3.deepseek_v3_tiny(**kw)

    @jax.jit
    def make(key, shake_key):
        params = deepseek_v3.init_params(cfg, key)
        # Norm weights away from one, and a selection bias large enough to
        # change which experts are chosen.
        keys = iter(jax.random.split(shake_key, 64))
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a * (1 + 0.2 * jax.random.normal(
                next(keys), a.shape)) if "norm" in str(path[-1]) else a,
            params)
        bias = 0.3 * jax.random.normal(next(keys),
                                       (cfg.expert_layers, cfg.num_experts))
        return params, bias

    params, bias = make(jax.random.key(seed), jax.random.key(seed + 1))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (rows, seq), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (rows, seq),
                                              dtype=np.int32))}
    return cfg, params, bias, batch


# ----------------------------------------------- the model and its reference

@pytest.mark.parametrize("held,start", [(None, 0), (2, 6)],
                         ids=["whole", "a_share"])
def test_model_matches_reference_logits_loss_and_every_gradient(held, start):
    """Float32 on both sides, seeded random weights, whole and on a share
    of the experts (2 of 16 from the seventh).  The loss to 1e-5 and every
    leaf's gradient to 2e-2 of its norm against ``jax.grad`` of the
    reference whole (two evaluations of the reference alone, the walk and
    the whole, differ by up to 1e-3 in a gradient).  Whole: the logits to
    2e-4 of their largest (two float32 orders of the same sums).  On the
    share: the norms' tree to 1e-2 against the reference's walk in blocks
    (what the chip's check runs), the routers' choices exactly, and the
    int8 control apart from both."""
    cfg, params, bias, batch = _setup(seq=32, experts_held=held,
                                      held_start=start)
    assert params["moe"]["w_gate"].shape[1] == (held or 16)
    assert params["moe"]["shared_up"].shape == (2, 64, 64)   # 2 x 32, one
    assert "wq" in params["dense"] and "wq_a" not in params["dense"]
    s, state = _sizes(cfg), {"bias": bias}
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda p: deepseek_v3.loss_and_report(p, batch, cfg, state),
        has_aux=True))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, bias, batch["tokens"], batch["loss_mask"], s)))(
            params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert sorted(report) == ["counts", "dropped", "sliced", "top"]
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and float(jnp.linalg.norm(w)) > 0, path
        assert float(jnp.linalg.norm(g - w)) < 2e-2 * float(
            jnp.linalg.norm(w)), jax.tree_util.keystr(path)
    if held is None:
        got = jax.jit(lambda p: deepseek_v3.forward(
            p, batch["tokens"], cfg, state))(params)
        want = jax.jit(lambda p: ref.logits(p, bias, batch["tokens"], s))(
            params)
        assert got.shape == want.shape == (2, 32, cfg.vocab_size)
        assert float(jnp.abs(got - want).max()) < 2e-4 * float(
            jnp.abs(want).max())
        return
    w_loss, norms, tops = ref.loss_norm_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert abs(float(w_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert float(ref.relative_distance(arch.norms_of(grads), norms)) < 1e-2
    np.testing.assert_array_equal(tops, report["top"])
    np.testing.assert_array_equal(
        ref.routing(params, bias, batch["tokens"], s), tops)
    # The int8 control is another function: it fails where rounding passes.
    _, control, _ = ref.loss_norm_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s, quant="int8")
    assert float(ref.relative_distance(control, norms)) > 2e-2


@pytest.mark.parametrize("how", ["loss_chunks", "rows_at_a_time_under_remat"])
def test_remat_rows_at_a_time_and_loss_chunks_do_not_change_the_loss(how):
    cfg, params, bias, batch = _setup(rows=4, seq=32)
    state = {"bias": bias}
    plain = jax.jit(lambda p: deepseek_v3.loss_fn(p, batch, cfg, state))(
        params)
    if how == "loss_chunks":
        other = jax.jit(lambda p: deepseek_v3.loss_fn(
            p, batch, cfg.replace(loss_chunks=4), state))(params)
        assert abs(float(other) - float(plain)) < 1e-5
        return
    other = cfg.replace(remat="full", layer_rows=1, loss_chunks=8)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: deepseek_v3.loss_fn(p, batch, other, state)))(params)
    assert abs(float(loss) - float(plain)) < 1e-5
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


# ------------------------------------------------------- latent attention

def _one_layer(cfg, seed=0):
    """(one layer's weights of the shared stack's tree, the reference's
    sizes) for a stack configuration."""
    params = jax.jit(lambda key: xing4.init_params(cfg, key))(
        jax.random.key(seed))
    layer = jax.tree.map(lambda a: a[0], params["dense"])
    s = {"H": cfg.heads, "rkv": cfg.kv_lora_rank, "dn": cfg.qk_nope_head_dim,
         "dr": cfg.qk_rope_head_dim, "dv": cfg.v_head_dim,
         "theta": cfg.rope_theta, "eps": cfg.norm_eps}
    return layer, s


@pytest.mark.parametrize("rank", [None, 48], ids=["no_bottleneck",
                                                  "q_lora_48"])
def test_one_latent_attention_with_and_without_a_query_bottleneck(rank):
    """``xing4._mla`` is the one latent attention: with ``q_lora_rank``
    None it reads one ``wq`` [E, H, 192] and holds no ``wq_a`` / ``q_norm``
    / ``wq_b``; with a rank it is Xing4.0's.  Both are the reference's
    (DeepSeek-V3's block: plain rotary at theta 1e6, scale 192^-1/2), value
    and gradient in every weight."""
    from ray_tpu.ops.rope import rope_lane_tables
    cfg = deepseek_v3.deepseek_v3_tiny(q_lora_rank=rank).stack
    layer, s = _one_layer(cfg)
    names = {"wq"} if rank is None else {"wq_a", "q_norm", "wq_b"}
    assert names <= set(layer)
    assert not ({"wq", "wq_a", "q_norm", "wq_b"} - names) & set(layer)
    assert xing4._layer_axes(cfg).keys() == {
        k for k in layer if k not in ("w_gate", "w_up", "w_down")}
    assert cfg.softmax_scale == 192 ** -0.5
    tables = rope_lane_tables(64, 64, cfg.rope_theta, None)
    h = jax.random.normal(jax.random.key(1), (2, 48, cfg.hidden))
    weights = {k: v for k, v in layer.items() if k.startswith(("w", "q_",
                                                               "kv_"))}
    got, g = jax.jit(jax.value_and_grad(lambda w: jnp.sum(jnp.sin(
        xing4._mla(cfg, *tables, h, w)))))(weights)
    want, gw = jax.jit(jax.value_and_grad(lambda w: jnp.sum(jnp.sin(
        ref.latent_attention(h, w, s)))))(weights)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-4
    for name in g:
        if float(jnp.linalg.norm(gw[name])) == 0:   # unused by attention
            continue
        assert float(jnp.linalg.norm(g[name] - gw[name])) < 1e-3 * float(
            jnp.linalg.norm(gw[name])), name


def test_flash_in_parts_interpreted_is_the_reference_attention():
    """The kernels as the cell calls them, at 128 + 64 / 128 with the one
    rotary key head (interpreted here): ``_mla`` under ``flash_interpret``
    against the reference's full masked softmax, value and the gradient in
    the hidden state, at a row of 256."""
    from ray_tpu.ops.rope import rope_lane_tables
    cfg = deepseek_v3.deepseek_v3_tiny(
        hidden=128, max_seq_len=256,
        attention_impl="flash_interpret").stack
    layer, s = _one_layer(cfg, seed=3)
    tables = rope_lane_tables(64, 256, cfg.rope_theta, None)
    h = jax.random.normal(jax.random.key(2), (1, 256, cfg.hidden))
    got, g = jax.jit(jax.value_and_grad(lambda h: jnp.sum(jnp.sin(
        xing4._mla(cfg, *tables, h, layer)))))(h)
    want, gw = jax.jit(jax.value_and_grad(lambda h: jnp.sum(jnp.sin(
        ref.latent_attention(h, layer, s)))))(h)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-3
    assert float(jnp.linalg.norm(g - gw)) < 2e-3 * float(jnp.linalg.norm(gw))


def test_a_traced_call_counts_its_geometry(monkeypatch):
    from ray_tpu.ops.rope import rope_lane_tables
    from ray_tpu.util import telemetry
    seen = []
    monkeypatch.setattr(
        telemetry, "inc", lambda name, value=1.0, tags=None: seen.append(
            tags) if name == "ray_tpu_mla_call_geometry_total" else None)
    for rank in (None, 48):
        cfg = deepseek_v3.deepseek_v3_tiny(q_lora_rank=rank).stack
        layer, _ = _one_layer(cfg)
        jax.eval_shape(lambda h: xing4._mla(
            cfg, *rope_lane_tables(64, 64), h, layer),
            jax.ShapeDtypeStruct((3, 32, 64), jnp.float32))
    keys = telemetry.CATALOG["ray_tpu_mla_call_geometry_total"]["tag_keys"]
    # (the tags a model with key heads, noise heads or a window adds,
    # ``models/motif.py``, are absent: these stacks keep their series)
    assert [tuple(t.get(k) for k in keys) for t in seen] == [
        ("2", "128", "64", "128", "none", "3", "32", None, None, None),
        ("2", "128", "64", "128", "48", "3", "32", None, None, None)]


# ----------------------------------------------- the selection bias, shares

def test_selection_bias_moves_by_the_sign_rule_after_a_step():
    """torchtitan's rule: d = rate * sign(mean load - load) over the
    router's outputs, b <- b + d - mean(d), from the loads the step itself
    counted (the optimizer never sees it: it is state, not a parameter)."""
    cfg, params, bias, batch = _setup(seq=32, bias_update_rate=1e-3)
    _, report = jax.jit(lambda p: deepseek_v3.loss_and_report(
        p, batch, cfg, {"bias": bias}))(params)
    state, metrics = deepseek_v3.update_state({"bias": bias}, report, cfg)
    counts = np.asarray(report["counts"], np.float32)        # [2, 16]
    assert counts.sum(-1).tolist() == [2 * 32 * 6] * 2       # 6 a token
    d = 1e-3 * np.sign(counts.mean(-1, keepdims=True) - counts)
    np.testing.assert_allclose(state["bias"],
                               bias + d - d.mean(-1, keepdims=True),
                               atol=1e-7)
    assert 1e-3 <= float(np.abs(np.asarray(state["bias"]) - bias).max()) \
        < 2e-3
    assert metrics["moe_choices"].shape == (2, 64, 6)
    assert float(metrics["moe_dropped"]) == 0.0


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """The share test: the routed parts that the eight shares of one expert
    layer give (2 of 16 experts each), with the shared SwiGLU, which every
    chip computes alike, counted once, are the uncut reference's layer."""
    cfg, params, _, _ = _setup()
    s = _sizes(cfg)
    layer = jax.tree.map(lambda a: a[0], params["moe"])
    bias = 0.3 * jax.random.normal(jax.random.key(2), (16,))
    h = jax.random.normal(jax.random.key(3), (2, 32, cfg.hidden))
    shared = xing4._swiglu(h, layer["shared_gate"], layer["shared_up"],
                           layer["shared_down"], cfg.dtype)
    routed, held = 0.0, 0
    for share in range(8):
        mine = cfg.replace(experts_held=2, held_start=2 * share).stack
        part = {k: (v[2 * share:2 * share + 2]
                    if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in layer.items()}
        out, loads = xing4._moe(mine, h, part, bias)
        routed = routed + out - shared
        held += int(loads["counts"][2 * share:2 * share + 2].sum())
    assert held == 64 * 6                      # every assignment, once
    want, _ = ref.feed_forward(h, layer, bias, s)
    np.testing.assert_allclose(shared + routed, want, atol=3e-5)
    # ...and a share alone is not the layer.
    assert float(jnp.abs(out - want).max()) > 1e-2


# ------------------------------------------------------ the train step

def test_train_step_trains_through_model_module_and_reports():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import (StepState, make_lm_train_step,
                                       model_module)
    cfg = deepseek_v3.deepseek_v3_tiny(experts_held=4, held_start=4,
                                       remat=True, layer_rows=1,
                                       loss_chunks=4)
    assert model_module(cfg) is deepseek_v3
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh,
                                                 learning_rate=1e-3)
    params, state = init_fn(jax.random.key(0))
    assert isinstance(state, StepState)
    assert state.model["bias"].shape == (2, 16)     # no module's layer
    assert "mtp" not in params and not [
        k for k in params["moe"] if k.startswith("hc_")]
    rng = np.random.default_rng(0)
    batch = place({"tokens": rng.integers(0, 256, (2, 64), dtype=np.int32),
                   "loss_mask": np.ones((2, 64), np.int32)})
    first = None
    for _ in range(3):
        params, state, m = step_fn(params, state, batch)
        first = first or m
    assert float(m["loss"]) < float(first["loss"])
    assert first["moe_choices"].shape == (2, 128, 6)
    assert float(first["moe_dropped"]) == 0.0
    assert not {"mtp_loss", "hc_sinkhorn_residual"} & set(first)
    assert float(jnp.abs(state.model["bias"]).max()) > 0


def test_a_mesh_and_a_pipeline_are_refused_by_name():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    cfg = deepseek_v3.deepseek_v3_tiny()
    params = jax.eval_shape(lambda k: deepseek_v3.init_params(cfg, k),
                            jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    with pytest.raises(NotImplementedError, match="pp_microbatches"):
        jax.eval_shape(lambda p, b: deepseek_v3.loss_fn(
            p, b, cfg.replace(pp_microbatches=2)), params, batch)
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(fsdp=2),
                                   devices=jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="on a mesh"):
            jax.eval_shape(lambda p, b: deepseek_v3.loss_fn(p, b, cfg),
                           params, batch)
    finally:
        set_global_mesh(before)


def test_compiled_step_names_the_scopes_the_benchmark_sums():
    """``mla_device_share`` is what ``benchmark/scopes.py`` finds under
    ``block/attn`` in the compiled step's text; latent attention's four
    products have scopes of their own under ``mla``, the rotary passes lie
    under ``rope``, forward and backward alike, and ``mla`` and ``rope``
    stay the parents the shipped readers match."""
    from benchmark import scopes
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    cfg = deepseek_v3.deepseek_v3_tiny(experts_held=4, held_start=4,
                                       remat=True, layer_rows=1)
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh, learning_rate=1e-3)
    params, state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "loss_mask")}
    names = list(scopes.op_names(step_fn.lower(params, state, batch)
                                 .compile().as_text()).values())
    by = {"scopes": dict.fromkeys({scopes.scope_path(n) for n in names},
                                  1.0)}
    for scope in ("block/attn", "block/attn/mla", "block/attn/mla/q",
                  "block/attn/mla/kv_a", "block/attn/mla/kv_b",
                  "block/attn/mla/out", "block/attn/rope",
                  "block/moe/shared", "block/moe/experts", "block/mlp"):
        assert scopes.seconds_under(by, scope) > 0, scope
    assert any("mla/q" in n and "transpose(jvp(" in n for n in names)
    from benchmark.layer_metrics import mla_device_share
    facts = {"trace": {"busy_s": float(len(by["scopes"]))},
             "arch": {"scopes": by, "sizes": _sizes(cfg)}}
    share = mla_device_share.read(facts)
    assert 0 < share < 100


def test_published_stack_is_built_but_not_run():
    """The published model's count, by the issue's arithmetic, and the
    benchmark's cut: its layout is the program's, its count the config
    file's."""
    cfg = deepseek_v3.DeepseekV3Config()
    shapes = jax.eval_shape(
        lambda k: deepseek_v3.init_params(cfg, k, jnp.bfloat16),
        jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        deepseek_v3.num_params(cfg) == 30_670_809_088
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256 \
        + 4096 * 2048
    assert attention == 26_345_984
    dense = attention + 2 * 2048 + 3 * 2048 * 6144
    expert_layer = attention + 2 * 2048 + 2048 * 128 + 3 * 2048 * 1536 \
        + 128 * 4_718_592
    assert (dense, expert_layer) == (64_098_816, 640_029_184)
    assert deepseek_v3.num_params(cfg) == dense + 47 * expert_layer \
        + 2 * 128256 * 2048 + 2048
    assert shapes["moe"]["w_gate"].shape == (47, 128, 2048, 768)
    assert shapes["moe"]["shared_up"].shape == (47, 2048, 1536)
    assert shapes["dense"]["wq"].shape == (1, 2048, 32, 192)
    with open(os.path.join(
            ROOT, "benchmark/configs/kanana-2-30b-a3b.json")) as f:
        config = json.load(f)
    s = arch.sizes_of(config)
    cut = arch.program_config(s, 8192, config["train"])
    shape_of = lambda tree: jax.tree.map(lambda x: x[0], tree,
                                         is_leaf=_lm.is_shape)
    assert shape_of(arch.shapes(s)) == shape_of(
        deepseek_v3.param_shapes(cut))
    layers = config["num_hidden_layers"]
    held = dense + (layers - 1) * (
        expert_layer - 112 * 4_718_592) + 2 * 16032 * 2048 + 2048
    assert arch.parameters(s)["held"] == deepseek_v3.num_params(cut) == \
        config["parameters"] == held
    assert {k: v for k, v in s.items()
            if k != "bias_update_rate"} == _sizes(cut)


# ------------------------------------------ Xing4.0's program is the parent's

#: sha256 and length of ``str(jax.make_jaxpr(step))`` of Xing4.0's tiny train
#: step (addresses struck), taken on the parent commit of PR 51 (e89f465)
#: with the script this test repeats
XING4_STEP_AT_THE_PARENT = (
    1805258,
    "53fa49f989ddc34bd1a01d842a0a59c93e5a9f10584ef0ee3b5375a908f5fdf6")


def test_xing4_tiny_train_step_s_jaxpr_is_the_parent_s():
    """Latent attention serves two models since PR 51; the program it
    traces for Xing4.0 (bottleneck, yarn, four lanes, the module) is,
    equation for equation, the one the parent commit traced: scopes and a
    trace-time counter were added, no operation."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    cfg = xing4.xing4_tiny(experts_held=4, held_start=4, remat=True,
                           layer_rows=1, loss_chunks=4)
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh, learning_rate=1e-3)
    params, state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "loss_mask")}
    text = re.sub(r"0x[0-9a-f]+", "0x",
                  str(jax.make_jaxpr(step_fn)(params, state, batch)))
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == \
        XING4_STEP_AT_THE_PARENT


# ------------------- Kanana's and Trinity-Mini's programs are the parent's

#: as ``XING4_STEP_AT_THE_PARENT``, for the tiny train steps of the two other
#: models that trace the functions PR 55 opened (``xing4._mla``,
#: ``ops/moe.sigmoid_routing``), taken on its parent commit (6745cf7) with
#: the script this test repeats
STEPS_AT_THE_PARENT = {
    "kanana": (deepseek_v3.deepseek_v3_tiny, (
        450537,
        "7867902a0e120a403b80a10878950adc934d3999c0cb243fdc2ee486b993185c")),
    "trinity": (afmoe.afmoe_tiny, (
        556643,
        "2e79d67e010269ae699609fbf749a6130a569fc9d8b9e555e4960013926bfa71")),
}


@pytest.mark.parametrize("model", sorted(STEPS_AT_THE_PARENT))
def test_a_tiny_train_step_s_jaxpr_is_the_parent_s(model):
    """Latent attention serves a third model since PR 55 (a head-wise gate
    that only ``models/bailing_hybrid.py`` hands it) and the router may
    limit its choice to groups; the program they trace for Kanana (no gate,
    one group) and for Trinity-Mini (one group) is, equation for equation,
    the one the parent commit traced."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    tiny, at_the_parent = STEPS_AT_THE_PARENT[model]
    cfg = tiny(experts_held=4, held_start=4, remat=True, layer_rows=1,
               loss_chunks=4)
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh, learning_rate=1e-3)
    params, state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "loss_mask")}
    text = re.sub(r"0x[0-9a-f]+", "0x",
                  str(jax.make_jaxpr(step_fn)(params, state, batch)))
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == \
        at_the_parent
