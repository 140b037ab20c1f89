"""The mimo_v2 model (MiMo-V2-Flash's block) against its plain reference, and
the pieces it brought: window layers whose softmax carries a learned sink,
head counts and rotary bases by layer kind, the partial rotary, the value
scale, a chip's share of the heads beside its share of the experts, and the
train step's state and report."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import _lm, afmoe, mimo_v2
from ray_tpu.models.mimo_v2 import FULL, WINDOW
from ray_tpu.ops.rope import apply_rope, rope_lane_tables, rotate_heads

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_mimo_v2 as ref  # noqa: E402
from benchmark.archs import mimo_v2_flash as arch  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")

LETTER = {FULL: "f", WINDOW: "w"}


def _sizes(cfg):
    """The reference's sizes for a program configuration (it reads the
    head counts off the weights it is handed)."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers,
            "kinds": "".join(LETTER[k] for k in cfg.kinds),
            "D": cfg.head_dim, "Dv": cfg.v_head_dim, "R": cfg.rotary_dim,
            "W": cfg.sliding_window, "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "route_eps": cfg.route_eps,
            "value_scale": cfg.value_scale, "theta": cfg.rope_theta,
            "swa_theta": cfg.swa_rope_theta, "eps": cfg.norm_eps}


@functools.lru_cache(maxsize=None)
def _setup(seed=0, rows=2, seq=40, **kw):
    """Tiny widths that keep what the code must tell apart (``mimo_v2_tiny``:
    the dense full layer, then W W F W; 8 query heads over 2 / 4 key heads,
    24 / 16 head sizes of which 8 lanes turn, a window of 24 in a row of
    40).  The WHOLE model's parameters, made once a configuration of this
    module under one ``jax.jit``, the norms shaken away from one and the
    sinks away from each other."""
    cfg = mimo_v2.mimo_v2_tiny(**kw)
    whole = cfg.replace(heads_held=None, experts_held=None)

    @jax.jit
    def make(key, shake_key):
        params = mimo_v2.init_params(whole, key)
        keys = iter(jax.random.split(shake_key, 64))

        def shake(path, a):
            if "norm" in str(path[-1]):             # away from one
                return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
            if "sink" in str(path[-1]):             # a head its own
                return a + jax.random.normal(next(keys), a.shape)
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


#: the share most cases run: 4 of 8 query heads from the fifth (a window
#: layer's key heads 2-3, a full layer's key head 1), 2 of 8 experts
SHARE = dict(heads_held=4, head_start=4, experts_held=2, held_start=4)


def _whole_loss(params, bias, batch, s):
    """The reference's pieces put together: the loss of its ``logits``."""
    lg = ref.logits(params, bias, batch["tokens"], s)
    t = batch["tokens"]
    targets = jnp.concatenate([t[:, 1:], jnp.zeros_like(t[:, :1])], 1)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, targets[..., None], -1)[..., 0]
    mask = batch["loss_mask"].astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.sum(mask)


@functools.lru_cache(maxsize=None)
def _reference(**share):
    """(the share's parameters, bias, batch, sizes, the reference's logits,
    loss and gradient in every leaf) of ``_setup(**share)``, once."""
    cfg, params, bias, batch = _setup(**share)
    held = mimo_v2.take_share(params, cfg)
    s = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: ref.logits(p, bias, batch["tokens"], s))(
            held)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: _whole_loss(p, bias, batch, s)))(held)
    return cfg, held, bias, batch, s, logits, loss, grads


def _close(got, want, tol, what=""):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        a = np.asarray(a)
        np.testing.assert_allclose(
            np.asarray(b), a, atol=tol * max(np.abs(a).max(), 1e-6),
            rtol=tol, err_msg=what + jax.tree_util.keystr(path))


# ------------------------------------------------------------- the model

#: how the program is run against the one reference
MODEL_CASES = {
    # the plain forms: reference attention, no remat, the whole batch
    "plain": {},
    # as the cell runs it: the kernels (interpreted), full remat, a row a
    # layer call, the loss in chunks
    "as_the_cell": dict(attention_impl="flash_interpret", remat=True,
                        layer_rows=1, loss_chunks=4),
}


@pytest.mark.parametrize("case", MODEL_CASES)
def test_model_matches_reference_logits_loss_and_every_gradient(case):
    """On a share of the heads and of the experts, float32 on both sides:
    the logits, the loss and the gradient of every leaf (the window layers'
    sinks, both kinds' ``wk`` / ``wv``, the dense layer among them) against
    ``jax.grad`` of the reference's pieces put together."""
    cfg, held, bias, batch, s, want_logits, want_loss, want = \
        _reference(**SHARE)
    cfg = cfg.replace(**MODEL_CASES[case])
    logits = jax.jit(lambda p: mimo_v2.forward(
        p, batch["tokens"], cfg, {"bias": bias}))(held)
    np.testing.assert_allclose(logits, want_logits, atol=2e-4, rtol=2e-4)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: mimo_v2.loss_fn(p, batch, cfg, {"bias": bias})))(held)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    _close(grads, want, 2e-3)
    sinks = arch.sinks_of(grads)
    assert len(sinks) == 3 and all(
        g.shape == (4,) and float(jnp.abs(g).max()) > 0 for g in sinks)


def test_the_walk_in_blocks_is_the_reference_too():
    """What the chip's check runs (a layer's ``jax.vjp`` at a time, the
    judged leaves alone) gives the whole reference's loss, judged gradients
    and routers' choices; ``routing`` row after row gives the same."""
    cfg, held, bias, batch, s, _, want_loss, want = _reference(**SHARE)
    with jax.default_matmul_precision("highest"):
        loss, judged, tops = ref.loss_judged_grads_and_routing(
            held, bias, batch["tokens"], batch["loss_mask"], s)
        again = ref.routing(held, bias, batch["tokens"], s)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    _close(judged, arch.judged_of(want), 1e-4)
    assert [sorted(layer) for layer in judged["layers"]] == [
        ["attn_norm", "mlp_norm"], ["attn_norm", "mlp_norm", "sink"],
        ["attn_norm", "mlp_norm", "sink"], ["attn_norm", "mlp_norm"],
        ["attn_norm", "mlp_norm", "sink"]]
    assert tops.shape == (4, 80, 4)
    np.testing.assert_array_equal(tops, again)
    _, loads = jax.jit(lambda p: mimo_v2.loss_and_report(
        p, batch, cfg, {"bias": bias}))(held)
    assert float(ref.routing_mismatch_share(loads["top"], tops, 8)) == 0.0


#: a reference that leaves something out, by the weights it is handed
FAULTS = {
    # no sink: the window layers' softmax without its extra column
    "no_sink": lambda layer: {k: v for k, v in layer.items() if k != "sink"},
    # the window layers' key heads read as the full layers' count: the
    # first half of them, twice the query heads each
    "window_key_heads_halved": lambda layer: {
        **layer, **({"wk": layer["wk"][:, :1], "wv": layer["wv"][:, :1]}
                    if "sink" in layer else {})},
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_reference_that_leaves_it_out_fails_the_same_comparison(fault):
    """The comparison the chip's ``correct`` makes (``relative_distance``
    over the judged gradients, here at float32's own noise) sees a missing
    sink and a window layer read with the full layers' key heads: each is
    far outside anything rounding gives, and the logits differ too."""
    cfg, held, bias, batch, s, want_logits, _, want = _reference(**SHARE)
    broken = {**held, "layers": [FAULTS[fault](l) for l in held["layers"]]}
    with jax.default_matmul_precision("highest"):
        _, got, _ = ref.loss_judged_grads_and_routing(
            broken, bias, batch["tokens"], batch["loss_mask"], s)
        _, ok, _ = ref.loss_judged_grads_and_routing(
            held, bias, batch["tokens"], batch["loss_mask"], s)
        logits = ref.logits(broken, bias, batch["tokens"], s)
    norms = arch.norms_of
    assert float(ref.relative_distance(norms(ok), norms(want))) < 1e-4
    assert float(ref.relative_distance(norms(got), norms(want))) > 0.1
    assert float(jnp.abs(logits - want_logits).max()) > 1e-2


# ----------------------------------------------------------- the shares

@pytest.mark.parametrize("kind,layer", [(WINDOW, 1), (FULL, 3)])
def test_the_four_head_shares_add_up_to_the_uncut_layer(kind, layer):
    """Four shares of 2 of the 8 query heads, each with the key heads it
    reads (a window layer's 4 key heads one a share; a full layer's 2 key
    heads each held by the two shares under it), at the same input: their
    parts of Attn add up to the uncut reference's, sinks and all."""
    cfg, params, _, _ = _setup()
    s = _sizes(cfg)
    x = jax.random.normal(jax.random.key(3), (2, 40, cfg.hidden))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, w: ref.attention_operator(
            x, w, s, LETTER[kind]))(x, params["layers"][layer])
    tables = {k: rope_lane_tables(cfg.rotary_dim, cfg.max_seq_len, theta)
              for k, theta in ((FULL, cfg.rope_theta),
                               (WINDOW, cfg.swa_rope_theta))}
    parts, masses = [], []
    for share in range(4):
        held = cfg.replace(heads_held=2, head_start=2 * share)
        assert held.heads_of(kind) == (
            (2, 1, share) if kind == WINDOW else (2, 1, share // 2))
        w = mimo_v2.take_share(params, held)["layers"][layer]
        assert w["wq"].shape[1] == 2 and w["wk"].shape[1] == 1
        part, mass = jax.jit(lambda x, w: mimo_v2._attn(
            held, kind, tables, x, w))(x, w)
        parts.append(part)
        masses.append(mass)
    np.testing.assert_allclose(sum(parts), want, atol=2e-5, rtol=2e-4)
    assert float(jnp.abs(parts[0] - want).max()) > 1e-2
    if kind == WINDOW:      # the shares' masses are means over their heads
        _, whole = jax.jit(lambda x, w: mimo_v2._attn(
            cfg, kind, tables, x, w))(x, params["layers"][layer])
        np.testing.assert_allclose(np.mean(masses), whole, rtol=1e-5)
        assert 0.02 < float(whole) < 0.98
    else:
        assert masses == [None] * 4


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of one expert each (a 32-chip group's 8 of 256 at the
    tiny size), every one routing over all 8 at the same input: their parts
    of MoE add up to the uncut reference's routed sum (no shared expert, so
    nothing is counted twice)."""
    cfg, params, bias, _ = _setup()
    s, layer = _sizes(cfg), params["layers"][2]
    x = jax.random.normal(jax.random.key(4), (2, 40, cfg.hidden))
    flat = x.reshape(-1, cfg.hidden)
    with jax.default_matmul_precision("highest"):
        top, w = ref.route(flat, layer["router"], bias[1], s)
        want = ref.held_experts(flat, top, w, layer["w_gate"], layer["w_up"],
                                layer["w_down"], 0).reshape(x.shape)
    parts = []
    for e in range(8):
        held = cfg.replace(experts_held=1, held_start=e)
        w_e = mimo_v2.take_share(params, held)["layers"][2]
        assert w_e["w_gate"].shape[0] == 1 and w_e["router"].shape[1] == 8
        part, loads = afmoe._moe(held, x, w_e, bias[1],
                                 route_eps=held.route_eps)
        np.testing.assert_array_equal(loads["top"], top)
        parts.append(part)
    np.testing.assert_allclose(sum(parts), want, atol=2e-5, rtol=2e-4)


def test_a_share_names_its_heads_or_is_refused():
    """``heads_of``: whole groups, or a part of one under its one key head;
    a share that straddles groups, does not divide the heads or starts off
    its own multiple is refused by name."""
    cfg = mimo_v2.MimoV2Config()
    assert cfg.heads_of(FULL) == (64, 4, 0)
    assert cfg.heads_of(WINDOW) == (64, 8, 0)
    four = cfg.replace(heads_held=16, head_start=32)
    assert four.heads_of(FULL) == (16, 1, 2)
    assert four.heads_of(WINDOW) == (16, 2, 4)
    eight = cfg.replace(heads_held=8, head_start=24)
    assert eight.heads_of(FULL) == (8, 1, 1)
    assert eight.heads_of(WINDOW) == (8, 1, 3)
    for bad in (dict(heads_held=24), dict(heads_held=16, head_start=8),
                dict(heads_held=16, head_start=64),
                dict(heads_held=16, swa_heads=32)):
        with pytest.raises(ValueError, match="share of"):
            cfg.replace(**bad).heads_of(WINDOW)
    assert cfg.kinds[:12] == (FULL,) + (WINDOW,) * 4 + (FULL,) \
        + (WINDOW,) * 5 + (FULL,)
    assert sum(k == FULL for k in cfg.kinds) == 9
    with pytest.raises(ValueError, match="layer_types"):
        cfg.replace(layer_types=("conv",) * 48).kinds


def test_the_published_model_counts_to_the_parameter():
    """From shapes alone: the whole model's language-model parameters
    (without the prediction and encoder modules the configuration has no
    key for), and the chip's share the benchmark's file states."""
    cfg = mimo_v2.MimoV2Config()
    attn_w, attn_f = (4096 * 64 * 192 + 2 * 0 + 4096 * k * (192 + 128)
                      + 64 * 128 * 4096 for k in (8, 4))
    expert = 3 * 4096 * 2048
    whole = (2 * 152576 * 4096 + 4096 + 48 * 2 * 4096
             + 39 * (attn_w + 64) + 9 * attn_f
             + 3 * 4096 * 16384 + 47 * (4096 * 256 + 256 * expert))
    assert mimo_v2.num_params(cfg) == whole
    assert 3.0e11 < whole < 3.1e11
    share = cfg.replace(
        vocab_size=19072, layers=6, num_dense_layers=0,
        layer_types=(WINDOW,) * 5 + (FULL,), heads_held=16, experts_held=8)
    assert mimo_v2.num_params(share) == 1510789200
    axes = mimo_v2.param_logical_axes(share)
    shapes = mimo_v2.param_shapes(share)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(shapes, is_leaf=_lm.is_shape)
    assert shapes["layers"][0]["sink"] == ((16,), 0, 0.0)
    assert "sink" not in shapes["layers"][5]
    assert shapes["layers"][0]["wk"][0] == (4096, 2, 192)
    assert shapes["layers"][5]["wv"][0] == (4096, 1, 128)


# ------------------------------------------------------------ the pieces

def test_partial_rotary_turns_the_first_lanes_alone():
    """``rotate_heads(rotary_dim=)``: the first 8 of 24 lanes turn as two
    halves of 4 by the tables of 8, the other 16 pass bit for bit, and the
    result is head-major; the two kinds' tables differ."""
    x = jax.random.normal(jax.random.key(5), (2, 40, 3, 24))
    cos, sin = rope_lane_tables(8, 64, 1e4)
    got = rotate_heads(x, cos, sin, rotary_dim=8)
    assert got.shape == (2, 3, 40, 24)
    np.testing.assert_array_equal(got[..., 8:],
                                  jnp.swapaxes(x, 1, 2)[..., 8:])
    want = apply_rope(jnp.swapaxes(x[..., :8], 1, 2), cos[:, :4], sin[:, 4:])
    np.testing.assert_allclose(got[..., :8], want, rtol=1e-6)
    np.testing.assert_allclose(
        jnp.swapaxes(got, 1, 2), ref._rope_first(x, 1e4, 8), atol=1e-5)
    other = rotate_heads(x, *rope_lane_tables(8, 64, 5e6), rotary_dim=8)
    assert float(jnp.abs(other - got)[..., :8].max()) > 0.1
    # the whole head where the lanes are all of it: the path it was
    np.testing.assert_array_equal(
        rotate_heads(x[..., :8], cos, sin, rotary_dim=8),
        rotate_heads(x[..., :8], cos, sin))


def test_rows_at_a_time_joins_the_groups_reports():
    """``_lm.rows_at_a_time``: a layer two rows at a time gives the whole
    batch's result; counts add, choices lie end to end, a sink's mass is the
    groups' mean; a batch that does not split is refused."""
    x = jnp.arange(4 * 3 * 2, dtype=jnp.float32).reshape(4, 3, 2)

    def one(rows):
        n = rows.shape[0] * rows.shape[1]
        return rows * 2, {"counts": jnp.full((5,), n), "dropped": jnp.int32(1),
                          "sliced": jnp.int32(0),
                          "top": jnp.broadcast_to(rows[..., :1].reshape(
                              n, 1).astype(jnp.int32), (n, 2)),
                          "sink_mass": jnp.mean(rows)}

    y, report = _lm.rows_at_a_time(one, x, 2, 2)
    np.testing.assert_array_equal(y, x * 2)
    whole_y, whole = _lm.rows_at_a_time(one, x, None, 2)
    np.testing.assert_array_equal(whole_y, y)
    np.testing.assert_array_equal(report["counts"], whole["counts"])
    np.testing.assert_array_equal(report["top"], whole["top"])
    assert int(report["dropped"]) == 2 and int(report["sliced"]) == 0
    np.testing.assert_allclose(report["sink_mass"], whole["sink_mass"])
    assert _lm.rows_at_a_time(lambda r: (r, {}), x, 1, 2)[1] == {}
    with pytest.raises(ValueError, match="layer_rows=3"):
        _lm.rows_at_a_time(one, x, 3, 2)


def test_the_step_reports_the_sinks_mass_and_moves_the_bias():
    """``update_state``: afmoe's metrics and state, and ``sink_mass_mean``,
    the mean over the window layers of what the sinks took, which the
    trainer records as ``ray_tpu_attn_sink_mass_mean``; a model with no
    window layer has no sink and reports none."""
    from ray_tpu.train import _context
    cfg, held, bias, batch, *_ = _reference(**SHARE)
    state = {"bias": bias}
    _, loads = jax.jit(lambda p: mimo_v2.loss_and_report(
        p, batch, cfg, state))(held)
    assert loads["sink_mass"].shape == (3,)
    assert loads["counts"].shape == (4, 8)
    after, metrics = mimo_v2.update_state(state, loads, cfg)
    np.testing.assert_allclose(metrics["sink_mass_mean"],
                               jnp.mean(loads["sink_mass"]))
    assert 0.02 < float(metrics["sink_mass_mean"]) < 0.98
    want_state, want = afmoe.update_state(
        state, {k: v for k, v in loads.items() if k != "sink_mass"}, cfg)
    np.testing.assert_array_equal(after["bias"], want_state["bias"])
    assert set(metrics) == set(want) | {"sink_mass_mean"}
    moved = float(jnp.abs(after["bias"] - bias).max())
    assert 0.5 * cfg.bias_update_rate < moved < 2 * cfg.bias_update_rate
    assert _context._loop_readings(
        {"sink_mass_mean": jnp.float32(0.5), "loss": 1.0}) == {
            "ray_tpu_attn_sink_mass_mean": 0.5}
    # a stack of full layers alone has no sink and reports no mass
    full = mimo_v2.mimo_v2_tiny(layers=2, layer_types=(FULL, FULL))
    _, loads = jax.jit(lambda p: mimo_v2.loss_and_report(
        p, batch, full))(mimo_v2.init_params(full, jax.random.key(0)))
    assert loads["sink_mass"].shape == (0,)
    assert "sink_mass_mean" not in mimo_v2.update_state(
        mimo_v2.init_state(full), loads, full)[1]


def test_the_train_step_trains_it_and_hands_out_the_sinks_mass():
    """``make_lm_train_step`` finds the model by its configuration's module,
    carries the selection bias as state, reports the loads and
    ``sink_mass_mean``, and moves every sink."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import (StepState, make_lm_train_step,
                                       model_module)
    cfg = mimo_v2.mimo_v2_tiny(layer_rows=1, remat="full", loss_chunks=2,
                               **SHARE)
    assert model_module(cfg) is mimo_v2
    init_fn, step_fn, place = make_lm_train_step(
        cfg, build_mesh(MeshSpec(), devices=jax.devices()[:1]),
        learning_rate=1e-3)
    params, state = init_fn(jax.random.key(0))
    assert isinstance(state, StepState)
    assert state.model["bias"].shape == (4, 8)
    sinks0 = [np.asarray(s) for s in arch.sinks_of(params)]
    assert all((s == 1.0).all() and s.shape == (4,) for s in sinks0)
    batch = place({"tokens": np.random.default_rng(0).integers(
        0, 256, (2, 40), dtype=np.int32)})
    losses = []
    for _ in range(4):
        params, state, m = step_fn(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert 0.02 < float(m["sink_mass_mean"]) < 0.98
    assert m["moe_choices"].shape == (4, 80, 4)
    assert float(m["moe_dropped"]) == 0.0
    for before, after in zip(sinks0, arch.sinks_of(params)):
        assert (np.asarray(after) != before).all()


def test_a_mesh_and_a_pipeline_are_refused_by_name():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    cfg, held, bias, batch, *_ = _reference(**SHARE)
    with pytest.raises(NotImplementedError, match="pp_microbatches.*M4"):
        mimo_v2.loss_fn(held, batch, cfg.replace(pp_microbatches=2),
                        {"bias": bias})
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(fsdp=2),
                                   devices=jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="head shares"):
            mimo_v2.loss_fn(held, batch, cfg, {"bias": bias})
    finally:
        set_global_mesh(before)
