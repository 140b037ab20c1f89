"""The afmoe model (Trinity's block) against its plain reference, the share of
an expert-parallel layer, windowed flash attention, the train step's state,
and a CPU rehearsal of its benchmark cell.  (The expert layer's own pieces,
cut from this file in PR 59: the sigmoid router, the dropless dispatch and
the grouped products in ``tests/test_afmoe_experts.py``, the sums over a
token's rows in ``tests/test_afmoe_rows.py``.)"""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import afmoe
from ray_tpu.ops import moe
from ray_tpu.ops.attention import (DIAGONAL, INTERIOR, KIND, block_schedule,
                                   flash_attention, reference_attention)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_afmoe as ref  # noqa: E402
from benchmark.archs import afmoe as arch  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers, "H": cfg.heads, "Hkv": cfg.kv_heads,
            "D": cfg.head_dim, "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "Ms": cfg.moe_mlp_dim * cfg.num_shared_experts,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "window": cfg.sliding_window,
            "layer_types": cfg.kinds, "theta": cfg.rope_theta,
            "eps": cfg.norm_eps}


@functools.lru_cache(maxsize=None)
def _setup(seed=0, **kw):
    """Made once a configuration of this module (nothing writes into what it
    returns), the parameters under one ``jax.jit``: run eagerly the
    initialisation is one program a leaf shape."""
    cfg = afmoe.afmoe_tiny(**kw)

    @jax.jit
    def make(key, shake_key):
        params = afmoe.init_params(cfg, key)
        # Norm weights away from one, and a selection bias large enough to
        # change which experts are chosen: choosing by s + b and weighting
        # by s are then told apart.
        keys = iter(jax.random.split(shake_key, 64))
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a * (1 + 0.2 * jax.random.normal(
                next(keys), a.shape)) if "norm" in str(path[-1]) else a,
            params)
        bias = 0.3 * jax.random.normal(
            next(keys), (cfg.expert_layers, cfg.num_experts))
        return params, bias

    params, bias = make(jax.random.key(seed), jax.random.key(seed + 1))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (2, 48), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (2, 48),
                                              dtype=np.int32))}
    return cfg, params, bias, batch


def _ref_loss(params, bias, batch, s):
    lg = ref.logits(params, bias, batch["tokens"], s)
    t = batch["tokens"]
    targets = jnp.concatenate([t[:, 1:], jnp.zeros_like(t[:, :1])], 1)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, targets[..., None], -1)[..., 0]
    mask = batch["loss_mask"].astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.sum(mask)


@pytest.mark.parametrize("held", [(8, 0), (2, 4)], ids=["whole", "share"])
def test_model_matches_reference_loss_and_all_gradients(held):
    cfg, params, bias, batch = _setup(experts_held=held[0],
                                      held_start=held[1])
    assert params["moe"]["w_gate"].shape[1] == held[0]
    s = _sizes(cfg)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: afmoe.loss_fn(
        p, batch, cfg, {"bias": bias})))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: _ref_loss(p, bias, batch, s)))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(g - w)) <= 2e-4 * float(
            jnp.linalg.norm(w)) + 1e-7, path
    # The bias matters: without it other experts are chosen.
    assert abs(float(jax.jit(lambda p: afmoe.loss_fn(p, batch, cfg))(params))
               - float(loss)) > 1e-4
    # The reference's own walk gives the same norm gradients and the
    # program's choices.
    _, norm_grads, top = ref.loss_norm_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert float(ref.relative_distance(
        norm_grads, arch.norms_of(want_grads))) < 1e-5
    _, loads = jax.jit(lambda p: afmoe.loss_and_loads(
        p, {"bias": bias}, batch, cfg))(params)
    assert float(ref.routing_mismatch_share(loads["top"], top,
                                            cfg.num_experts)) == 0.0
    assert int(loads["dropped"].sum()) == 0


def test_remat_rows_at_a_time_and_loss_chunks_do_not_change_the_loss():
    cfg, params, bias, batch = _setup()
    run = jax.jit(jax.value_and_grad(lambda p, c: afmoe.loss_and_loads(
        p, {"bias": bias}, batch, c), has_aux=True), static_argnums=1)
    (want, want_loads), want_grads = run(params, cfg)
    # A layer takes one row of the two at a time, each under the remat.
    (got, loads), grads = run(params, cfg.replace(remat=True, loss_chunks=4,
                                                  layer_rows=1))
    assert abs(float(got) - float(want)) < 1e-5
    for name in ("counts", "dropped", "top"):
        np.testing.assert_array_equal(loads[name], want_loads[name])
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-4)
    # At most layer_rows rows: more than the batch has is the whole batch.
    assert float(run(params, cfg.replace(layer_rows=3))[0][0]) == float(want)
    three = {k: jnp.concatenate([v, v[:1]]) for k, v in batch.items()}
    with pytest.raises(ValueError, match="layer_rows=2"):
        afmoe.loss_fn(params, three, cfg.replace(layer_rows=2))


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """Each share adds the shared expert and its own experts' part: the sum
    of the shares' routed parts, with the shared expert counted once, is the
    uncut layer."""
    cfg = afmoe.afmoe_tiny(num_experts=16, top_k=4)
    keys = jax.random.split(jax.random.key(3), 4)
    layer = jax.tree.map(lambda a: a[0], jax.jit(
        lambda key: afmoe.init_params(cfg, key))(keys[0])["moe"])
    bias = 0.3 * jax.random.normal(keys[1], (16,))
    h = jax.random.normal(keys[2], (2, 32, cfg.hidden))
    s = _sizes(cfg)
    flat = h.reshape(-1, cfg.hidden)
    top, w = ref.route(flat, layer["router"], bias, s)
    shared = ref._swiglu(h, layer["shared_gate"], layer["shared_up"],
                         layer["shared_down"], None)
    want = shared + ref.held_experts(
        flat, top, w, layer["w_gate"], layer["w_up"], layer["w_down"],
        0).reshape(h.shape)
    total, held = 0.0, 0
    for share in range(8):
        mine = cfg.replace(experts_held=2, held_start=2 * share)
        part = {k: (v[2 * share:2 * share + 2]
                    if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in layer.items()}
        out, loads = afmoe._moe(mine, h, part, bias)
        total = total + out - shared
        held += int(loads["counts"][2 * share:2 * share + 2].sum())
    assert held == 64 * 4                      # every assignment, once
    np.testing.assert_allclose(total + shared, want, atol=2e-5)


@pytest.mark.parametrize("window", [None, 40, 64, 150])
def test_windowed_flash_matches_reference(window):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 32))
    k = jax.random.normal(ks[1], (1, 2, 256, 32))
    v = jax.random.normal(ks[2], (1, 2, 256, 32))

    def grads(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))),
            argnums=(0, 1, 2))(q, k, v)

    want = grads(lambda q, k, v: reference_attention(q, k, v, window=window))
    got = grads(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=True, window=window))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    # The window is a different function.
    if window is not None:
        full = reference_attention(q, k, v)
        assert float(jnp.abs(full - reference_attention(
            q, k, v, window=window)).max()) > 1e-3


@pytest.mark.parametrize("seq,window,steps,edge", [
    (8192, 2048, 70, 28), (8192, None, 136, 16), (4096, None, 36, 8),
    (8192, 8192, 136, 16), (2048, 512, 7, 7)])
def test_block_schedule_with_a_window(seq, window, steps, edge):
    for major in "qk":
        sched = block_schedule(seq, seq, 512, 512, 0, True, major, window)
        assert sched.shape[1] == steps
        assert int((sched[KIND] == DIAGONAL).sum()) == edge
        assert int((sched[KIND] == INTERIOR).sum()) == steps - edge


def test_train_step_carries_the_selection_bias_past_the_optimizer():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import StepState, make_lm_train_step
    cfg = afmoe.afmoe_tiny(experts_held=4, held_start=4, remat=True)
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh,
                                                 learning_rate=1e-3)
    params, state = init_fn(jax.random.key(0))
    assert isinstance(state, StepState)
    assert state.model["bias"].shape == (4, 8)
    assert "bias" not in jax.tree_util.tree_structure(params).__repr__()
    rng = np.random.default_rng(0)
    batch = place({"tokens": rng.integers(0, 256, (2, 64), dtype=np.int32),
                   "loss_mask": np.ones((2, 64), np.int32)})
    _, loads = afmoe.loss_and_loads(params, state.model, batch, cfg)
    want = moe.update_selection_bias(state.model["bias"], loads["counts"],
                                     cfg.bias_update_rate)
    first = None
    for _ in range(3):
        params, state, m = step_fn(params, state, batch)
        first = first if first is not None else (
            m, np.asarray(state.model["bias"]))
    np.testing.assert_allclose(first[1], want, atol=1e-7)
    assert float(m["loss"]) < float(first[0]["loss"])
    assert float(m["moe_dropped"]) == 0.0
    # Half the experts are held here, twice the buffer's rows: each of the
    # four expert layers' calls takes it in slices.
    assert float(first[0]["moe_sliced_calls"]) == float(
        (loads["counts"][:, 4:8].sum(-1) > moe.buffer_rows(2 * 64, cfg.top_k)
         ).sum()) == 4.0
    assert float(first[0]["moe_held_assignments"]) == float(
        loads["counts"][:, 4:8].sum(-1).mean())
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    # The step hands out its routers' own choices, row after row.
    np.testing.assert_array_equal(first[0]["moe_choices"], loads["top"])
    assert loads["top"].shape == (4, 2 * 64, cfg.top_k)


def test_compiled_step_names_the_scopes_the_benchmark_sums():
    """``moe_device_share.moe8k`` is what ``benchmark/scopes.py`` finds under
    ``block/moe`` in the compiled step's text: every part of the expert layer
    and both kinds of attention are there under their names, forward and
    backward, through the scan, the remat, the row-at-a-time map and the
    dispatch's ``cond``."""
    from benchmark import scopes
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    cfg = afmoe.afmoe_tiny(experts_held=4, held_start=4, remat=True,
                           layer_rows=1)
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh, learning_rate=1e-3)
    params, state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "loss_mask")}
    names = scopes.op_names(step_fn.lower(params, state, batch).compile()
                            .as_text()).values()
    paths = {scopes.scope_path(n) for n in names}
    for part in ("route", "shared", "dispatch", "experts", "combine"):
        assert f"forward_backward/block/moe/{part}" in paths, part
    for kind in ("block/attn_window", "block/attn_full"):
        assert any(p.endswith(kind) for p in paths), kind
    assert "optimizer" in paths
    # Forward and backward of a part fall under one path.
    assert sum(1 for n in names if "block/moe/cond" in n
               and "transpose(jvp())" in n) > 0
    assert not any("cond" in p or "jvp" in p or "while" in p for p in paths)


def test_published_stack_is_built_but_not_run():
    cfg = afmoe.AfmoeConfig()
    shapes = jax.eval_shape(
        lambda k: afmoe.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        afmoe.num_params(cfg) == 26123970560
    assert shapes["moe"]["w_gate"].shape == (30, 128, 2048, 1024)
    assert cfg.kinds.count(afmoe.FULL) == 8 and cfg.kinds[3] == afmoe.FULL
    batch = {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)}
    out = jax.eval_shape(
        lambda p, b: afmoe.loss_and_loads(p, afmoe.init_state(cfg), b,
                                          cfg.replace(remat=True)),
        shapes, batch)
    assert out[1]["counts"].shape == (30, 128)
    # The benchmark's cut: its layout is the program's.
    with open(os.path.join(ROOT, "benchmark/configs/trinity-mini.json")) as f:
        config = json.load(f)
    s = arch.sizes_of(config)
    cut = arch.program_config(s, 8192, config["train"])
    assert jax.tree.map(lambda x: x[0], arch.shapes(s),
                        is_leaf=afmoe._is_shape) == jax.tree.map(
        lambda x: x[0], afmoe.param_shapes(cut), is_leaf=afmoe._is_shape)
    assert arch.parameters(s)["held"] == afmoe.num_params(cut) == \
        config["parameters"] == 1243427072
    assert cut.kinds == ("sliding_attention",) * 3 + (
        "full_attention",) + ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")


def test_report_records_the_experts_loads():
    from ray_tpu.train import _context
    got = _context._moe_loads({"moe_held_assignments": jnp.float32(5.0),
                               "moe_dropped": 0.0, "loss": 1.0,
                               "moe_sliced_calls": jnp.float32(2.0)})
    assert got == {"ray_tpu_moe_held_assignments": 5.0,
                   "ray_tpu_moe_dropped_total": 0.0,
                   "ray_tpu_moe_sliced_calls_total": 2.0}


def test_benchmark_cell_rehearses_on_the_cpu():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "trinity-mini.train-moe8k", "--seed", str(2 ** 31 + 5),
         "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    named = set(last["metrics_named"])
    assert "moe_load_max_over_mean.moe8k" in named
    assert not {n for n in named if "roofline" in n or "mfu" in n
                or "share" in n}
    assert "name=routing_mismatch_share" in done.stdout
    assert "'moe_dropped': 0.0" in done.stdout
    # At the toy sizes a quarter of the experts are held: the calls take
    # the buffer in slices, and the runner's line says so.
    assert "'moe_sliced_calls': " in done.stdout
