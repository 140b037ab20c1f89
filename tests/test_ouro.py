"""The looped stack (models/ouro.py) against its plain reference
(benchmark/reference_ouro.py), on the CPU at tiny sizes and seeded weights:
loss, the passes' losses and exit shares, the gradient of every leaf; what
one loop reduces to; the tied gradient as the sum over untied copies; remat
and loss chunks; what the train step reports and the trainer records; the
scopes the readers sum; the cell's rehearsal; the int8 control."""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import archs, reference_ouro as ref  # noqa: E402
from benchmark.archs import ouro as arch  # noqa: E402
from ray_tpu.models import _lm, ouro  # noqa: E402

S = {"V": 256, "E": 64, "L": 3, "T": 4, "H": 4, "Hkv": 2, "D": 16, "M": 96,
     "theta": 1e6, "eps": 1e-6, "beta": 0.1, "post_norm_start": 0.5,
     "gate_start": 1.0}
CFG = ouro.ouro_tiny(kv_heads=2)
LEAVES = sorted("/".join(str(k.key) for k in path) for path, _ in
                jax.tree_util.tree_flatten_with_path(
                    ouro.param_shapes(CFG), is_leaf=ouro._is_shape)[0])


@functools.lru_cache(maxsize=None)
def _weights(seed=5):
    """float32 weights in the program's layout, norms off 1 so that their
    gradients say something.  Made once a seed: nothing writes into them."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     archs.make_weights(arch.shapes(S), seed))
    keys = iter(jax.random.split(jax.random.key(seed), 16))
    jiggle = lambda a: a * (1 + 0.1 * jax.random.normal(next(keys), a.shape))
    w["final_norm"] = jiggle(w["final_norm"])
    w["blocks"] = {k: jiggle(v) if k.endswith("norm") else v
                   for k, v in w["blocks"].items()}
    w["exit_gate"]["b"] = jnp.float32(0.3)
    return w


def _batch(seed=1, rows=2, seq=32):
    tokens = jax.random.randint(jax.random.key(seed), (rows, seq), 0, S["V"])
    mask = jnp.ones((rows, seq), jnp.int32).at[:, -1].set(0).at[0, :5].set(0)
    return {"tokens": tokens, "loss_mask": mask}


# The program's loss, report and gradients, one compiled program a
# configuration: run eagerly it is a program an operation.
_value_and_grads = jax.jit(jax.value_and_grad(
    ouro.loss_and_report, has_aux=True), static_argnums=2)


def _at(tree, leaf):
    for k in leaf.split("/"):
        tree = tree[k]
    return tree


@pytest.fixture(scope="module")
def both():
    """(program, reference): each (loss, report, gradient of every leaf)."""
    w, batch = _weights(), _batch()
    (loss, report), grads = _value_and_grads(w, batch, CFG)
    with jax.default_matmul_precision("highest"):
        (want, want_report), want_grads = jax.jit(jax.value_and_grad(
            lambda w: ref.loss_and_report(w, batch["tokens"],
                                          batch["loss_mask"], S),
            has_aux=True))(w)
    return (loss, report, grads), (want, want_report, want_grads)


def test_the_layout_is_the_benchmarks_and_the_count_is_published():
    shapes = jax.tree.map(lambda x: x[0], ouro.param_shapes(CFG),
                          is_leaf=ouro._is_shape)
    assert shapes == jax.tree.map(lambda x: x[0], arch.shapes(S),
                                  is_leaf=archs.is_shape)
    assert ouro.param_logical_axes(CFG).keys() == shapes.keys()
    assert ouro.num_params(ouro.OuroConfig()) == 2667974657
    assert ouro.num_params(ouro.OuroConfig(layers=12)) == 817991681
    params = ouro.init_params(CFG, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == \
        ouro.num_params(CFG) == arch.parameters(S)["held"]
    assert float(params["exit_gate"]["b"]) == 0.0
    assert float(jnp.std(params["exit_gate"]["w"])) > 0.05


def test_loss_matches_the_reference(both):
    (loss, _, _), (want, _, _) = both
    assert float(loss) == pytest.approx(float(want), rel=2e-6)


@pytest.mark.parametrize("key", ["loop_loss", "loop_exit_share",
                                 "loop_exit_entropy"])
def test_report_matches_the_reference(both, key):
    (_, report, _), (_, want, _) = both
    assert report[key].shape == (() if key == "loop_exit_entropy"
                                 else (S["T"],))
    np.testing.assert_allclose(report[key], want[key], rtol=3e-6)
    if key == "loop_exit_share":
        assert float(jnp.sum(report[key])) == pytest.approx(1.0, abs=1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_matches_the_reference(both, leaf):
    (_, _, grads), (_, _, want) = both
    got, want = _at(grads, leaf), _at(want, leaf)
    assert float(jnp.linalg.norm(want)) > 0
    assert float(ref.relative_distance(got, want)) < 2e-5, leaf


def test_the_walk_is_the_whole_function(both):
    """The reference's walk, a layer and a head at a time (what the chip's
    check runs), gives what differentiating it in one piece gives."""
    _, (want, want_report, want_grads) = both
    batch = _batch()
    with jax.default_matmul_precision("highest"):
        loss, report, grads = ref.loss_and_judged_grads(
            _weights(), batch["tokens"], batch["loss_mask"], S)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    for k in want_report:
        np.testing.assert_allclose(report[k], want_report[k], rtol=3e-6)
    assert float(ref.relative_distance(
        grads, arch.judged_of(want_grads))) < 1e-5


def test_forward_is_the_last_pass():
    w, batch = _weights(), _batch()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda w: ref.logits(w, batch["tokens"], S))(w)
    np.testing.assert_allclose(
        jax.jit(lambda w: ouro.forward(w, batch["tokens"], CFG))(w), want,
        atol=2e-5)


def test_one_loop_is_plain_cross_entropy_and_moves_no_gate():
    w, batch = _weights(), _batch()
    cfg = CFG.replace(loops=1)
    (loss, report), grads = _value_and_grads(w, batch, cfg)
    h, = ouro.hidden_states(w, batch["tokens"], cfg)
    plain = _lm.next_token_loss(h, w["lm_head"], batch, 0, cfg.dtype)
    assert float(loss) == pytest.approx(float(plain), rel=1e-6)
    assert float(report["loop_loss"][0]) == pytest.approx(float(plain),
                                                          rel=1e-6)
    assert float(report["loop_exit_share"][0]) == 1.0
    assert float(report["loop_exit_entropy"]) == 0.0
    assert float(jnp.max(jnp.abs(grads["exit_gate"]["w"]))) == 0.0
    assert float(grads["exit_gate"]["b"]) == 0.0
    assert float(jnp.linalg.norm(grads["blocks"]["wq"])) > 0


def test_tied_gradient_is_the_sum_over_untied_copies(both):
    """T copies of the stack holding the same values, one a pass: the
    program's gradient of a layer weight is the sum of the copies'."""
    (_, _, grads), _ = both
    w, batch = _weights(), _batch()
    wide = ref._widen(w)

    def untied(copies):
        x, nll, z = wide["embed"][batch["tokens"]], [], []
        for blocks in copies:
            for i in range(S["L"]):
                x = ref.layer(x, jax.tree.map(lambda a: a[i], blocks), S)
            x = ref._rms_norm(x, wide["final_norm"], S["eps"])
            n, g = ref.head(x, wide["lm_head"], wide["exit_gate"],
                            ref._targets(batch["tokens"]))
            nll.append(n)
            z.append(g)
        return ref.objective(jnp.stack(nll), jnp.stack(z),
                             batch["loss_mask"].astype(jnp.float32),
                             S["beta"])[0]

    with jax.default_matmul_precision("highest"):
        per_copy = jax.jit(jax.grad(untied))([wide["blocks"]] * S["T"])
    assert len(per_copy) == S["T"]
    summed = jax.tree.map(lambda *g: sum(g), *per_copy)
    for name, got in grads["blocks"].items():
        assert float(ref.relative_distance(got, summed[name])) < 2e-5, name
        # and no single copy's is the whole of it
        assert float(ref.relative_distance(
            per_copy[-1][name], summed[name])) > 0.1, name


@pytest.mark.parametrize("chunks", [0, 2, 8])
def test_loss_chunks_do_not_change_the_per_token_nll(chunks):
    w, batch = _weights(), _batch()
    h = ouro.hidden_states(w, batch["tokens"], CFG)[-1]
    targets, _, _ = _lm.targets_and_mask(batch)
    want = _lm.token_nll(h, w["lm_head"], targets, 0, jnp.float32)
    got = _lm.token_nll(h, w["lm_head"], targets, chunks, jnp.float32)
    assert got.shape == want.shape == batch["tokens"].shape
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # and the weighted sum, which leaves no chunk as a per-token array (the
    # scalar losses of Llama and afmoe are one)
    wts = jax.random.uniform(jax.random.key(3), want.shape)
    total = _lm.token_nll(h, w["lm_head"], targets, chunks, jnp.float32, wts)
    assert total.shape == ()
    np.testing.assert_allclose(total, jnp.sum(want * wts), rtol=2e-6)


@pytest.mark.parametrize("options", [
    dict(remat=True), dict(remat="dots"), dict(remat="full", loss_chunks=2),
    dict(remat=False, loss_chunks=8), dict(remat="dots_nobatch",
                                           loss_chunks=8)],
    ids=lambda o: "-".join(f"{k}={v}" for k, v in o.items()))
def test_remat_and_loss_chunks_do_not_change_loss_or_gradient(both, options):
    (want, want_report, want_grads), _ = both
    (loss, report), grads = _value_and_grads(_weights(), _batch(),
                                             CFG.replace(**options))
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(report["loop_loss"], want_report["loop_loss"],
                               rtol=1e-6)
    assert float(ref.relative_distance(grads, want_grads)) < 1e-5


def test_what_the_model_does_not_do_is_refused_by_name():
    w, batch = _weights(), _batch()
    with pytest.raises(NotImplementedError, match="pipeline"):
        ouro.loss_fn(w, batch, CFG.replace(pp_microbatches=2))
    with pytest.raises(ValueError, match="mlp_only"):
        ouro.loss_fn(w, batch, CFG.replace(remat="mlp_only"))


def _step(cfg, **kw):
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    return make_lm_train_step(cfg, build_mesh(MeshSpec(), devices=jax.devices()[:1]),
                              learning_rate=1e-3, **kw)


def test_train_step_reports_the_passes_and_learns():
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    before = get_global_mesh()
    try:
        init_fn, step_fn, place = _step(CFG)
        params, opt_state = init_fn(jax.random.key(0))
        batch = place(_batch(rows=2))
        (want, want_report) = ouro.loss_and_report(params, batch, CFG)
        losses = []
        for i in range(4):
            params, opt_state, m = step_fn(params, opt_state, batch)
            if i == 0:
                assert set(m) == {"loss", "grad_norm", "loop_loss",
                                  "loop_exit_share", "loop_exit_entropy"}
                assert m["loop_loss"].shape == (4,) == \
                    m["loop_exit_share"].shape
                assert float(m["loss"]) == pytest.approx(float(want),
                                                         rel=1e-5)
                np.testing.assert_allclose(
                    m["loop_exit_share"], want_report["loop_exit_share"],
                    rtol=1e-5)
                assert 0 < float(m["loop_exit_entropy"]) <= np.log(4) + 1e-6
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        with pytest.raises(NotImplementedError, match="reports"):
            _step(CFG, grad_accum=2)
    finally:
        set_global_mesh(before)


def test_train_step_on_a_mesh_gives_what_one_device_gives():
    """The step through the mesh's GSPMD rules ({fsdp: 4} over host
    devices): same loss, same report, same gradient norm."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    before, got = get_global_mesh(), {}
    try:
        for name, spec, n in (("one", MeshSpec(), 1),
                              ("fsdp4", MeshSpec(fsdp=4), 4)):
            init_fn, step_fn, place = make_lm_train_step(
                CFG, build_mesh(spec, devices=jax.devices()[:n]),
                learning_rate=1e-3)
            params, opt_state = init_fn(jax.random.key(0))
            _, _, got[name] = step_fn(params, opt_state,
                                      place(_batch(rows=4)))
    finally:
        set_global_mesh(before)
    for k, v in got["one"].items():
        np.testing.assert_allclose(got["fsdp4"][k], v, rtol=2e-5,
                                   err_msg=k)


def test_report_records_the_passes_gauges():
    from ray_tpu.train import _context
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import telemetry
    got = _context._loop_readings({
        "loss": 1.0, "loop_loss": jnp.asarray([3.0, 2.5, 2.25, 2.0]),
        "loop_exit_share": np.asarray([0.4, 0.3, 0.2, 0.1], np.float32),
        "loop_exit_entropy": jnp.float32(1.25)})
    assert got["ray_tpu_train_loop_loss"] == [3.0, 2.5, 2.25, 2.0]
    assert got["ray_tpu_train_loop_exit_entropy"] == 1.25
    assert len(got["ray_tpu_train_loop_exit_share"]) == 4
    assert _context._loop_readings({"loss": 1.0}) == {}
    for name in got:
        assert telemetry.CATALOG[name]["type"] == "gauge"
    assert telemetry.CATALOG["ray_tpu_train_loop_loss"]["tag_keys"] == \
        ("pass",)
    # train.report's own path: the gauges of a reported step.
    metrics_mod._reset_for_tests()

    class Rank0:
        _report_seq = 1

        def get_world_rank(self):
            return 0

    _context._note_step(Rank0(), 0.0, 0.0, {
        "loop_loss": [3.0, 2.0], "loop_exit_share": [0.75, 0.25],
        "loop_exit_entropy": 0.5})
    text = metrics_mod.prometheus_text()
    assert 'ray_tpu_train_loop_loss{pass="1"} 2.0' in text, text[-2000:]
    assert 'ray_tpu_train_loop_exit_share{pass="0"} 0.75' in text
    assert "ray_tpu_train_loop_exit_entropy 0.5" in text
    metrics_mod._reset_for_tests()


def test_compiled_step_names_the_scopes_the_readers_sum():
    from benchmark import scopes
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    before = get_global_mesh()
    try:
        init_fn, step_fn, place = _step(CFG.replace(remat="full",
                                                    loss_chunks=2))
        params, opt_state = jax.eval_shape(init_fn, jax.random.key(0))
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in _batch().items()}
        text = step_fn.lower(params, opt_state, batch).compile().as_text()
    finally:
        set_global_mesh(before)
    paths = {scopes.scope_path(name) for name in
             scopes.op_names(text).values()}
    by = {"scopes": {p: 1.0 for p in paths}}
    for scope in ("loop/0", "loop/1", "loop/2", "loop/3", "block/attn",
                  "block/mlp", "loss", "forward_backward", "optimizer"):
        assert scopes.seconds_under(by, scope) > 0, (scope, sorted(paths))
    # a layer's scopes lie inside a pass's, the heads and the gate outside
    assert any(p.endswith("loop/2/block/mlp") for p in paths), sorted(paths)
    assert not any("loop/" in p and "loss" in p for p in paths)


def test_benchmark_cell_rehearses_on_the_cpu_and_names_no_device_metric():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ouro-2.6b.train-loop4k", "--seed", str(2 ** 31 + 7),
         "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "correct" not in last
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    device = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    assert not device & set(last["metrics_named"]), last
    assert "loop_exit_entropy.loop4k" in last["metrics_named"]
    for line in done.stdout.splitlines():
        if line.startswith("[correct]"):
            assert line.endswith("ok=True"), line


CONTROL_CELL = (
    {"chips": 1},
    {**json.load(open(os.path.join(ROOT, "benchmark/configs/ouro-2.6b.json"))),
     "hidden_size": 256, "intermediate_size": 704, "num_hidden_layers": 3,
     "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
     "vocab_size": 2048},
    {"seq_len": 256})
CONTROL_CELL[1]["train"] = {**CONTROL_CELL[1]["train"],
                            "tokens_per_chip": 512, "attention": "reference",
                            "loss_chunks": 0}


def test_int8_control_lies_above_the_limits_and_the_program_below():
    """At a size a test can hold: the reference computed in int8 is called
    wrong by the cell's limits, through the loss function and through the
    compiled step, and the program is not (the chip's readings at the
    cell's sizes are in the configuration's ``correct_why``)."""
    from benchmark import control_loop
    limits = CONTROL_CELL[1]["correct"]
    seeds = [1, 2]
    program = dict(control_loop.program_numbers(*CONTROL_CELL, seeds))
    control = dict(control_loop.control_numbers(*CONTROL_CELL, seeds))
    for seed in seeds:
        p, c = program[seed], control[seed]
        print(seed, {k: (p[k], c[k]) for k in limits})
        for name in ("norm_grad_distance", "step_moments_distance"):
            assert c[name] > limits[name] > p[name], (seed, name)
            assert c[name] > 2 * p[name], (seed, name)
        for name in limits:
            assert p[name] <= limits[name], (seed, name)
        assert p["step_update_mismatch"] == 0
        assert p["step_loss_distance"] < 1e-3


def test_a_pass_left_out_or_a_loss_on_the_last_pass_alone_is_called_wrong():
    """What the two new distances are for: a step that ran a pass fewer, or
    weighed nothing and took the last pass's loss, reports other per-pass
    losses and exit shares than the reference."""
    from benchmark.kinds import train_loop
    limits = CONTROL_CELL[1]["correct"]
    w, batch = _weights(), _batch()
    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(lambda w: ref.loss_and_report(
            w, batch["tokens"], batch["loss_mask"], S))(w)
    want = train_loop.loop_readings(want)
    (_, sound), _ = _value_and_grads(w, batch, CFG)
    d = train_loop.loop_distances(train_loop.loop_readings(sound), want)
    assert d["loop_loss_distance"] < limits["loop_loss_distance"]
    assert d["exit_share_distance"] < limits["exit_share_distance"]
    # a pass fewer: the last reported pass is the third's state run again
    _, short = jax.jit(ouro.loss_and_report, static_argnums=2)(
        w, batch, CFG.replace(loops=3))
    short = train_loop.loop_readings(short)
    padded = {k: (v + v[-1:] if isinstance(v, list) else v)
              for k, v in short.items()}
    d = train_loop.loop_distances(padded, want)
    assert d["exit_share_distance"] > limits["exit_share_distance"]
    # the last pass alone: all of the exit distribution on it
    alone = {**want, "loop_exit_share": [0.0, 0.0, 0.0, 1.0]}
    assert train_loop.loop_distances(alone, want)["exit_share_distance"] \
        > limits["exit_share_distance"]
