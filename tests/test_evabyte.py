"""The EvaByte block (models/evabyte.py) against its plain reference
(benchmark/reference_evabyte.py), on the CPU at tiny sizes and seeded
weights: loss, the heads' losses, the gradient of every leaf; one head as
Llama's loss; remat, loss chunks and the kernels; the pinned stream; what
the train step reports and the trainer records; the scopes the readers sum;
the cell's rehearsal; the int8 control.  (The EVA kernels alone, cut from
this file in PR 59: ``tests/test_evabyte_kernels.py``.)"""

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

from benchmark import archs, reference_evabyte as ref  # noqa: E402
from benchmark.archs import evabyte as arch  # noqa: E402
from ray_tpu.models import _lm, evabyte, llama  # noqa: E402


S = {"V": 64, "E": 64, "L": 2, "H": 4, "Hkv": 4, "D": 16, "M": 96,
     "window": 64, "chunk": 8, "J": 3, "theta": 1e5, "eps": 1e-5}


CFG = evabyte.evabyte_tiny()


LEAVES = sorted("/".join(str(k.key) for k in path) for path, _ in
                jax.tree_util.tree_flatten_with_path(
                    evabyte.param_shapes(CFG), is_leaf=_lm.is_shape)[0])


@functools.lru_cache(maxsize=None)
def _weights(seed=5):
    """float32 weights in the program's layout, the norms' offsets off 0 so
    that a missing ``1 +`` shows.  Made once a seed: nothing writes into
    them."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     archs.make_weights(arch.shapes(S), seed))
    keys = iter(jax.random.split(jax.random.key(seed), 8))
    off = lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape)
    w["final_norm"] = off(w["final_norm"])
    w["blocks"] = {k: off(v) if k.endswith("norm") else v
                   for k, v in w["blocks"].items()}
    return w


def _batch(seed=1, rows=2, seq=256):
    tokens = jax.random.randint(jax.random.key(seed), (rows, seq), 0, S["V"])
    mask = jnp.ones((rows, seq), jnp.int32).at[0, :5].set(0)
    return {"tokens": tokens, "loss_mask": mask}


def _at(tree, leaf):
    for k in leaf.split("/"):
        tree = tree[k]
    return tree


# ----------------------------------------------------------------- model

@pytest.fixture(scope="module")
def both():
    """(program, reference): each (loss, report, gradient of every leaf)."""
    w, batch = _weights(), _batch()
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda w: evabyte.loss_and_report(w, batch, CFG), has_aux=True))(w)
    with jax.default_matmul_precision("highest"):
        (want, want_report), want_grads = jax.jit(jax.value_and_grad(
            lambda w: ref.loss_and_report(w, batch["tokens"],
                                          batch["loss_mask"], S),
            has_aux=True))(w)
    return (loss, report, grads), (want, want_report, want_grads)


def test_the_layout_is_the_benchmarks_and_the_count_is_published():
    shapes = jax.tree.map(lambda x: x[0], evabyte.param_shapes(CFG),
                          is_leaf=_lm.is_shape)
    assert shapes == jax.tree.map(lambda x: x[0], arch.shapes(S),
                                  is_leaf=archs.is_shape)
    assert evabyte.param_logical_axes(CFG).keys() == shapes.keys()
    assert evabyte.param_logical_axes(CFG)["blocks"].keys() \
        == shapes["blocks"].keys()
    assert evabyte.num_params(evabyte.EvaByteConfig()) == 6488330240
    assert evabyte.num_params(evabyte.EvaByteConfig(layers=4)) == 821366784
    params = evabyte.init_params(CFG, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == \
        evabyte.num_params(CFG) == arch.parameters(S)["held"]
    # a norm's weight is an offset and starts at 0; mu is of a query's size
    assert not params["final_norm"].any()
    assert not params["blocks"]["attn_norm"].any()
    assert 0.5 < float(jnp.std(params["blocks"]["eva_mu"])) < 1.5


def test_loss_matches_the_reference(both):
    (loss, _, _), (want, _, _) = both
    assert float(loss) == pytest.approx(float(want), rel=2e-6)


def test_head_losses_match_the_reference(both):
    (loss, report, _), (_, want, _) = both
    assert report["head_loss"].shape == (S["J"],)
    np.testing.assert_allclose(report["head_loss"], want["head_loss"],
                               rtol=3e-6)
    assert float(loss) == pytest.approx(float(jnp.mean(want["head_loss"])),
                                        rel=3e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_matches_the_reference(both, leaf):
    (_, _, grads), (_, _, want) = both
    got, want = _at(grads, leaf), _at(want, leaf)
    assert float(jnp.linalg.norm(want)) > 0
    assert float(ref.relative_distance(got, want)) < 2e-5, leaf


def test_the_walk_is_the_whole_function(both):
    """The reference's walk, a layer at a time (what the chip's check
    runs), gives what differentiating it in one piece gives."""
    _, (want, want_report, want_grads) = both
    batch = _batch()
    with jax.default_matmul_precision("highest"):
        loss, report, grads = ref.loss_and_judged_grads(
            _weights(), batch["tokens"], batch["loss_mask"], S)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    np.testing.assert_allclose(report["head_loss"],
                               want_report["head_loss"], rtol=3e-6)
    assert float(ref.relative_distance(
        grads, arch.judged_of(want_grads))) < 1e-5


def test_the_reference_takes_the_mlp_by_rows(monkeypatch):
    """The reference's gated MLP over 64 positions at a time gives what it
    gives at once."""
    w, batch = _weights(), _batch()
    with jax.default_matmul_precision("highest"):
        program = lambda: jax.jit(lambda w: ref.loss_and_report(  # traced anew
            w, batch["tokens"], batch["loss_mask"], S))
        whole = program()(w)
        monkeypatch.setattr(ref, "MLP_ROWS", 64)
        sliced = program()(w)
    assert float(sliced[0]) == pytest.approx(float(whole[0]), rel=1e-6)


def test_forward_is_the_heads_logits():
    w, batch = _weights(), _batch()
    logits = jax.jit(lambda w: evabyte.forward(w, batch["tokens"], CFG))(w)
    assert logits.shape == (2, 256, S["J"], S["V"])
    assert logits.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda w: ref.logits(w, batch["tokens"], S))(w)
    np.testing.assert_allclose(logits, want, atol=2e-4)


@pytest.mark.parametrize("masked", [False, True])
def test_one_head_is_llamas_loss(masked):
    """With ``num_pred_heads`` 1 the objective is ``next_token_loss``."""
    one = CFG.replace(pred_heads=1)
    w = jax.jit(lambda key: evabyte.init_params(one, key))(jax.random.key(3))
    batch = _batch()
    if not masked:
        batch = {"tokens": batch["tokens"]}
    else:
        batch["loss_mask"] = batch["loss_mask"].at[:, -1].set(0)
    x = evabyte._forward_hidden(w, batch["tokens"], one)
    want = _lm.next_token_loss(x, w["lm_head"][:, 0], batch, 0, one.dtype)
    loss, report = evabyte.loss_and_report(w, batch, one)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert report["head_loss"].shape == (1,)
    assert llama.loss_fn  # the function every Llama-shaped loss stands on


@pytest.mark.parametrize("chunks", [0, 2, 8])
def test_loss_chunks_do_not_change_the_heads_nll(chunks):
    """``token_nll`` with several heads a position: the per-token form and
    the weighted sums, fused and in chunks."""
    x = jax.random.normal(jax.random.key(0), (2, 32, 16))
    head = jax.random.normal(jax.random.key(1), (16, 3, 40))
    targets = jax.random.randint(jax.random.key(2), (2, 32, 3), 0, 40)
    weights = jax.random.uniform(jax.random.key(3), (2, 32, 3))
    want = jnp.stack([_lm.token_nll(x, head[:, j], targets[..., j], 0,
                                    jnp.float32) for j in range(3)], -1)
    got = _lm.token_nll(x, head, targets, chunks, jnp.float32)
    assert got.shape == (2, 32, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    sums = _lm.token_nll(x, head, targets, chunks, jnp.float32, weights)
    np.testing.assert_allclose(sums, jnp.sum(want * weights, axis=(0, 1)),
                               rtol=1e-5)


@pytest.mark.parametrize("options", [
    dict(remat="full"), dict(remat="dots"), dict(loss_chunks=4),
    dict(remat="full", loss_chunks=8, attention_impl="flash_interpret")])
def test_remat_chunks_and_kernels_do_not_change_loss_or_gradient(both,
                                                                 options):
    (want, want_report, want_grads), _ = both
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda w, b: evabyte.loss_and_report(w, b, CFG.replace(**options)),
        has_aux=True))(_weights(), _batch())
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_allclose(report["head_loss"], want_report["head_loss"],
                               rtol=1e-5)
    assert float(ref.relative_distance(grads, want_grads)) < 2e-5


@pytest.mark.parametrize("remat", [False, "full"])
def test_pinning_the_stream_does_not_change_loss_or_gradient(monkeypatch,
                                                             remat):
    """Where JAX reports a TPU the float32 stream's norms pin its layout
    (``ops/norms.py``): the program carries the constraint at each of its
    three traced norms, forward and backward, and the loss, the heads and
    the gradient of every leaf are what they are without it, up to the
    order of a float32 sum (the compiler fuses differently round it)."""
    import importlib
    from ops_cases import _norm_paths
    cfg = CFG.replace(remat=remat)
    w, batch = _weights(), _batch()
    program = lambda: jax.jit(jax.value_and_grad(   # each traced anew
        lambda w, b: evabyte.loss_and_report(w, b, cfg), has_aux=True))
    (want, want_report), want_grads = program()(w, batch)
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"),
                        "_on_tpu", lambda: True)
    before = _norm_paths()
    fn = program()
    assert fn.lower(w, batch).as_text().count("@LayoutConstraint") >= 6
    (loss, report), grads = fn(w, batch)
    # two rows of 256; the scan's body is traced once: its two norms and
    # the final one (the call finds the lowering's trace)
    assert _norm_paths().get(("row_major", "512"), 0) == \
        before.get(("row_major", "512"), 0) + 3
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(report["head_loss"], want_report["head_loss"],
                               rtol=1e-6)
    for leaf in LEAVES:
        assert float(ref.relative_distance(
            _at(grads, leaf), _at(want_grads, leaf))) < 2e-6, leaf


@pytest.mark.parametrize("what", ["pipeline", "mlp_only", "positions",
                                  "grouped heads", "ring"])
def test_what_the_model_does_not_do_is_refused_by_name(what):
    w, batch = _weights(), _batch()
    if what == "pipeline":
        with pytest.raises(NotImplementedError, match="pipeline"):
            evabyte.loss_fn(w, batch, CFG.replace(pp_microbatches=2))
    elif what == "mlp_only":
        with pytest.raises(ValueError, match="mlp_only"):
            evabyte.loss_fn(w, batch, CFG.replace(remat="mlp_only"))
    elif what == "positions":
        with pytest.raises(NotImplementedError, match="sharded sequence"):
            evabyte.loss_fn(w, batch, CFG, positions=jnp.arange(256))
    elif what == "grouped heads":
        with pytest.raises(ValueError, match="a key head a query head"):
            evabyte.init_params(CFG.replace(kv_heads=2), jax.random.key(0))
    else:
        with pytest.raises(ValueError, match="no impl"):
            evabyte.loss_fn(w, batch, CFG.replace(attention_impl="ring"))


def _step(cfg, **kw):
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    return make_lm_train_step(
        cfg, build_mesh(MeshSpec(), devices=jax.devices()[:1]),
        learning_rate=1e-3, **kw)


def test_train_step_reports_the_heads_and_learns():
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    from ray_tpu.parallel.spmd import model_module
    before = get_global_mesh()
    try:
        assert model_module(CFG) is evabyte
        init_fn, step_fn, place = _step(CFG)
        params, opt_state = init_fn(jax.random.key(0))
        batch = place(_batch(rows=2))
        (want, want_report) = evabyte.loss_and_report(params, batch, CFG)
        losses = []
        for i in range(4):
            params, opt_state, m = step_fn(params, opt_state, batch)
            if i == 0:
                assert set(m) == {"loss", "grad_norm", "head_loss"}
                assert m["head_loss"].shape == (3,)
                assert float(m["loss"]) == pytest.approx(float(want),
                                                         rel=1e-5)
                np.testing.assert_allclose(
                    m["head_loss"], want_report["head_loss"], rtol=1e-5)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        with pytest.raises(NotImplementedError, match="reports"):
            _step(CFG, grad_accum=2)
    finally:
        set_global_mesh(before)


def test_report_records_the_heads_gauges():
    from ray_tpu.train import _context
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import telemetry
    got = _context._loop_readings({
        "loss": 1.0, "head_loss": jnp.asarray([3.0, 2.5, 2.25])})
    assert got == {"ray_tpu_train_head_loss": [3.0, 2.5, 2.25]}
    assert telemetry.CATALOG["ray_tpu_train_head_loss"]["tag_keys"] == \
        ("head",)
    assert telemetry.CATALOG["ray_tpu_eva_step_geometry_total"]["type"] == \
        "counter"
    metrics_mod._reset_for_tests()

    class Rank0:
        _report_seq = 1

        def get_world_rank(self):
            return 0

    _context._note_step(Rank0(), 0.0, 0.0, {"head_loss": [3.0, 2.0]})
    text = metrics_mod.prometheus_text()
    assert 'ray_tpu_train_head_loss{head="1"} 2.0' in text, text[-2000:]
    metrics_mod._reset_for_tests()


def test_compiled_step_names_the_scopes_the_readers_sum():
    from benchmark import scopes
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    before = get_global_mesh()
    try:
        init_fn, step_fn, place = _step(CFG.replace(
            remat="full", loss_chunks=2, attention_impl="flash_interpret"))
        params, opt_state = jax.eval_shape(init_fn, jax.random.key(0))
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in _batch().items()}
        text = step_fn.lower(params, opt_state, batch).compile().as_text()
    finally:
        set_global_mesh(before)
    paths = {scopes.scope_path(name) for name in
             scopes.op_names(text).values()}
    by = {"scopes": {p: 1.0 for p in paths}}
    for scope in ("stack", "block/attn", "block/attn/eva",
                  "block/attn/eva_pool", "block/mlp", "loss",
                  "forward_backward", "optimizer"):
        assert scopes.seconds_under(by, scope) > 0, (scope, sorted(paths))
    # the pooling is not counted as the attention, nor either as the MLP
    assert scopes.seconds_under(by, "block/attn/eva") \
        < scopes.seconds_under(by, "block/attn")
    assert not any("eva" in p and "block/mlp" in p for p in paths)


def test_benchmark_cell_rehearses_on_the_cpu_and_names_no_device_metric():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "evabyte-6.5b.train-eva32k", "--seed", str(2 ** 31 + 7),
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
    assert "heads_last={'head_loss': [" in done.stdout
    for line in done.stdout.splitlines():
        if line.startswith("[correct]"):
            assert line.endswith("ok=True"), line


def test_the_cell_is_in_the_benchmark_as_the_issue_names_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "evabyte-6.5b.train-eva32k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("evabyte-6.5b", "train-eva32k", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "evabyte-6.5b")
    assert entry["reduced"] == ["num_hidden_layers"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    s = arch.sizes_of(config)
    assert (s["E"], s["H"], s["D"], s["M"], s["window"], s["chunk"], s["J"],
            s["V"], s["L"]) == (4096, 32, 128, 11008, 2048, 16, 8, 320, 4)
    assert arch.parameters(s)["held"] == config["parameters"] == 821366784
    assert set(config["correct"]) == {
        "norm_grad_distance", "step_moments_distance",
        "step_update_mismatch", "head_loss_distance"}
    named = {m["name"] for m in bench["per_layer"]
             if cell["name"] in m.get("workloads", ())}
    assert {"eva_attn_roofline.eva32k", "eva_pool_roofline.eva32k",
            "eva_device_share.eva32k", "eva_remote_key_share.eva32k",
            "mfu_looped_pct.eva32k", "idle_share.eva32k"} <= named


CONTROL_CELL = (
    {"chips": 1},
    {**json.load(open(os.path.join(
        ROOT, "benchmark/configs/evabyte-6.5b.json"))),
     "hidden_size": 256, "intermediate_size": 704, "num_hidden_layers": 2,
     "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 64,
     "window_size": 128, "chunk_size": 16},
    {"seq_len": 512})
CONTROL_CELL[1]["train"] = {**CONTROL_CELL[1]["train"],
                            "tokens_per_chip": 512, "attention": "reference",
                            "loss_chunks": 0}


def test_int8_control_lies_above_the_limits_and_the_program_below():
    """At a size a test can hold: the reference computed in int8 is called
    wrong by the cell's limits, and the program is not (the chip's readings
    at the cell's sizes are in the configuration's ``correct_why``)."""
    from benchmark import control_eva
    limits = CONTROL_CELL[1]["correct"]
    seeds = [1, 2]
    program = dict(control_eva.program_numbers(*CONTROL_CELL, seeds))
    control = dict(control_eva.control_numbers(*CONTROL_CELL, seeds))
    for seed in seeds:
        p, c = program[seed], control[seed]
        print(seed, {k: (p[k], c[k]) for k in limits})
        for name in ("norm_grad_distance", "step_moments_distance"):
            assert c[name] > limits[name] > p[name], (seed, name)
            assert c[name] > 2 * p[name], (seed, name)
        assert c["step_update_mismatch"] > p["step_update_mismatch"]
        # A head's loss is a mean over 511 positions here and 32,760 in the
        # cell: the chip's limit is not this size's.
        assert p["head_loss_distance"] < 1e-3 > p["step_loss_distance"]


def test_a_head_shifted_by_one_position_is_called_wrong():
    """What ``head_loss_distance`` is for: a head that predicts a
    neighbour's byte reports that neighbour's loss."""
    from benchmark.kinds import train_eva
    limit = CONTROL_CELL[1]["correct"]["head_loss_distance"]
    w, batch = _weights(), _batch()
    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(lambda w: ref.loss_and_report(
            w, batch["tokens"], batch["loss_mask"], S))(w)
    want = train_eva.head_readings(want)
    _, sound = jax.jit(lambda w: evabyte.loss_and_report(w, batch, CFG))(w)
    assert train_eva.head_distance(train_eva.head_readings(sound),
                                   want)["head_loss_distance"] < limit
    shifted = {"head_loss": want["head_loss"][1:] + want["head_loss"][:1]}
    assert train_eva.head_distance(shifted, want)["head_loss_distance"] \
        > limit
    # and an update that is a whole step off is counted, a rounded one not
    opts = CONTROL_CELL[1]["train"]
    g = {"x": np.asarray([1.0, -2.0, 3.0, -4.0], np.float32)}
    start = {"x": jnp.zeros(4, jnp.bfloat16)}
    lr = opts["learning_rate"]
    sound = {"x": np.asarray([-lr, lr, -lr * 1.004, lr * 0.996], np.float32)}
    assert train_eva.update_mismatch(sound, g, start, opts) == 0
    wrong = {"x": np.asarray([lr, lr, -lr, 0.0], np.float32)}
    assert train_eva.update_mismatch(wrong, g, start, opts) == 0.5
