"""EVA (ops/eva.py) and the EvaByte stack (models/evabyte.py) against their
plain reference (benchmark/reference_evabyte.py), on the CPU at tiny sizes
and seeded weights: the chunks' summaries and the two-operand attention,
``jnp`` path and kernels in interpret mode, values and gradients; the two
limits that are plain causal attention; who sees what, by perturbation; the
tables the kernels walk; the eight-head loss; the model's loss and the
gradient of every leaf; what the train step reports and the trainer
records; the scopes the readers sum; the cell's rehearsal; the int8
control."""

from __future__ import annotations

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
from benchmark import roofline_eva  # noqa: E402
from benchmark.archs import evabyte as arch  # noqa: E402
from ray_tpu.models import _lm, evabyte, llama  # noqa: E402
from ray_tpu.ops import eva  # noqa: E402
from ray_tpu.ops.attention import (EMPTY, KIND,  # noqa: E402
                                   reference_attention)

S = {"V": 64, "E": 64, "L": 2, "H": 4, "Hkv": 4, "D": 16, "M": 96,
     "window": 64, "chunk": 8, "J": 3, "theta": 1e5, "eps": 1e-5}
CFG = evabyte.evabyte_tiny()
LEAVES = sorted("/".join(str(k.key) for k in path) for path, _ in
                jax.tree_util.tree_flatten_with_path(
                    evabyte.param_shapes(CFG), is_leaf=_lm.is_shape)[0])
#: (row, window, chunk): 4 windows of 64 in chunks of 8, 3 of 128 in 16s
GEOMETRIES = [(256, 64, 8), (384, 128, 16)]
IMPLS = ["reference", "flash_interpret"]


def _weights(seed=5):
    """float32 weights in the program's layout, the norms' offsets off 0 so
    that a missing ``1 +`` shows."""
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


def _qkv(seq, seed=0, B=1, H=2, D=16):
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k, v, g = (jax.random.normal(ks[i], (B, H, seq, D), jnp.float32)
                  for i in range(4))
    mu, phi = (jax.random.normal(ks[4 + i], (H, D), jnp.float32)
               for i in range(2))
    return q, k, v, g, mu, phi


def _bshd(a):
    """[B, H, S, D] (the program's) <-> [B, S, H, D] (the reference's)."""
    return jnp.swapaxes(a, 1, 2)


# ------------------------------------------------------------------- ops

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("seq,window,chunk", GEOMETRIES)
def test_summaries_match_the_reference(seq, window, chunk, impl):
    """Values and the gradients in k, v, mu and phi."""
    _, k, v, _, mu, phi = _qkv(seq)
    gk, gv = (jax.random.normal(jax.random.key(7 + i),
                                (1, 2, seq // chunk, 16)) for i in range(2))

    def loss(fn, lay):
        def f(k, v, mu, phi):
            ks, vs = fn(lay(k), lay(v), mu, phi, chunk)
            return jnp.sum(lay(ks) * gk) + jnp.sum(lay(vs) * gv), (lay(ks),
                                                                   lay(vs))
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)(
            k, v, mu, phi)

    with jax.default_matmul_precision("highest"):
        (_, got), grads = loss(lambda *a: eva.eva_summaries(*a, impl=impl),
                               lambda a: a)
        (_, want), want_grads = loss(ref.summaries, _bshd)
    for a, b in zip(got + grads, want + want_grads):
        assert float(jnp.linalg.norm(b)) > 0
        assert float(ref.relative_distance(a, b)) < 1e-5


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("seq,window,chunk", GEOMETRIES)
def test_attention_matches_the_reference(seq, window, chunk, impl):
    """Values and the gradients in q, k, v and both summaries."""
    q, k, v, g, mu, phi = _qkv(seq)
    ks, vs = eva.reference_summaries(k, v, mu, phi, chunk)

    def loss(fn, lay):
        def f(*a):
            out = lay(fn(*(lay(x) for x in a), window, chunk))
            return jnp.sum(out * g), out
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            q, k, v, ks, vs)

    with jax.default_matmul_precision("highest"):
        (_, got), grads = loss(
            lambda *a: eva.eva_attention(*a, impl=impl), lambda a: a)
        (_, want), want_grads = loss(ref.eva_attention, _bshd)
    for a, b in zip((got,) + grads, (want,) + want_grads):
        assert float(jnp.linalg.norm(b)) > 0
        assert float(ref.relative_distance(a, b)) < 1e-5


@pytest.mark.parametrize("blocks", [(32, 32, 8), (64, 16, 16), (16, 64, 32)])
def test_attention_kernels_at_other_blocks(blocks):
    """A q block smaller than the window, token and summary blocks of
    other sizes: the same values and gradients."""
    q, k, v, g, mu, phi = _qkv(256)
    ks, vs = eva.reference_summaries(k, v, mu, phi, 8)
    block_q, block_k, block_s = blocks

    def grads(**kw):
        return jax.grad(lambda *a: jnp.sum(
            eva.eva_attention(*a, 64, 8, **kw) * g),
            argnums=(0, 1, 2, 3, 4))(q, k, v, ks, vs)

    with jax.default_matmul_precision("highest"):
        got = grads(impl="flash_interpret", block_q=block_q, block_k=block_k,
                    block_s=block_s)
        want = grads(impl="reference")
    for a, b in zip(got, want):
        assert float(ref.relative_distance(a, b)) < 1e-5


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("limit", ["window_covers_the_row", "chunk_of_one"])
def test_the_two_limits_are_plain_causal_attention(limit, impl):
    """With ``window >= S`` no summary is visible; with ``chunk == 1`` a
    summary is its own key and value, and the earlier windows are seen
    whole."""
    q, k, v, g, mu, phi = _qkv(128)
    window, chunk = (256, 8) if limit == "window_covers_the_row" else (32, 1)
    ks, vs = eva.eva_summaries(k, v, mu, phi, chunk, impl=impl)
    if chunk == 1:
        np.testing.assert_allclose(ks, k, atol=1e-6)
        np.testing.assert_allclose(vs, v, atol=1e-6)

    def grads(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * g),
                                  argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got, got_grads = grads(lambda q, k, v: eva.eva_attention(
            q, k, v, *eva.eva_summaries(k, v, mu, phi, chunk, impl=impl),
            window, chunk, impl=impl))
        want, want_grads = grads(
            lambda q, k, v: reference_attention(q, k, v, causal=True))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert float(ref.relative_distance(a, b)) < 1e-5


@pytest.mark.parametrize("impl", IMPLS)
def test_who_sees_what_by_perturbation(impl):
    """A change at position p moves no output before p; a change in the
    query's own window reaches it only through the local set; the first
    window sees no summary."""
    seq, window, chunk, p = 256, 64, 8, 100            # p in window 1
    q, k, v, _, mu, phi = _qkv(seq)
    bump = lambda a: a.at[:, :, p].add(1.0)

    def out(k_att, v_att, k_pool, v_pool):
        ks, vs = eva.eva_summaries(k_pool, v_pool, mu, phi, chunk, impl=impl)
        return eva.eva_attention(q, k_att, v_att, ks, vs, window, chunk,
                                 impl=impl)

    base = out(k, v, k, v)
    moved = lambda o: np.abs(np.asarray(o - base)).max(axis=(0, 1, 3)) > 1e-6
    # everywhere: nothing before p, p itself, and every later window
    everywhere = moved(out(bump(k), bump(v), bump(k), bump(v)))
    assert not everywhere[:p].any() and everywhere[p:].all()
    # through the summaries alone: not the query's own window (64..127),
    # every window after it
    remote = moved(out(k, v, bump(k), bump(v)))
    assert not remote[:128].any() and remote[128:].all()
    # through the tokens alone: the rest of p's window and nothing else
    local = moved(out(bump(k), bump(v), k, v))
    assert local[p:128].all() and not local[:p].any() \
        and not local[128:].any()
    # the first window sees no summary at all
    ks, vs = eva.eva_summaries(k, v, mu, phi, chunk, impl=impl)
    other = moved(eva.eva_attention(q, k, v, ks + 1.0, vs - 1.0, window,
                                    chunk, impl=impl))
    assert not other[:window].any() and other[window:].all()


def test_the_tables_at_the_cells_shape():
    """32,768 / 2,048 / 16 at 512 x 512: a head's forward walk takes 144
    summary steps and 160 token steps, dk/dv the 160, the summaries'
    gradients 120 and one empty step for the last window's; the pairs are
    the roofline's."""
    sched, taken = eva.eva_schedule(32768, 2048, 16, 512, 512, 512, "q")
    assert taken[1].sum() == 144 and (taken[1] == 0).sum() == 160
    assert sched.shape[1] == 304 and (sched[KIND] != EMPTY).all()
    # window 0's ten token steps; then a q block's summaries before its
    # tokens (window 1: one block of summaries, then 1, 2, .. token blocks)
    assert not taken[1][:10].any()
    assert taken[1][10:15].tolist() == [1, 0, 1, 0, 0]
    assert eva.eva_schedule(32768, 2048, 16, 512, 512, 512, "k")[0].shape[1] \
        == 160
    dsum, _ = eva.eva_schedule(32768, 2048, 16, 2048, 512, 128, "s")
    assert dsum.shape[1] == 121 and (dsum[KIND] == EMPTY).sum() == 1
    local, remote = roofline_eva.visible_pairs(32768, 2048, 16)
    assert (local, remote) == (32768 * 2049 / 2, 2048 * 2048 / 16 * 120)
    assert round(local / 1e6, 2) == 33.57 and round(remote / 1e6, 2) == 31.46
    assert roofline_eva.visible_pairs(2048, 2048, 16)[1] == 0
    packed = eva._pack(sched, taken)
    assert packed.dtype == np.int32 and packed.size == 608


@pytest.mark.parametrize("what", ["a ragged row", "a ragged window",
                                  "grouped heads", "a mesh"])
def test_what_eva_does_not_do_is_refused_by_name(what):
    q, k, v, _, mu, phi = _qkv(128)
    ks, vs = eva.reference_summaries(k, v, mu, phi, 8)
    if what == "a ragged row":
        with pytest.raises(ValueError, match="whole windows"):
            eva.eva_attention(q, k, v, ks, vs, 48, 8, impl="flash_interpret")
    elif what == "a ragged window":
        with pytest.raises(ValueError, match="whole chunks"):
            eva.eva_attention(q, k, v, ks, vs, 64, 24, impl="reference")
    elif what == "grouped heads":
        with pytest.raises(ValueError, match="a key head a query head"):
            eva.eva_attention(q, k[:, :1], v[:, :1], ks, vs, 64, 8)
    else:
        class Mesh:
            size = 4
        with pytest.raises(NotImplementedError, match="mesh"):
            eva.eva_attention(q, k, v, ks, vs, 64, 8, mesh=Mesh())


@pytest.mark.parametrize("S_,H", [(32768, 32), (4096, 16)])
def test_rotary_and_flash_tiles_at_the_cells_row(S_, H):
    """``rotate_heads`` and ``_tiles`` meet B = 1, S = 32,768, H = 32 for
    the first time: a step's tile is what the other cells' is."""
    from ray_tpu.ops import rope
    from ray_tpu.ops.attention import _tiles
    assert rope._tile(S_, H) == (512, 8)
    for kind in ("fwd", "dq", "dkv"):
        t = _tiles(kind, 2048, 2048, 128, 1)
        assert (t.block_q, t.block_k, t.heads) == (512, 512, 1)


# ----------------------------------------------------------------- model

@pytest.fixture(scope="module")
def both():
    """(program, reference): each (loss, report, gradient of every leaf)."""
    w, batch = _weights(), _batch()
    (loss, report), grads = jax.value_and_grad(
        evabyte.loss_and_report, has_aux=True)(w, batch, CFG)
    with jax.default_matmul_precision("highest"):
        (want, want_report), want_grads = jax.value_and_grad(
            lambda w: ref.loss_and_report(w, batch["tokens"],
                                          batch["loss_mask"], S),
            has_aux=True)(w)
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
        whole = ref.loss_and_report(w, batch["tokens"], batch["loss_mask"], S)
        monkeypatch.setattr(ref, "MLP_ROWS", 64)
        sliced = ref.loss_and_report(w, batch["tokens"], batch["loss_mask"], S)
    assert float(sliced[0]) == pytest.approx(float(whole[0]), rel=1e-6)


def test_forward_is_the_heads_logits():
    w, batch = _weights(), _batch()
    logits = evabyte.forward(w, batch["tokens"], CFG)
    assert logits.shape == (2, 256, S["J"], S["V"])
    assert logits.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = ref.logits(w, batch["tokens"], S)
    np.testing.assert_allclose(logits, want, atol=2e-4)


@pytest.mark.parametrize("masked", [False, True])
def test_one_head_is_llamas_loss(masked):
    """With ``num_pred_heads`` 1 the objective is ``next_token_loss``."""
    one = CFG.replace(pred_heads=1)
    w = evabyte.init_params(one, jax.random.key(3))
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
    from tests.test_ops import _norm_paths
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


def test_kernels_count_their_geometry():
    from ray_tpu.util import metrics as metrics_mod
    metrics_mod._reset_for_tests()
    q, k, v, g, mu, phi = _qkv(256)
    jax.grad(lambda k: jnp.sum(eva.eva_attention(
        q, k, v, *eva.eva_summaries(k, v, mu, phi, 8, impl="flash_interpret"),
        64, 8, impl="flash_interpret") * g))(k)
    text = metrics_mod.prometheus_text()
    for kernel in ("eva_fwd_w64c8", "eva_dq_w64c8", "eva_dkv_w64c8",
                   "eva_dsum_w64c8", "eva_pool_fwd_c8", "eva_pool_bwd_c8"):
        assert f'kernel="{kernel}"' in text, kernel
    # 4 windows of 8 summaries in blocks of 32: a q block of window w > 0
    # takes one summary step; every q block its one token step
    line = next(l for l in text.splitlines() if 'kernel="eva_fwd_w64c8"' in l)
    assert 'summary_steps="3"' in line and 'token_steps="4"' in line, line
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
        _, want = ref.loss_and_report(w, batch["tokens"],
                                      batch["loss_mask"], S)
    want = train_eva.head_readings(want)
    _, sound = evabyte.loss_and_report(w, batch, CFG)
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
