"""The afmoe model (Trinity's block) against its plain reference, and the
pieces it brought: the sigmoid router without drops, the share of an
expert-parallel layer, the selection bias, windowed flash attention, the
train step's state, and a CPU rehearsal of its benchmark cell."""

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


def _setup(seed=0, **kw):
    cfg = afmoe.afmoe_tiny(**kw)
    params = afmoe.init_params(cfg, jax.random.key(seed))
    # Norm weights away from one, and a selection bias large enough to
    # change which experts are chosen: choosing by s + b and weighting by s
    # are then told apart.
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1 + 0.2 * jax.random.normal(
            next(keys), a.shape)) if "norm" in str(path[-1]) else a, params)
    bias = 0.3 * jax.random.normal(
        next(keys), (cfg.expert_layers, cfg.num_experts))
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
    loss, grads = jax.value_and_grad(afmoe.loss_fn)(
        params, batch, cfg, {"bias": bias})
    want, want_grads = jax.value_and_grad(_ref_loss)(params, bias, batch, s)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(g - w)) <= 2e-4 * float(
            jnp.linalg.norm(w)) + 1e-7, path
    # The bias matters: without it other experts are chosen.
    assert abs(float(afmoe.loss_fn(params, batch, cfg)) - float(loss)) > 1e-4
    # The reference's own walk gives the same norm gradients and the
    # program's choices.
    _, norm_grads, top = ref.loss_norm_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert float(ref.relative_distance(
        norm_grads, arch.norms_of(want_grads))) < 1e-5
    _, loads = afmoe.loss_and_loads(params, {"bias": bias}, batch, cfg)
    assert float(ref.routing_mismatch_share(loads["top"], top,
                                            cfg.num_experts)) == 0.0
    assert int(loads["dropped"].sum()) == 0


def test_remat_rows_at_a_time_and_loss_chunks_do_not_change_the_loss():
    cfg, params, bias, batch = _setup()
    run = jax.value_and_grad(lambda p, c: afmoe.loss_and_loads(
        p, {"bias": bias}, batch, c), has_aux=True)
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
    layer = jax.tree.map(lambda a: a[0],
                         afmoe.init_params(cfg, keys[0])["moe"])
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


def _experts(T=64, E=32, M=16, X=16, Xh=4, k=4, seed=1):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (T, E)),
            jax.random.normal(ks[1], (E, X)) * 0.3,
            jax.random.normal(ks[2], (Xh, E, M)) * 0.2,
            jax.random.normal(ks[3], (Xh, E, M)) * 0.2,
            jax.random.normal(ks[4], (Xh, M, E)) * 0.2)


@pytest.mark.parametrize("push,held,impl", [
    (10.0, 256, None), (-10.0, 0, None), (0.0, None, None),
    (10.0, 256, "gmm_interpret"), (0.0, None, "gmm_interpret")],
    ids=["every-token-held", "none-held", "mixed", "every-held-pallas",
         "mixed-pallas"])
def test_dropless_dispatch(push, held, impl):
    """All T*k assignments to the held experts (the slices path: four times
    the usual buffer), none, and the usual share: nothing dropped, and the
    result is the masked dense sum."""
    xt, rw, wg, wu, wd = _experts()
    bias = jnp.where(jnp.arange(16) < 4, push, 0.0)
    routing = moe.sigmoid_routing(xt, rw, bias, 4, 2.5)
    out, (n, dropped) = moe.dropless_experts(xt, routing, wg, wu, wd, 0,
                                             impl)
    assert int(dropped) == 0 and (held is None or int(n) == held)
    want = ref.held_experts(xt, routing.expert_index, routing.weights, wg,
                            wu, wd, 0)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert int(routing.counts.sum()) == 64 * 4


def _poisoned_grouped_matmul(lhs, rhs, group_sizes, impl=None,
                             rows_a_group=None, tiling=None):
    """``lax.ragged_dot`` that, as the Pallas grouped matmul does, leaves the
    rows past the last group unwritten, forward and backward: NaN here."""
    def dead_rows(lhs, sizes):
        return (jnp.arange(lhs.shape[0]) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def mm(lhs, rhs, sizes):
        return jnp.where(dead_rows(lhs, sizes), jnp.nan,
                         jax.lax.ragged_dot(lhs, rhs, sizes))

    def fwd(lhs, rhs, sizes):
        return mm(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        dead = dead_rows(lhs, sizes)
        _, vjp = jax.vjp(lambda l, r: jax.lax.ragged_dot(
            jnp.where(dead, 0, l), r, sizes), lhs, rhs)
        dl, dr = vjp(jnp.where(dead, 0, g))
        return jnp.where(dead, jnp.nan, dl), dr, None

    mm.defvjp(fwd, bwd)
    return mm(lhs, rhs, group_sizes)


def test_rows_no_group_holds_reach_neither_result_nor_gradient(monkeypatch):
    """The Pallas grouped matmul leaves the rows past the last group
    unwritten, forward and backward.  With those rows poisoned, the layer's
    result and every gradient stay those of the clean products."""
    xt, rw, wg, wu, wd = _experts()

    def layer(xt, rw, wg, wu, wd):
        routing = moe.sigmoid_routing(xt, rw, jnp.zeros((16,)), 4, 2.5)
        return jnp.sum(jnp.sin(moe.dropless_experts(
            xt, routing, wg, wu, wd, 0)[0]))

    want = jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4))(
        xt, rw, wg, wu, wd)
    monkeypatch.setattr(moe, "grouped_matmul", _poisoned_grouped_matmul)
    got = jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4))(
        xt, rw, wg, wu, wd)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, atol=1e-5)


def _scattered_held_rows(xt, top, w, w_gate, w_up, w_down, held_start, rows,
                         impl, activation="silu", experts=None,
                         act_weights=None):
    """``ops/moe._held_rows`` as it was before the pair (PR 29): a stable
    sort of the assignments by held expert, ``xt[tok]`` into the buffer and
    ``.at[tok].add`` out of it."""
    T, k = top.shape
    Xh = w_gate.shape[0]
    local = top - held_start
    local = jnp.where((local >= 0) & (local < Xh), local, Xh).reshape(T * k)
    order = jnp.argsort(local, stable=True)[:rows]
    sizes = jnp.sum(local[:, None] == jnp.arange(Xh)[None, :], axis=0,
                    dtype=jnp.int32)
    used = jnp.minimum(jnp.sum(sizes), rows)
    live = (jnp.arange(rows) < used)[:, None]
    tok = order // k
    x_rows = jnp.where(live, xt[tok], 0)
    mm = lambda a, b: moe.grouped_matmul(a, b, sizes, impl)
    y_rows = mm(jax.nn.silu(mm(x_rows, w_gate)) * mm(x_rows, w_up), w_down)
    y_rows = jnp.where(live, y_rows, 0) * w.reshape(T * k)[order][:, None]
    return jnp.zeros(xt.shape, y_rows.dtype).at[tok].add(y_rows), used


@pytest.mark.parametrize("poisoned", [False, True], ids=["clean", "poisoned"])
@pytest.mark.parametrize("T,E,push", [
    (64, 32, 0.0), (62, 32, 0.0), (64, 32, 10.0), (256, 128, 0.0),
    (256, 128, 10.0)],
    ids=["at-once", "odd-tokens", "sliced", "at-once-kernel",
         "sliced-kernel"])
def test_the_pair_is_the_gather_and_scatter_add_it_replaced(
        T, E, push, poisoned, monkeypatch):
    """``rows_of_tokens`` / ``tokens_from_rows`` against ``xt[tok]`` and
    ``.at[tok].add`` in float32: the layer's result and all five gradients,
    with tokens that have 0, 1 and 8 held assignments, in the buffer at
    once, in its slices and at a token count the tiers do not divide; and
    with the sums on the kernel path (interpreted), under the ``cond``, the
    ``map`` over slices and their ``checkpoint``."""
    xt, rw, wg, wu, wd = _experts(T=T, E=E, X=16, Xh=8, k=8)
    if E == 128:
        monkeypatch.setattr(moe, "_INTERPRET_ROWS", True)
        assert moe._rows_tile(T // 4, T * 2, E, 8, xt.dtype) == 64
    # The first feature decides how many of a token's 8 choices are held:
    # all of them, none, or (weakly pushed) a few; few enough in all for
    # the buffer to take them at once.
    lean = jnp.asarray(np.resize([6.0, -6.0, -6.0, -0.9, -6.0, -1.5, -6.0], T))
    xt = xt.at[:, 0].set(lean)
    rw = rw * (32 / E) ** 0.5           # the other features' say, as at 32
    rw = rw.at[0].set(jnp.where(jnp.arange(16) < 8, 1.0, -1.0))
    bias = jnp.where(jnp.arange(16) < 8, push, 0.0)

    def layer(xt, rw, wg, wu, wd):
        routing = moe.sigmoid_routing(xt, rw, bias, 8, 2.5)
        out, stats = moe.dropless_experts(xt, routing, wg, wu, wd, 0)
        sliced = stats[0] > moe.buffer_rows(T, 8)
        return jnp.sum(jnp.sin(out)), (out, routing, (*stats, sliced))

    run = jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4), has_aux=True)
    with monkeypatch.context() as m:
        m.setattr(moe, "_held_rows", _scattered_held_rows)
        (want, (want_out, routing, _)), want_grads = run(xt, rw, wg, wu, wd)
    if poisoned:
        monkeypatch.setattr(moe, "grouped_matmul", _poisoned_grouped_matmul)
    (got, (out, _, (held, dropped, sliced))), grads = run(xt, rw, wg, wu, wd)
    a_token = np.asarray((routing.expert_index < 8).sum(-1))
    if push:
        assert set(a_token) == {8} and int(sliced) == 1
    else:
        assert {0, 1, 8} <= set(a_token) and int(sliced) == 0
    assert int(dropped) == 0 and int(held) == a_token.sum()
    # float32 sums over E features and, for the router's gradient, over T
    # tokens, in another order than the scatter-add's (at 256 x 128 the
    # jnp form is 1.8e-4 from it too)
    atol, rtol = (2e-5, 1e-5) if E == 32 else (8e-5, 5e-4)
    np.testing.assert_allclose(out, want_out, atol=atol, rtol=rtol)
    np.testing.assert_allclose(got, want, rtol=rtol)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, atol=atol, rtol=rtol)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("T", [64, 62], ids=["at-once-or-sliced",
                                             "odd-tokens"])
def test_no_scatter_of_rows_or_counts_in_the_expert_layer(T):
    """Forward and backward of the routed experts move rows by gathers and
    dense passes alone: no scatter or scatter-add lands in a ``[*, E]`` row
    array or in the ``[X]`` counts."""
    xt, rw, wg, wu, wd = _experts(T=T)
    E, X = xt.shape[1], rw.shape[1]

    def layer(xt, rw, wg, wu, wd):
        routing = moe.sigmoid_routing(xt, rw, jnp.zeros((X,)), 4, 2.5)
        out, _ = moe.dropless_experts(xt, routing, wg, wu, wd, 0,
                                      "ragged_dot")
        return jnp.sum(jnp.sin(out)) + 1e-3 * jnp.sum(routing.counts)

    jaxpr = jax.make_jaxpr(jax.grad(layer, argnums=(0, 1, 2, 3, 4)))(
        xt, rw, wg, wu, wd)
    seen = [e for e in _equations(jaxpr.jaxpr)]
    assert any(e.primitive.name == "gather" for e in seen)
    for eqn in seen:
        if eqn.primitive.name.startswith("scatter"):
            shape = eqn.invars[0].aval.shape
            assert shape != (X,) and not (len(shape) == 2 and shape[1] == E), \
                (eqn.primitive.name, shape)


def _counted(name, keys):
    """A counter of this process as {(the tags ``keys``' values): count}."""
    from ray_tpu.util import metrics
    _by_name, acc = metrics._aggregate_snapshots()
    return {tuple(dict(tags)[k] for k in keys): value
            for tags, value in acc.get(name, {}).values()}


def _rows_counted():
    return _counted("ray_tpu_moe_rows_path_total",
                    ("path", "op", "tokens", "slots", "lanes"))


def _rows_case(T, k, E, Xh, X, load, dtype, seed=0):
    """A call's places, a buffer whose rows no group holds are NaN, weights
    and a cotangent.  ``load``: "none" (no token holds a row), "every"
    (every slot of every token is held: the slices' branch, R = T * k),
    else the probability scale of the held experts (1.0: uniform)."""
    rng = np.random.default_rng(seed)
    if load == "none":
        local = np.full((T, k), Xh)
    else:
        p = np.ones(X)
        p[:Xh] *= 1.0 if load == "every" else load
        g = rng.gumbel(size=(T, X)) + np.log(p)
        if load == "every":
            g[:, Xh:] = -np.inf
        top = np.argsort(-g, axis=1)[:, :k]
        local = np.where(top < Xh, top, Xh)
    R = T * k if load == "every" else moe.buffer_rows(T, k)
    at, used = moe._places(jnp.asarray(local, jnp.int32), Xh, R)
    y = jnp.asarray(rng.normal(size=(R, E)), dtype)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(T, E)), dtype)
    return at, int(used), jnp.where(at.live, y, jnp.nan), w, g


# (T, k, E, Xh, X, load, dtype): the cells' k and lanes at a CPU test's
# token counts (E over 128, the lanes _take moves its scalars by); a buffer that overflows (held > R: the last rows dropped,
# as _held_rows says, and the slices take over in dropless_experts)
_ROWS_CASES = {
    "none-held": (128, 4, 256, 4, 16, "none", jnp.bfloat16),
    "every-slot-held": (64, 4, 256, 4, 4, "every", jnp.bfloat16),
    "overflows": (128, 8, 256, 8, 16, 3.0, jnp.bfloat16),
    "k6-2688-lanes": (128, 6, 2688, 4, 32, 1.0, jnp.bfloat16),
    "k4-3584-lanes": (64, 4, 3584, 2, 16, 1.0, jnp.bfloat16),
    "k8-two-tiles": (1024, 8, 256, 16, 128, 1.0, jnp.bfloat16),
    "float32-rows": (128, 8, 256, 4, 16, 1.5, jnp.float32),
}


@pytest.mark.parametrize("case", list(_ROWS_CASES))
def test_rows_kernel_is_the_jnp_form(case, monkeypatch):
    """The pair on the kernel path (interpreted here) against the ``jnp``
    form: forward, both transposes and the weights' gradient, bit for bit
    where a token holds at most one row and within one rounding of the
    output's dtype elsewhere (on the chip two rows are bit for bit too,
    PERF.md PR 45; here XLA's CPU code fuses the product into the sum, in
    the interpreted kernel and not in the ``jnp`` form, so a second row's
    product is not rounded); rows no group holds are NaN in the buffer and
    reach nothing; the kernel path's trace holds no array of T * k * E
    elements and no scatter, and the counter says which path ran."""
    T, k, E, Xh, X, load, dtype = _ROWS_CASES[case]
    at, used, y, w, g = _rows_case(T, k, E, Xh, X, load, dtype)
    R = y.shape[0]
    if case == "overflows":
        assert int(jnp.sum(at.sizes)) > R == used
    x = g                                   # any [T, E] stream

    def pair(y, w, x):
        out = moe.tokens_from_rows(y, w, at)
        rows = moe.rows_of_tokens(x, at)
        return out, rows

    def run():
        (out, rows), vjp = jax.vjp(pair, jnp.where(at.live, y, 0), w, x)
        clean = (out, rows) + vjp((g, jnp.where(at.live, y, 0)))
        (out, rows), vjp = jax.vjp(pair, y, w, x)
        # the cotangent of rows_of_tokens is NaN on rows no group holds too
        return clean, (out, rows) + vjp((g, y))

    want, want_poisoned = run()
    before = _rows_counted()
    with monkeypatch.context() as m:
        m.setattr(moe, "_INTERPRET_ROWS", True)
        assert moe._rows_tile(T, R, E, Xh, dtype) in (64, 128, 256, 512)
        got, got_poisoned = run()
        jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(pair, *a)[1]((g, y)))(
            y, w, x)
    gained = {key: v - before.get(key, 0) for key, v in
              _rows_counted().items() if v != before.get(key, 0)}
    assert set(gained) == {
        ("kernel", op, str(T), str(k), str(E))
        for op in ("tokens_from_rows", "rows_of_tokens_bwd")}
    a_token = np.asarray(jnp.sum(at.row < R, axis=1))
    few = (a_token <= 1)[:, None]
    # one rounding of the output's dtype, of the largest the sum could be
    # (a sum of three or more may cancel, in another order)
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22
    bound = {"out": moe._sum_rows_xla(jnp.abs(jnp.where(at.live, y, 0)
                                              ).astype(jnp.float32), w, at),
             "d_x": moe._sum_rows_xla(jnp.abs(jnp.where(at.live, y, 0)
                                              ).astype(jnp.float32), None,
                                      at)}
    names = ("out", "rows", "d_y", "d_w", "d_x")
    for name, a, b, c, d in zip(names, got, want, got_poisoned,
                                want_poisoned):
        a, b, c, d = (np.asarray(v, np.float32) for v in (a, b, c, d))
        live = np.asarray(at.live) if a.shape[0] == R else True
        assert np.isfinite(np.where(live, c, 0)).all(), name
        np.testing.assert_array_equal(np.where(live, c, 0),
                                      np.where(live, a, 0), err_msg=name)
        np.testing.assert_array_equal(np.where(live, d, 0),
                                      np.where(live, b, 0), err_msg=name)
        if name in ("out", "d_x"):
            np.testing.assert_array_equal(np.where(few, a, 0),
                                          np.where(few, b, 0), err_msg=name)
            assert (np.abs(a - b) <= step * np.asarray(bound[name])).all(), \
                name
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
    for eqn in _equations(jaxpr.jaxpr):
        assert not eqn.primitive.name.startswith("scatter"), eqn
        for v in eqn.outvars:       # (the slices' buffer IS T * k rows)
            assert R == T * k or np.prod(
                v.aval.shape, dtype=np.int64) < T * k * E, eqn


@pytest.mark.parametrize("T,E,dtype,why", [
    (128, 96, jnp.bfloat16, "lanes"), (96, 128, jnp.bfloat16, "tokens"),
    (128, 128, jnp.float16, "dtype"), (128, 128, jnp.bfloat16, "off-chip")])
def test_rows_path_is_xla_where_the_kernel_does_not_take_the_call(
        T, E, dtype, why, monkeypatch):
    """Lanes not in whole tiles, a token count the granule does not divide,
    a dtype the kernel was not written for, or no TPU: the ``jnp`` form,
    and the counter says so."""
    monkeypatch.setattr(moe, "_INTERPRET_ROWS", why != "off-chip")
    at, used, y, w, g = _rows_case(T, 4, E, 4, 16, 1.0, dtype)
    before = _rows_counted()
    out = moe.tokens_from_rows(y, w, at)
    key = ("xla", "tokens_from_rows", str(T), "4", str(E))
    assert _rows_counted().get(key, 0) == before.get(key, 0) + 1
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(moe._sum_rows_xla(y, w, at), np.float32))
    from ray_tpu.util import telemetry
    assert telemetry.CATALOG["ray_tpu_moe_rows_path_total"]["tag_keys"] == (
        "path", "op", "tokens", "slots", "lanes")


# (rows a group, buffer rows, hidden, expert width) of a layer call in the
# three sparse cells, at PR 29's whole-batch shape and at a CPU test's.
_CALLS = {"nemotron": (384, 12288, 2688, 1856),
          "trinity": (512, 16384, 2048, 1024),
          "xing4": (512, 8192, 3584, 1024),
          "whole-batch": (2048, 32768, 2048, 1024),
          "tiny": (16, 64, 32, 16),
          "odd-rows": (384, 12288 + 64, 2688, 1856)}


def _kernel_sizes(kind, product, E, M):
    """(k, n) as the KERNEL of ``kind`` sees the product ``up`` ([E, M]
    weights) or ``down`` ([M, E]): the rows' gradient contracts the
    forward's n."""
    k, n = (E, M) if product == "up" else (M, E)
    return (n, k) if kind == "dlhs" else (k, n)


@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("kind", moe.GMM_KINDS)
@pytest.mark.parametrize("call", list(_CALLS))
def test_gmm_tiles_fit_the_operand_the_lanes_and_the_default_vmem(
        call, kind, product):
    """Whatever the shapes: no tile larger than its operand, ``tm`` divides
    the buffer's rows, every tile a multiple of 128 where the operand
    allows one, and the double buffers with the float32 accumulator inside
    the budget under Mosaic's default 16 MiB of scoped VMEM."""
    rows_a_group, R, E, M = _CALLS[call]
    k, n = _kernel_sizes(kind, product, E, M)
    tm, tk, tn = moe._tiles_of(kind, R, k, n, rows_a_group, None)
    assert tm <= R and tk <= k and tn <= n and R % tm == 0
    assert tk == k or tk % 128 == 0
    assert tn == n or tn % 128 == 0
    assert tm % 128 == 0 or R % 128
    assert moe._gmm_vmem_bytes(kind, tm, tk, tn) <= moe._VMEM_BUDGET \
        < 16 * 2 ** 20


@pytest.mark.parametrize("call,kind,product,tiles", [
    # gmm: a low tile, the contraction whole, n covered with the least
    # padding that fits; tgmm: k and n covered up to 1,024
    ("nemotron", "fwd", "up", (256, 2688, 640)),
    ("nemotron", "fwd", "down", (256, 1856, 896)),
    ("nemotron", "dlhs", "up", (256, 1856, 896)),
    ("nemotron", "dlhs", "down", (256, 2688, 640)),
    ("nemotron", "tgmm", "up", (256, 896, 640)),
    ("nemotron", "tgmm", "down", (256, 640, 896)),
    ("trinity", "fwd", "up", (256, 2048, 1024)),
    ("trinity", "fwd", "down", (256, 1024, 1024)),
    ("trinity", "dlhs", "up", (256, 1024, 1024)),
    ("trinity", "tgmm", "up", (256, 1024, 1024)),
    ("xing4", "fwd", "up", (256, 3584, 512)),
    ("xing4", "fwd", "down", (256, 1024, 1792)),
    ("xing4", "dlhs", "down", (256, 3584, 512)),
    ("xing4", "tgmm", "up", (256, 896, 1024)),
    ("xing4", "tgmm", "down", (256, 1024, 896)),
    # 2,048 rows a group: gmm's pick read 6-16 % faster than (512, 1024,
    # 1024) at PR 29's shape, tgmm's 2 % (PERF.md, PR 44)
    ("whole-batch", "fwd", "up", (256, 2048, 1024)),
    ("whole-batch", "tgmm", "up", (256, 1024, 1024)),
    ("tiny", "fwd", "up", (64, 32, 16)),
    ("tiny", "tgmm", "down", (64, 16, 32)),
    # tm has to divide the buffer's rows: 12,352 = 64 * 193
    ("odd-rows", "fwd", "up", (64, 2688, 640))])
def test_gmm_tiles_at_the_cells_shapes(call, kind, product, tiles):
    rows_a_group, R, E, M = _CALLS[call]
    k, n = _kernel_sizes(kind, product, E, M)
    assert moe._tiles_of(kind, R, k, n, rows_a_group, None) == tiles


@pytest.mark.parametrize("kind,rows_a_group,k,tiles", [
    # tgmm takes the tall tile again where groups are many tiles tall
    ("tgmm", 4095, 2048, (256, 1024, 1024)),
    ("tgmm", 4096, 2048, (512, 1024, 1024)),
    # gmm keeps the contraction whole at any height ...
    ("fwd", 8192, 2048, (256, 2048, 1024)),
    # ... and a contraction too long for the budget keeps today's tiles
    ("fwd", 384, 8192, (512, 1024, 1024)),
    ("dlhs", 384, 8192, (512, 1024, 1024))])
def test_gmm_tiles_where_the_rule_turns(kind, rows_a_group, k, tiles):
    assert moe._gmm_tiles(kind, rows_a_group, k, 1024) == tiles


def _ragged(sizes, R=512, K=256, N=384, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    sizes = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(R) < jnp.sum(sizes))[:, None]
    return (jax.random.normal(ks[0], (R, K)),
            jax.random.normal(ks[1], (len(sizes), K, N)) * 0.1,
            jax.random.normal(ks[2], (R, N)), sizes, live)


def _product_and_gradients(lhs, rhs, weigh, sizes, live, impl, **kw):
    """(result, d lhs, d rhs) of a grouped product whose rows past the last
    group are masked on both sides, as the layer masks them."""
    def f(lhs, rhs):
        out = moe.grouped_matmul(jnp.where(live, lhs, 0), rhs, sizes, impl,
                                 **kw)
        out = jnp.where(live, out, 0)
        return jnp.sum(out * weigh), out
    (_, out), (d_lhs, d_rhs) = jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True)(lhs, rhs)
    return out, d_lhs, d_rhs


# tiles of 128 rows over 512: an empty group, a group inside one tile, a
# group over three tiles, groups that start mid-tile, rows past the last.
_RAGGED = {"empty-inside-over-three": [100, 0, 20, 300, 30],
           "every-row-used": [128, 256, 1, 127],
           "first-and-last-empty": [0, 200, 130, 0],
           "one-group-holds-all": [0, 0, 470, 0, 0],
           "nothing-held": [0, 0, 0]}


@pytest.mark.parametrize("tiling", [(128, 128, 128), (256, 256, 128), None],
                         ids=["t128", "t256", "picked"])
@pytest.mark.parametrize("sizes", list(_RAGGED))
def test_grouped_matmul_under_its_own_vjp_matches_ragged_dot(sizes, tiling):
    """Upstream's two kernels (interpreted) under ``ops/moe.py``'s own
    ``custom_vjp``: result, rows' gradient and weights' gradient against
    ``lax.ragged_dot``'s, for every raggedness a call can meet; the rows
    past the last group reach neither (they are masked on both sides and
    everything stays finite)."""
    lhs, rhs, weigh, n, live = _ragged(_RAGGED[sizes])
    want = _product_and_gradients(lhs, rhs, weigh, n, live, "ragged_dot")
    got = _product_and_gradients(lhs, rhs, weigh, n, live, "gmm_interpret",
                                 tiling=tiling)
    for a, b, name in zip(got, want, ("out", "d_lhs", "d_rhs")):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(a, b, atol=2e-4, err_msg=name)


def _gmm_tiles_counted():
    return _counted("ray_tpu_gmm_tile_geometry_total",
                    ("kind", "tm", "tk", "tn", "rows_a_group"))


@pytest.mark.parametrize("tiling,tiles", [
    ((256, 128, 256), {kind: ("256", "128", "256")
                       for kind in moe.GMM_KINDS}),
    (None, {"fwd": ("256", "256", "384"), "dlhs": ("256", "384", "256"),
            "tgmm": ("256", "256", "384")})],
    ids=["explicit-wins", "picked"])
def test_gmm_tile_counter_names_what_each_kernel_took(tiling, tiles):
    """One traced call, forward and backward: the counter gains one count
    a kernel, tagged with the tiles it took (an explicit ``tiling`` for all
    three, else ``_gmm_tiles``' pick for each) and the rows a group was
    expected to hold."""
    lhs, rhs, weigh, n, live = _ragged([100, 0, 20, 300, 30], seed=3)
    before = _gmm_tiles_counted()
    _product_and_gradients(lhs, rhs, weigh, n, live, "gmm_interpret",
                           tiling=tiling, rows_a_group=77.5)
    after = _gmm_tiles_counted()
    gained = {k: v - before.get(k, 0) for k, v in after.items()
              if v != before.get(k, 0)}
    assert gained == {(kind, *t, "77"): 1 for kind, t in tiles.items()}
    from ray_tpu.util import telemetry
    assert telemetry.CATALOG["ray_tpu_gmm_tile_geometry_total"][
        "tag_keys"] == ("kind", "tm", "tk", "tn", "rows_a_group")


def test_a_layer_call_tells_the_products_the_rows_an_expert_expects(
        monkeypatch):
    """``dropless_experts`` hands every grouped product T * k / X, the rows
    a held expert expects of the call (static), whether the buffer is
    taken at once or in slices of the tokens."""
    xt, rw, wg, wu, wd = _experts()                 # T 64, k 4, X 16
    seen = []

    def spy(lhs, rhs, group_sizes, impl=None, rows_a_group=None,
            tiling=None):
        seen.append((lhs.shape[0], rows_a_group))
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)

    monkeypatch.setattr(moe, "grouped_matmul", spy)
    routing = moe.sigmoid_routing(xt, rw, jnp.zeros((16,)), 4, 2.5)
    moe.dropless_experts(xt, routing, wg, wu, wd, 0)
    # at once: 64 rows, 64 * 4 / 16 a group; a slice: 16 tokens, 4 a group
    assert sorted(set(seen)) == [(64, 4.0), (64, 16.0)]


def test_sigmoid_routing_chooses_by_score_plus_bias_and_weighs_by_score():
    xt, rw, *_ = _experts()
    bias = jnp.zeros((16,)).at[5].set(10.0)
    r = moe.sigmoid_routing(xt, rw, bias, 4, 2.826)
    assert bool(jnp.all(jnp.any(r.expert_index == 5, axis=-1)))
    scores = jax.nn.sigmoid(xt @ rw)
    w = jnp.take_along_axis(scores, r.expert_index, -1)
    np.testing.assert_allclose(
        r.weights, w / w.sum(-1, keepdims=True) * 2.826, rtol=1e-6)
    np.testing.assert_allclose(r.weights.sum(-1), 2.826, rtol=1e-5)


def test_selection_bias_update_rule():
    counts = jnp.array([[10, 0, 5, 5], [3, 3, 3, 3]], jnp.int32)
    bias = jnp.array([[0.1, -0.1, 0.0, 0.2], [0.0, 0.0, 0.5, 0.0]])
    got = moe.update_selection_bias(bias, counts, 1e-3)
    d = 1e-3 * np.array([[-1, 1, 0, 0], [0, 0, 0, 0]], np.float32)
    np.testing.assert_allclose(
        got, np.asarray(bias) + d - d.mean(-1, keepdims=True), atol=1e-7)
    # Overloaded experts fall, underloaded rise, the mean of the step is 0.
    assert got[0, 0] < bias[0, 0] and got[0, 1] > bias[0, 1]
    np.testing.assert_allclose((got - bias).mean(-1), 0, atol=1e-8)


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
