"""The expert layer's sums over a token's rows (``ops/moe.rows_of_tokens`` /
``tokens_from_rows``) against the gather and scatter-add they replaced, on
the ``jnp`` and the kernel path, and that rows no group holds reach nothing.
(Cut from ``tests/test_afmoe.py``, PR 59.)"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_afmoe as ref  # noqa: E402
from ops_cases import _counted, _experts  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


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


# share of the router's experts held: (held, routed, tiers at 8,192 tokens,
# the buffer's rows there at k 8 / 6 / 4).  An eighth in Trinity-Mini,
# Xing4.0, Nemotron, Kanana and LFM2, a thirty-second in MiMo-V2-Flash and
# Ling-3.0-flash, a forty-eighth in Motif-3-beta, all in a one-chip model.
_SHARES = {"eighth": (16, 128, 4, (16384, 12288, 8192)),
           "thirty-second": (8, 256, 16, (4096, 3072, 2048)),
           "forty-eighth": (8, 384, 16, (4096, 3072, 2048)),
           "all": (8, 8, 4, (16384, 12288, 8192))}


def _buffers_counted():
    return _counted("ray_tpu_moe_buffer_total",
                    ("rows", "tiers", "held", "routed", "tokens", "slots"))


@pytest.mark.parametrize("share,load", [
    *((share, load) for share in _SHARES
      for load in ("usual", "every-slot-held")),
    ("thirty-second", "every-slot-held-kernel")])
def test_the_buffer_follows_the_share_of_the_experts_held(share, load,
                                                          monkeypatch):
    """The buffer is twice the expected load at any share, and never over a
    quarter of the worst case: its rows and tiers for 8,192 tokens (an
    eighth and all held: what the four tiers gave), the (T, k) form the
    share unknown; the layer against the dense sum, result and gradients,
    with the usual load through the buffer at once and with every slot of
    every token held, 8 times the mean and more, through all its slices
    (16 of 64 tokens where a thirty-second or less is held; once with the
    sums on the kernel path, interpreted), nothing dropped; and the counter
    says which buffer the call took."""
    Xh, X, tiers, rows = _SHARES[share]
    assert moe.buffer_tiers(8192, Xh, X) == tiers
    assert tuple(moe.buffer_rows(8192, k, Xh, X) for k in (8, 6, 4)) == rows
    assert tuple(moe.buffer_rows(8192, k) for k in (8, 6, 4)) == (
        16384, 12288, 8192)
    # a slice is whole tiles of the sum's kernel, or the tiers stay lower
    assert moe.buffer_tiers(512, Xh, X) == min(tiers, 8)
    assert moe.buffer_tiers(62, Xh, X) == 1

    T, k, E = 1024, 8, 128 if load.endswith("kernel") else 32
    xt, rw, wg, wu, wd = _experts(T=T, E=E, X=X, Xh=Xh, k=k)
    R = moe.buffer_rows(T, k, Xh, X)
    assert R == T * k // tiers
    if E == 128:
        monkeypatch.setattr(moe, "_INTERPRET_ROWS", True)
        assert moe._rows_tile(T // tiers, R, E, Xh, xt.dtype) == 64
    bias = jnp.where(jnp.arange(X) < Xh, 10.0 * (load != "usual"), 0.0)

    def layer(reference, xt, rw, wg, wu, wd):
        routing = moe.sigmoid_routing(xt, rw, bias, k, 2.5)
        if reference:
            out, stats = ref.held_experts(xt, routing.expert_index,
                                          routing.weights, wg, wu, wd, 0), ()
        else:
            out, stats = moe.dropless_experts(xt, routing, wg, wu, wd, 0)
        return jnp.sum(jnp.sin(out)), (out, stats)

    run = lambda which: jax.jit(jax.value_and_grad(
        functools.partial(layer, which), argnums=(0, 1, 2, 3, 4),
        has_aux=True))(xt, rw, wg, wu, wd)
    before = _buffers_counted()
    (got, (out, (held, dropped))), grads = run(False)
    key = (str(R), str(tiers), str(Xh), str(X), str(T), str(k))
    assert _buffers_counted().get(key, 0) == before.get(key, 0) + 1
    (want, (want_out, _)), want_grads = run(True)
    sliced = int(held) > R
    if load == "usual":
        # twice the mean holds what a seeded router sends (all held: T * k)
        assert sliced == (share == "all") and int(held) >= T * k * Xh // X // 2
    else:
        assert sliced and int(held) == T * k
    assert int(dropped) == 0
    np.testing.assert_allclose(out, want_out, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # float32 sums over E features and, for the router's, T tokens
    atol = 5e-5 if E == 32 else 4e-4
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, atol=atol, rtol=5e-4)


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
