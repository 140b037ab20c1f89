"""The hyper-connection passes as Pallas kernels (``ops/hyper.py``), in
interpret mode on the CPU, against the ``jnp`` forms that define them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import hyper

MAPS = (20, 1e-6, (-30.0, 30.0))
#: lanes, the stream's dtype, rows of the batch, tokens a row, width; the
#: last two: a width of five inner-loop slices, and two tiles a row (so that
#: d phi adds up over the token grid)
CASES = [(n, dt, B, 32, 128) for n in (4, 2)
         for dt in (jnp.bfloat16, jnp.float32) for B in (1, 2)] + [
    (4, jnp.bfloat16, 1, 32, 640), (4, jnp.float32, 2, 1024, 128)]
IDS = [f"{n}lanes-{jnp.dtype(dt).name}-{B}rows" + (
    "" if (S, C) == (32, 128) else f"-{S}x{C}") for n, dt, B, S, C in CASES]


def _inputs(n, dt, B, S=32, C=128):
    """A stream, a sublayer's map weights with gains of 1 (so that the maps
    differ between tokens), a toy F's weight and what the loss weighs the
    written stream by."""
    k = jax.random.split(jax.random.key(n + B), 6)
    M = 2 * n + n * n
    return {"X": jax.random.normal(k[0], (B, n, S, C)).astype(dt),
            "phi": jax.random.normal(k[1], (n * C, M)) / np.sqrt(n * C),
            "b": jax.random.normal(k[2], (M,)),
            "alpha": jnp.array([1.0, 0.8, 1.2]),
            "w": jax.random.normal(k[3], (C, C)) / np.sqrt(C),
            "y0": jax.random.normal(k[4], (B, S, C)).astype(dt),
            "weigh": jax.random.normal(k[5], (B, n, S, C))}


def _sublayer(p, kernels: bool):
    """maps + collect + a toy F + deposit -> (u, H_post, H_res, X')."""
    if kernels:
        X, u, H_post, H_res = hyper.collect(
            p["X"], p["phi"], p["b"], p["alpha"], *MAPS, interpret=True)
    else:
        H_pre, H_post, H_res = hyper.hc_maps(
            p["X"], p["phi"], p["b"], p["alpha"], *MAPS)
        X, u = p["X"], hyper.hc_collect(p["X"], H_pre)
    y = (jnp.tanh(u.astype(jnp.float32) @ p["w"]).astype(u.dtype) + p["y0"])
    out = (hyper.deposit(X, H_res, H_post, y, interpret=True) if kernels
           else hyper.hc_deposit(X, H_res, H_post, y))
    return u, H_post, H_res, out


def _tolerance(dt):
    """A bf16 result is one rounding from the other path's (2 ** -8 of a
    number of size 2-4); float32 differs by the order of its sums."""
    return 4e-2 if dt == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("n,dt,B,S,C", CASES, ids=IDS)
def test_collect_and_deposit_match_the_jnp_forms(n, dt, B, S, C):
    p = _inputs(n, dt, B, S, C)
    got, want = _sublayer(p, True), _sublayer(p, False)
    for name, g, w in zip(("u", "H_post", "H_res", "X'"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_allclose(
            g.astype(jnp.float32), w.astype(jnp.float32),
            atol=1e-5 if name.startswith("H_") else _tolerance(dt),
            err_msg=name)


@pytest.mark.parametrize("n,dt,B,S,C", CASES, ids=IDS)
def test_every_gradient_matches_autodiff_of_the_jnp_path(n, dt, B, S, C):
    """X, phi, b, alpha and the sublayer's own y (through ``y0``, and
    through a toy F's weight that only ``du`` reaches)."""
    p = _inputs(n, dt, B, S, C)
    weigh = p.pop("weigh")

    def loss(p, kernels):
        out = _sublayer(p, kernels)[-1]
        return jnp.sum(out.astype(jnp.float32) * weigh)

    grad = jax.jit(jax.grad(loss), static_argnums=1)
    got = grad(p, True)
    want = grad(p, False)
    assert sorted(got) == ["X", "alpha", "b", "phi", "w", "y0"]
    for name in got:
        g, w = (a[name].astype(jnp.float32) for a in (got, want))
        assert got[name].dtype == want[name].dtype, name
        distance = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        # bf16: each path rounds X's cotangent (2 ** -9 a number) where the
        # other does not; the small weights' gradients are sums over tokens.
        assert distance < (6e-3 if dt == jnp.bfloat16 else 2e-5), \
            (name, distance)


def _paths(monkeypatch):
    from ray_tpu.util import telemetry
    seen = []
    monkeypatch.setattr(
        telemetry, "inc", lambda name, value=1.0, tags=None: seen.append(
            (tags["path"], tags["lanes"]))
        if name == "ray_tpu_hc_path_total" else None)
    return seen


@pytest.mark.parametrize("S,C", [(32, 64), (24, 128), (32, 192)],
                         ids=["64-wide", "24-rows", "192-wide"])
def test_a_shape_that_does_not_tile_takes_xla_and_says_so(monkeypatch, S, C):
    seen = _paths(monkeypatch)
    p = _inputs(4, jnp.float32, 1, S=S, C=C)
    monkeypatch.setattr(hyper, "_call", None)       # no kernel is built
    got, want = _sublayer(p, True), _sublayer(p, False)
    assert seen == [("xla", "4")] * 2
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_the_kernel_path_counts_kernel_and_the_chip_takes_it_unasked(
        monkeypatch):
    """``interpret`` is the tests' way in; on a TPU the same shapes take the
    kernels with nothing said."""
    import importlib
    seen = _paths(monkeypatch)
    p = _inputs(2, jnp.float32, 1)
    _sublayer(p, True)
    assert seen == [("kernel", "2")]
    assert not hyper._kernels(p["X"], False)
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"),
                        "_on_tpu", lambda: True)
    assert hyper._kernels(p["X"], False)
    assert not hyper._kernels(p["X"][..., :64], False)


def test_tiles_follow_the_shapes():
    """Every kernel's blocks fit Mosaic's default 16 MiB of scoped VMEM,
    double-buffered (a kernel that asks for more hangs the cell's step): at
    the cell's [., 4, 8192, 3584] bf16 the kernels that hold 14 planes take
    32 rows, the write-back 64, those that hold 5 take 128; a float32 stream
    of that width does not fit and takes ``xla``; a short row is one tile."""
    def tiles(S, C, dt, n=4):
        X = jax.ShapeDtypeStruct((1, n, S, C), dt)
        return {k: hyper._tile(X, k) for k in hyper._HELD}

    assert tiles(8192, 3584, jnp.bfloat16) == {
        "collect": 128, "pre_bwd": 128, "deposit": 64, "deposit_bwd": 32,
        "collect_bwd": 32}
    assert tiles(8192, 3584, jnp.float32)["collect_bwd"] is None
    assert not hyper._kernels(
        jax.ShapeDtypeStruct((1, 4, 8192, 3584), jnp.float32), True)
    assert set(tiles(64, 128, jnp.bfloat16).values()) == {64}
    assert set(tiles(8192 + 16, 128, jnp.bfloat16).values()) == {16 * 27}
    assert set(tiles(24, 128, jnp.bfloat16).values()) == {None}
    for kernel, (planes, phi, dphi) in hyper._HELD.items():
        rows = tiles(8192, 3584, jnp.bfloat16)[kernel]
        held = rows * 3584 * 2 * planes(4) + 4 * 32 * 3584 * (2 * phi
                                                              + 4 * dphi)
        assert 2 * held <= 12 * 2 ** 20 < 4 * held, kernel
