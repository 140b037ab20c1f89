"""The pieces the afmoe model brought to ``ops/moe``: the sigmoid router and
its selection bias, the dispatch without drops, the grouped products' tiles
and their own vjp.  (Cut from ``tests/test_afmoe.py``, PR 59.)"""

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
