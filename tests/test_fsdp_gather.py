"""What crosses the ICI on an fsdp mesh (``parallel/fsdp.on_rows`` under
``models/_lm.token_nll``): on ``{fsdp: 4}`` over the CPU's virtual devices a
chunked loss whose head is gathered once a call gives the one-device
program's loss and gradients, with float32 master weights too; where the
mesh or the batch does not allow the manual region, and without an ``fsdp``
axis over 1, the program is the one it always was.  What the TPU compiler
makes of the collectives is read in ``tests/test_tpu_compile_fsdp4.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import _lm
from ray_tpu.models.llama import (init_params, llama_tiny, loss_fn,
                                  param_logical_axes)
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.parallel.fsdp import manual_mesh, on_rows
from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
from ray_tpu.parallel.sharding import named_sharding
from ray_tpu.parallel.spmd import make_lm_eval_step


@pytest.fixture
def ambient_mesh():
    """Install a mesh as the ambient one for a test, and put back what was
    there."""
    before = get_global_mesh()
    yield set_global_mesh
    set_global_mesh(before)


def _cfg(**kw):
    return llama_tiny().replace(dtype=jnp.float32, layers=3, **kw)


def _batch(cfg, rows=8, seq=64):
    rng = np.random.default_rng(0)
    return {"tokens": rng.integers(0, cfg.vocab_size, (rows, seq),
                                   dtype=np.int32)}


def _mesh(**axes):
    spec = MeshSpec(**axes)
    n = int(np.prod([s for _, s in spec.shape()]))
    return build_mesh(spec, devices=jax.devices()[:n])


def _loss_and_grads(cfg, mesh, params, batch):
    """jit(value_and_grad(loss_fn)) with the parameters placed by the
    default rules on ``mesh`` (None: one device, no mesh)."""
    fn = jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg))
    if mesh is None:
        return jax.jit(fn)
    shardings = jax.tree.map(lambda ax: named_sharding(mesh, ax),
                             param_logical_axes(cfg),
                             is_leaf=lambda x: isinstance(x, tuple))
    rows = NamedSharding(mesh, P(("dp", "fsdp"), None))
    return jax.jit(fn, in_shardings=(shardings, rows),
                   out_shardings=(None, shardings))


def _close(got, want, tol=2e-5):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, atol=tol * float(jnp.max(jnp.abs(w))), rtol=0,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("remat,chunks", [
    ("full", 0), ("full", 4), (True, 4), ("dots", 2), (False, 4),
    ("mlp_only", 8)])
def test_fsdp4_loss_and_gradients_are_the_one_device_program_s(
        remat, chunks, ambient_mesh):
    """Loss and every gradient leaf on ``{fsdp: 4}`` against one device, to
    float32 rounding, under every remat mode the model knows, the loss
    fused and chunked; the gradients come back in the parameters' layout."""
    cfg = _cfg(remat=remat, loss_chunks=chunks)
    params = init_params(cfg, jax.random.key(0))
    batch = _batch(cfg)
    ambient_mesh(None)
    want_loss, want = _loss_and_grads(cfg, None, params, batch)(params, batch)
    mesh = _mesh(fsdp=4)
    ambient_mesh(mesh)
    got_loss, got = _loss_and_grads(cfg, mesh, params, batch)(params, batch)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        assert g.sharding.spec == named_sharding(
            mesh, jax.tree_util.tree_reduce(
                lambda a, k: a[k.key], path,
                param_logical_axes(cfg))).spec, path
    _close(got, want)


@pytest.mark.parametrize("axes", [{"dp": 2, "fsdp": 2}, {"fsdp": 8},
                                  {"fsdp": 2, "tp": 2}],
                         ids=["dp2xfsdp2", "fsdp8", "fsdp2xtp2"])
def test_beside_other_axes_and_on_eight_chips(axes, ambient_mesh):
    """The same values where ``dp`` stands beside ``fsdp`` (the head's
    region is manual over both), on a wider axis, and beside ``tp``, where
    the head stays the partitioner's."""
    cfg = _cfg(remat="full", loss_chunks=2)
    params = init_params(cfg, jax.random.key(2))
    batch = _batch(cfg)
    ambient_mesh(None)
    want_loss, want = _loss_and_grads(cfg, None, params, batch)(params, batch)
    mesh = _mesh(**axes)
    assert manual_mesh(mesh, 8) == ("tp" not in axes)
    ambient_mesh(mesh)
    got_loss, got = _loss_and_grads(cfg, mesh, params, batch)(params, batch)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    _close(got, want)


def _scan_carries(jaxpr, shape):
    """The dtypes of every scan carry of ``shape`` in ``jaxpr``, regions
    and loops inside it included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            n = eqn.params["num_consts"], eqn.params["num_carry"]
            found += [v.aval.dtype for v in eqn.invars[n[0]:n[0] + n[1]]
                      if v.aval.shape == shape]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scan_carries(sub, shape)
    return found


def test_float32_master_weights_sum_the_head_s_gradient_in_float32(
        ambient_mesh):
    """float32 parameters under bfloat16 compute: the head crosses the ICI
    as bfloat16, and the chunks' gradients are summed on each chip in
    float32 as the one-device program sums them; the head's gradient is as
    near the all-float32 program's as one device's is."""
    cfg = _cfg(remat="full", loss_chunks=8).replace(dtype=jnp.bfloat16)
    params = init_params(cfg, jax.random.key(4))
    assert params["lm_head"].dtype == jnp.float32
    batch = _batch(cfg)
    ambient_mesh(None)
    exact = _cfg(remat="full", loss_chunks=8)
    _, ref = _loss_and_grads(exact, None, params, batch)(params, batch)
    want_loss, want = _loss_and_grads(cfg, None, params, batch)(params, batch)
    mesh = _mesh(fsdp=4)
    ambient_mesh(mesh)
    step = _loss_and_grads(cfg, mesh, params, batch)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: loss_fn(p, b, cfg)))(
        params, batch)
    whole = ",".join(map(str, params["lm_head"].shape))
    assert f"bf16[{whole}] = all_gather[" in str(jaxpr)
    carries = _scan_carries(jaxpr.jaxpr, params["lm_head"].shape)
    assert carries and all(d == jnp.float32 for d in carries), carries
    got_loss, got = step(params, batch)
    assert got["lm_head"].dtype == jnp.float32
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-3)
    err = lambda g: float(jnp.linalg.norm(g["lm_head"] - ref["lm_head"])
                          / jnp.linalg.norm(ref["lm_head"]))
    assert err(got) <= 1.25 * err(want), (err(got), err(want))


def test_rows_that_do_not_divide_over_the_chips_keep_the_old_program(
        ambient_mesh):
    """After a train step was made for ``{fsdp: 4}`` the mesh stays
    installed: scoring one row in the same process runs as it did."""
    cfg = _cfg(remat="full", loss_chunks=4)
    params = init_params(cfg, jax.random.key(0))
    one = _batch(cfg, rows=1)
    ambient_mesh(None)
    want = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, one)
    mesh = _mesh(fsdp=4)
    assert not manual_mesh(mesh, 1) and not manual_mesh(mesh, 6)
    assert manual_mesh(mesh, 8)
    ambient_mesh(mesh)
    got = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, one)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def _parent_token_nll(x, lm_head, targets, num_chunks, dt, weights):
    """``token_nll``'s chunked weighted sum as it was before the head could
    be gathered once: the loops close over the head."""
    def nll(xc, tc, wc=None):
        logits = jnp.einsum("bse,ev->bsv", xc, lm_head.astype(dt),
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1,
                                  mode="promise_in_bounds")[..., 0]
        return jnp.sum((lse - tgt) * wc, axis=(0, 1))

    B, S, E = x.shape
    c = S // num_chunks
    chunks = lambda a: jnp.swapaxes(
        a.reshape((B, num_chunks, c) + a.shape[2:]), 0, 1)
    chunk_nll = jax.checkpoint(nll)
    total, _ = jax.lax.scan(
        lambda acc, xtw: (acc + chunk_nll(*xtw), None),
        jnp.zeros(weights.shape[2:], jnp.float32),
        (chunks(x), chunks(targets), chunks(weights)))
    return total


@pytest.mark.parametrize("axes,rows", [
    (None, 8), ({"dp": 4}, 8), ({"dp": 2, "tp": 2}, 8), ({"fsdp": 4}, 6)],
    ids=["no-mesh", "dp4", "dp2xtp2", "fsdp4-6rows"])
def test_where_the_head_is_not_gathered_the_program_is_the_parent_s(
        axes, rows, ambient_mesh):
    """Without an ``fsdp`` axis over 1, beside ``tp``, or on rows that do
    not divide: the chunked loss and its gradients lower to the text of the
    function as it was."""
    key = jax.random.key(5)
    x = jax.random.normal(key, (rows, 64, 32), jnp.float32)
    head = jax.random.normal(key, (32, 96), jnp.float32)
    targets = jnp.zeros((rows, 64), jnp.int32)
    weights = jnp.ones((rows, 64), jnp.float32)
    ambient_mesh(_mesh(**axes) if axes else None)
    text = lambda f: jax.jit(jax.grad(f, argnums=(0, 1))).lower(
        x, head).as_text()
    now = text(lambda x, h: _lm.token_nll(x, h, targets, 4, jnp.bfloat16,
                                          weights))
    was = text(lambda x, h: _parent_token_nll(x, h, targets, 4,
                                              jnp.bfloat16, weights))
    assert now == was


@pytest.mark.parametrize("reduce", [True, False])
def test_on_rows_gathers_once_and_scatters_once(reduce):
    """``on_rows`` alone: a function that reads the weight in a loop sees it
    whole; the traced program holds one gather and its gradient one
    reduce-scatter, whatever the loop's length."""
    mesh = _mesh(dp=2, fsdp=4)
    key = jax.random.key(6)
    w = jax.random.normal(key, (32, 48), jnp.float32)
    rows = jax.random.normal(key, (8, 5, 32), jnp.float32)

    def fn(w, rows):
        assert w.shape == (32, 48) and rows.shape == (1, 5, 32)
        out, _ = jax.lax.scan(
            lambda acc, r: (acc + jnp.tanh(r @ w), None),
            jnp.zeros((1, 48)), jnp.swapaxes(rows, 0, 1))
        return jnp.sum(out) if reduce else out

    plain = lambda w, rows: jnp.sum(jnp.sum(jnp.tanh(rows @ w), axis=1))
    ours = lambda w, rows: jnp.sum(on_rows(
        fn, w, (rows,), mesh=mesh, logical=("embed", "vocab"),
        dtype=jnp.float32, reduce=reduce))
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(w, rows)
    got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1)))(w, rows)
    _close(got, want)
    text = str(jax.make_jaxpr(jax.grad(ours))(w, rows))
    assert text.count("all_gather[") == 1, text.count("all_gather[")
    assert text.count("reduce_scatter[") == 1, text.count("reduce_scatter[")


def test_eval_step_on_an_fsdp_mesh_gives_the_one_device_loss(ambient_mesh):
    """``make_lm_eval_step`` on ``{fsdp: 4}`` with a chunked loss: nothing
    is differentiated, the head is gathered once all the same."""
    cfg = _cfg(remat="full", loss_chunks=4)
    params = init_params(cfg, jax.random.key(0))
    batch = _batch(cfg)
    ambient_mesh(None)
    want = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
    evaluate = make_lm_eval_step(cfg, _mesh(fsdp=4))
    np.testing.assert_allclose(evaluate(params, batch), want, rtol=1e-6)
