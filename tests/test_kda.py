"""``ops/kda.py``: the chunked gated delta rule with a decay a channel, its
``jnp`` form and its Pallas kernels (interpreted here), against the
token-by-token recurrence: forward and five gradients."""

from __future__ import annotations

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.kda import chunk_carry, kda

K = importlib.import_module("ray_tpu.ops.kda")


def recurrence(q, k, v, g, beta, scale):
    """S_t = (I - b k k^T) Diag(e^g) S_{t-1} + b k v^T, o_t = S_t^T q_t."""
    dk, dv = q.shape[-1], v.shape[-1]

    def head(q, k, v, g, b):
        def step(S, x):
            q_, k_, v_, g_, b_ = x
            S = jnp.exp(g_)[:, None] * S
            S = S - b_ * jnp.outer(k_, k_ @ S) + b_ * jnp.outer(k_, v_)
            return S, S.T @ (q_ * scale)
        return jax.lax.scan(step, jnp.zeros((dk, dv), jnp.float32),
                            (q, k, v, g, b))[1]

    return jax.vmap(jax.vmap(head, in_axes=1, out_axes=1))(q, k, v, g, beta)


def inputs(seed, B, S, H, dk, dv, gate):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, dk)))
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = {"random": -5 * jax.nn.sigmoid(
            jax.random.normal(ks[3], (B, S, H, dk)) - 2),
         "bound": jnp.full((B, S, H, dk), -5 + 1e-3),
         "none": jnp.full((B, S, H, dk), -1e-3)}[gate]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, S, H, dv))


def _close(got, want, tol):
    return float(jnp.max(jnp.abs(got - want))) <= tol * max(
        float(jnp.max(jnp.abs(want))), 1e-30)


@pytest.mark.parametrize("gate", ["random", "bound", "none"])
@pytest.mark.parametrize("form", ["xla16", "xla64", "kernels64", "kernels128"])
def test_forward_and_five_gradients_against_the_recurrence(form, gate):
    """The chunked form equals the recurrence at the gate's bound (the worst
    case of a sub-block's exponentials), with hardly any decay (the longest
    memory) and in between; the kernels take heads of 128 channels."""
    kernels = form.startswith("kernels")
    C, d = int(re.sub(r"\D", "", form)), 128 if kernels else 32
    args, w = inputs(1, 2, 128, 2, d, d, gate)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args, d ** -0.5)
        got = kda(*args, C, interpret=kernels)
        assert _close(got, want, 1e-5)
        dwant = jax.grad(lambda *a: jnp.sum(recurrence(*a, d ** -0.5) * w),
                         argnums=(0, 1, 2, 3, 4))(*args)
        dgot = jax.grad(lambda *a: jnp.sum(kda(*a, C, interpret=kernels) * w),
                        argnums=(0, 1, 2, 3, 4))(*args)
    # g's gradient at the bound is six orders under q's: judged looser
    for name, a, b in zip("qkvgb", dgot, dwant):
        assert _close(a, b, 3e-4 if name == "g" else 2e-5), name


def test_a_row_the_chunk_does_not_divide_is_padded():
    """40 tokens in chunks of 16: the padded tokens leave the state alone
    and nothing before them sees them."""
    args, _ = inputs(2, 1, 40, 2, 16, 16, "random")
    with jax.default_matmul_precision("highest"):
        assert _close(kda(*args, 16), recurrence(*args, 0.25), 1e-5)
        grad = jax.grad(lambda v: jnp.sum(kda(args[0], args[1], v, args[3],
                                              args[4], 16)))(args[2])
    assert grad.shape == args[2].shape and bool(jnp.all(jnp.isfinite(grad)))


def test_every_row_starts_from_a_zero_state():
    args, _ = inputs(3, 2, 64, 2, 16, 16, "none")
    both = kda(*args, 16)
    for row in range(2):
        alone = kda(*(a[row:row + 1] for a in args), 16)
        np.testing.assert_allclose(np.asarray(both[row:row + 1]),
                                   np.asarray(alone), rtol=1e-6, atol=1e-6)


def test_the_kernels_keep_the_input_s_dtype_and_take_bfloat16():
    args, _ = inputs(4, 1, 64, 2, 128, 128, "random")
    q, k, v, g, beta = args
    bf = lambda a: a.astype(jnp.bfloat16)
    got = kda(bf(q), bf(k), bf(v), g, beta, 64, interpret=True)
    assert got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = recurrence(*(bf(a).astype(jnp.float32) for a in (q, k, v)),
                          g, beta, 128 ** -0.5)
    assert _close(got.astype(jnp.float32), want, 1e-2)


def test_a_chunk_that_is_no_power_of_two_of_sub_blocks_is_refused():
    args, _ = inputs(5, 1, 48, 1, 16, 16, "none")
    for chunk in (24, 8, 48):
        with pytest.raises(ValueError, match="sub-blocks"):
            kda(*args, chunk)


def test_a_mesh_of_more_than_one_device_is_refused():
    from ray_tpu.parallel.mesh import (MeshSpec, build_mesh, get_global_mesh,
                                       set_global_mesh)
    args, _ = inputs(6, 1, 16, 1, 16, 16, "none")
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(tp=2), jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="ROADMAP M8"):
            kda(*args, 16)
    finally:
        set_global_mesh(before)


@pytest.mark.parametrize("C", [16, 32, 64, 128])
@pytest.mark.parametrize("case", ["random", "equal_keys"])
def test_the_triangular_inverse_block_by_block_is_exact(case, C):
    """``(I + A)^-1`` from its diagonal blocks of 2, joined a round a
    doubling (16: a sub-block alone, the rounds under a sublane tile; 128:
    the chunk that ships, with the three rounds that stream whole tiles);
    ``equal_keys`` is the worst case of a series in powers of A (every key
    the same, beta 1, no decay: A all ones under the diagonal, whose powers
    reach 1e17 at 64), which joining blocks does not form."""
    A = np.tril(np.ones((C, C), np.float32), -1) if case == "equal_keys" \
        else np.tril(np.asarray(jax.random.normal(jax.random.key(7), (C, C))),
                     -1) * 0.3
    with jax.default_matmul_precision("highest"):
        T = K._tri_inv(jnp.asarray(A))
        want = np.linalg.inv(np.eye(C) + A.astype(np.float64))
        np.testing.assert_allclose(np.asarray(T), want, atol=2e-4 * np.abs(
            want).max())
        dT = jax.random.normal(jax.random.key(8), (C, C))
        got = jax.grad(lambda A: jnp.sum(K._tri_inv(A) * dT))(jnp.asarray(A))
        ref = np.tril(-(want.T @ np.asarray(dT, np.float64) @ want.T), -1)
    np.testing.assert_allclose(np.asarray(got), ref,
                               atol=2e-4 * np.abs(ref).max())


@pytest.mark.parametrize("gate", ["random", "bound", "none"])
def test_a_chunk_s_own_values_are_float32_at_full_precision(gate):
    """What a chunk of 128 computes before it meets the carried state, each
    against ``numpy.float64`` of what it is computed FROM (the running sum
    from g, ``A`` and ``Aqk`` from that float32 sum, the inverse from that
    float32 ``A``): 2e-6 of the largest entry, which three bf16 passes in
    the place of six, or a bf16 operand, break."""
    C, d = 128, 128
    (q, k, _, g, beta), _ = inputs(9, 1, C, 1, d, d, gate)
    q, k, g, beta = (np.asarray(a[0, :, 0]) for a in (q, k, g, beta))
    with jax.default_matmul_precision("highest"):
        G, A, Aqk = K._within(*(jnp.asarray(a) for a in (
            q, k, g, beta[:, None])))
        T = K._tri_inv(A)
    near = lambda got, want: np.testing.assert_allclose(
        np.asarray(got), want, rtol=0, atol=2e-6 * np.abs(want).max())
    near(G, np.cumsum(g.astype(np.float64), axis=0))
    G64 = np.asarray(G, np.float64)
    decay = np.exp(np.minimum(G64[:, None] - G64[None], 0.0))      # [t, s, d]
    kk = np.einsum("td,sd,tsd->ts", k.astype(np.float64), k, decay)
    qk = np.einsum("td,sd,tsd->ts", q.astype(np.float64), k, decay)
    near(A, np.tril(beta.astype(np.float64)[:, None] * kk, -1))
    near(Aqk, np.tril(qk))
    near(T, np.linalg.inv(np.eye(C) + np.asarray(A, np.float64)))


def test_chunk_carry_is_the_mean_decay_of_a_whole_chunk():
    g = jnp.full((2, 40, 3, 4), -0.01)
    assert float(chunk_carry(g, 16)) == pytest.approx(np.exp(-0.16), rel=1e-5)
    assert float(chunk_carry(g[:, :8], 16)) == 1.0
    assert float(jax.grad(lambda g: chunk_carry(g, 16))(g).sum()) == 0.0
