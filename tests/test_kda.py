"""``ops/kda.py``: the chunked gated delta rule with a decay a channel, its
``jnp`` form and its Pallas kernels (interpreted here), against the
token-by-token recurrence: forward and five gradients."""

from __future__ import annotations

import functools
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


@functools.lru_cache(maxsize=None)
def _by_the_recurrence(d, gate):
    """(inputs, the cotangent, the recurrence's output and five gradients) at
    heads of ``d`` channels, once a module under one ``jax.jit``: the two
    chunk sizes of a form compare against the same."""
    args, w = inputs(1, 2, 128, 2, d, d, gate)

    def loss(*a):
        out = recurrence(*a, d ** -0.5)
        return jnp.sum(out * w), out

    with jax.default_matmul_precision("highest"):
        (_, want), dwant = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return args, w, want, dwant


@pytest.mark.parametrize("gate", ["random", "bound", "none"])
@pytest.mark.parametrize("form", ["xla16", "xla64", "kernels64", "kernels128"])
def test_forward_and_five_gradients_against_the_recurrence(form, gate):
    """The chunked form equals the recurrence at the gate's bound (the worst
    case of a sub-block's exponentials), with hardly any decay (the longest
    memory) and in between; the kernels take heads of 128 channels."""
    kernels = form.startswith("kernels")
    C, d = int(re.sub(r"\D", "", form)), 128 if kernels else 32
    args, w, want, dwant = _by_the_recurrence(d, gate)

    def loss(*a):
        out = kda(*a, C, interpret=kernels)
        return jnp.sum(out * w), out

    with jax.default_matmul_precision("highest"):
        (_, got), dgot = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    assert _close(got, want, 1e-5)
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


# ------------------------- the pass between the projections and the scan

def mixer_inputs(seed, B, S, H, D, gate, w_dtype=jnp.float32):
    """A KDA layer's projections' results (bfloat16, as a training step has
    them) and its small weights; ``gate``: the decay's projection random,
    saturated at the bound, or so far under it that nothing decays."""
    ks = jax.random.split(jax.random.key(seed), 10)
    F, bf = H * D, jnp.bfloat16
    qkv = jax.random.normal(ks[0], (B, S, 3 * F)).astype(bf)
    a = (jax.random.normal(ks[1], (B, S, F))
         * {"random": 2.0, "bound": 0.1, "none": 0.1}[gate]
         + {"random": 0.0, "bound": 12.0, "none": -12.0}[gate]).astype(bf)
    conv_w = (0.5 * jax.random.normal(ks[2], (4, 3 * F))).astype(w_dtype)
    A_log = jnp.log(jax.random.uniform(ks[3], (H,), minval=1.0, maxval=16.0))
    dt_bias = jax.random.normal(ks[4], (F,))
    cts = tuple(jax.random.normal(k, (B, S, F)).astype(dt) for k, dt in zip(
        ks[5:9], (bf, bf, bf, jnp.float32)))
    return (qkv, a, conv_w, A_log, dt_bias), cts


def _a_bf16_step(got, want):
    """Equal but for roundings to bfloat16 that an operation order moved (a
    cotangent rounded on its way, or the result): at most a thousandth of
    the entries differ at all, none by more than a step of the format at
    the largest entry."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    off = np.abs(got - want)
    return off.max() <= 2.0 ** -8 * np.abs(want).max() \
        and np.count_nonzero(off) <= got.size // 1000


@functools.partial(jax.jit, static_argnames=("heads", "kernels"))
def _pass_and_its_pull(args, cts, heads, kernels):
    """The pass before the scan and its five gradients through ``cts``: one
    program a dtype of the taps and a path for every gate (what differs
    between the gates is the operands' values), the same jit on both sides
    so that the forward's bits are the paths' own."""
    B, S, F = args[1].shape
    if kernels:
        fn = lambda *a: K._inputs(*a, heads=heads, bound=-5.0, interpret=True)
    else:
        fn = lambda *a: tuple(x.reshape(B, S, F) for x in K._inputs_xla(
            *a, heads, -5.0))
    out, pull = jax.vjp(fn, *args)
    return out, pull(cts)


@pytest.mark.parametrize("gate,w_dtype", [
    ("random", jnp.float32), ("bound", jnp.float32), ("none", jnp.float32),
    ("random", jnp.bfloat16)])
def test_the_pass_before_the_scan_is_the_jnp_form(gate, w_dtype):
    """``kda_in_fwd`` / ``kda_in_bwd`` (interpreted) against ``_inputs_xla``
    on two rows of 1,024 tokens: two tiles of a row forward and four
    backward, so that taps cross a tile's edge both ways and the carried
    rows reset at a row's start; six heads are two columns of three (a
    column range's offset is not its tile).  Forward: the same bits, q, k,
    v (bfloat16) and g (float32).  The five gradients through cotangents of
    all four: the float32 ones to an accumulation order, the bfloat16 ones
    (d qkv, d a, and d conv_w where the weights are bfloat16) to a rounding
    that the order moved."""
    B, S, H, D = 2, 1024, 6, 128
    args, cts = mixer_inputs(11, B, S, H, D, gate, w_dtype)
    assert K._in_tile(S, H, D, 4, False) == (512, 384)
    assert K._in_tile(S, H, D, 4, True) == (256, 384)
    want, dwant = _pass_and_its_pull(args, cts, H, False)
    got, dgot = _pass_and_its_pull(args, cts, H, True)
    for name, x, y in zip("qkvg", got, want):
        assert x.dtype == y.dtype and x.shape == y.shape, name
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32), err_msg=name)
    if gate != "random":
        g = np.asarray(got[3])
        assert (g.max() < -4.99) if gate == "bound" else (g.min() > -1e-2)
    for name, x, y in zip(("qkv", "a", "conv_w", "A_log", "dt_bias"),
                          dgot, dwant):
        assert x.dtype == y.dtype and x.shape == y.shape, name
        if x.dtype == jnp.bfloat16:
            assert _a_bf16_step(x, y), name
        else:
            # (the taps' sums see the few cotangents whose rounding moved)
            assert _close(x, y, 1e-4 if name == "conv_w" else 1e-5), name


def _within_a_bf16_step(got, want):
    """No entry further from ``want`` than two steps of bfloat16 at the larger
    of the two, or than that at a hundredth of the largest entry where
    terms cancelled: what a rounding more or less of each factor of a
    product moves."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    size = np.maximum(np.maximum(np.abs(got), np.abs(want)),
                      1e-2 * np.abs(want).max())
    return bool(np.all(np.abs(got - want) <= 2.0 ** -6 * size))


def test_the_pass_after_the_scan_is_the_jnp_form():
    """``kda_norm_fwd`` / ``kda_norm_bwd`` (interpreted) against the head
    norm times the gate's sigmoid in ``jnp``, two rows of two tiles, six
    heads in two columns.  The pass is float32 from its reads to the one
    rounding of each result; ``jnp`` here rounds the normed head and the
    sigmoid before their product (inside the TPU compiler's fusion it does
    not): two steps of bfloat16 apart at most forward, and each gradient
    within a step of the format at its largest entry (d o cancels two
    terms, the weight's is a sum over every token and head)."""
    B, S, H, D = 2, 1024, 6, 128
    ks = jax.random.split(jax.random.key(14), 4)
    bf = jnp.bfloat16
    o = jax.random.normal(ks[0], (B, S, H, D)).astype(bf)
    gate = (2 * jax.random.normal(ks[1], (B, S, H * D))).astype(bf)
    w = (1 + 0.1 * jax.random.normal(ks[2], (D,))).astype(bf)
    dy = jax.random.normal(ks[3], (B, S, H * D)).astype(bf)
    want, pull_want = jax.vjp(jax.jit(lambda *a: K._gated_norm_xla(
        *a, 1e-6)), o, gate, w)
    got, pull_got = jax.vjp(lambda *a: K._gated_norm(
        *a, heads=H, eps=1e-6, interpret=True), o, gate, w)
    assert got.dtype == want.dtype == bf and got.shape == want.shape
    assert _within_a_bf16_step(got, want)
    # against float32 of the same lines the pass is the nearer of the two
    o32, g32, w32 = (a.astype(jnp.float32) for a in (o, gate, w))
    exact = K._gated_norm_xla(o32, g32, w32, 1e-6)
    err = lambda y: float(jnp.mean(jnp.abs(y.astype(jnp.float32) - exact)))
    assert err(got) <= err(want)
    for name, x, y in zip(("o", "gate", "w"), jax.jit(pull_got)(dy),
                          jax.jit(pull_want)(dy)):
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert _close(x.astype(jnp.float32), y.astype(jnp.float32),
                      2.0 ** -7), name


def test_a_layer_takes_the_pass_where_a_row_is_whole_tiles_and_says_which():
    """``kda_mixer`` and ``gated_head_norm`` read the path from the call: a
    row of 512 tokens of heads of 128 goes through the Pallas passes (the
    scan handed flat arrays), 64 tokens through ``jnp``; both give the
    numbers of the ``jnp`` forms round ``kda``, and the counter names the
    path of each pass."""
    from ray_tpu.util import metrics as metrics_mod
    H, D = 1, 128
    metrics_mod._reset_for_tests()
    for S, path in ((512, "kernel"), (64, "xla")):
        (qkv, a, conv_w, A_log, dt_bias), _ = mixer_inputs(
            12, 1, S, H, D, "random")
        beta = jax.nn.sigmoid(jax.random.normal(jax.random.key(13),
                                                (1, S, H)))
        o, g = K.kda_mixer(qkv, a, beta, conv_w, A_log, dt_bias, bound=-5.0,
                           chunk=64, interpret=True)
        # (jitted, as a step runs it: eagerly its sums are not fused)
        q, k, v, g4 = jax.jit(lambda *args: K._inputs_xla(*args, H, -5.0))(
            qkv, a, conv_w, A_log, dt_bias)
        want = kda(q, k, v, g4, beta, 64, interpret=True)
        assert o.shape == (1, S, H, D) and o.dtype == qkv.dtype
        assert g.shape == ((1, S, H * D) if path == "kernel"
                           else (1, S, H, D))
        assert _a_bf16_step(o, want)
        assert float(chunk_carry(g, 64)) == pytest.approx(
            float(chunk_carry(g4, 64)), rel=1e-6)
        text = metrics_mod.prometheus_text()
        line = next(l for l in text.splitlines() if l.startswith(
            "ray_tpu_kda_pass_path_total{") and 'pass="inputs"' in l
            and f'seq="{S}"' in l)
        assert f'path="{path}"' in line and 'heads="1"' in line, line
        gate = jax.random.normal(jax.random.key(15), (1, S, H * D)).astype(
            qkv.dtype)
        w = jnp.ones((D,), qkv.dtype)
        y = K.gated_head_norm(o, gate, w, 1e-6, interpret=True)
        assert _within_a_bf16_step(y, jax.jit(lambda *a: K._gated_norm_xla(
            *a, 1e-6))(o, gate, w))
        line = next(l for l in metrics_mod.prometheus_text().splitlines()
                    if l.startswith("ray_tpu_kda_pass_path_total{")
                    and 'pass="norm"' in l and f'seq="{S}"' in l)
        assert f'path="{path}"' in line, line
    metrics_mod._reset_for_tests()
