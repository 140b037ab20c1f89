"""Numerics tests for the ops layer on the 8-device CPU mesh: the norms, the
rotary tables and kernels, ring and Ulysses attention, mesh sharding,
PolyNorm.  Flash attention: ``tests/test_ops_flash*.py``.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (
    apply_rope, reference_attention, rms_norm, rope_frequencies,
    rope_lane_tables, rotate_heads)
from ray_tpu.ops.ring_attention import ring_attention_sharded
from ray_tpu.ops.ulysses import ulysses_attention_sharded
from ray_tpu.parallel import MeshSpec, build_mesh

from ops_cases import _counted, _norm_paths, _qkv


def test_devices_available():
    assert len(jax.devices()) == 8


class TestRmsNorm:
    def test_matches_manual(self):
        x = jax.random.normal(jax.random.key(0), (4, 16), jnp.float32)
        w = jnp.ones(16) * 1.5
        out = rms_norm(x, w)
        expect = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * 1.5
        np.testing.assert_allclose(out, expect, rtol=1e-5)

    def test_bf16_io(self):
        x = jax.random.normal(jax.random.key(1), (4, 16)).astype(jnp.bfloat16)
        assert rms_norm(x, jnp.ones(16)).dtype == jnp.bfloat16


def _norm_as_it_was(x, w, eps=1e-5):
    """``rms_norm`` before PR 39: the chain and nothing else."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


#: (shape, dtype, the platform JAX reports is a TPU, the path taken)
NORM_CASES = [
    ((1, 256, 128), jnp.float32, True, "row_major"),    # EvaByte's: one row
    ((2, 96, 192), jnp.float32, True, "row_major"),     # any width and rows
    ((64, 128), jnp.float32, True, "row_major"),        # a decode step's
    ((2, 128, 256), jnp.bfloat16, True, "xla"),     # every other model's
    ((4, 16, 64, 128), jnp.bfloat16, True, "xla"),      # a q / k norm
    ((2, 128, 256), jnp.float32, False, "xla"),         # no TPU
    ((128,), jnp.float32, True, "xla"),                 # nothing to lay out
]


@pytest.mark.parametrize("shape,dtype,on_tpu,path", NORM_CASES)
def test_norm_pins_a_float32_input_row_major_on_a_tpu(monkeypatch, shape,
                                                      dtype, on_tpu, path):
    """The dtype and the backend decide: a float32 input on a TPU carries a
    layout constraint into the program, every other call lowers to the
    text it lowered to; the values and both gradients are the chain's, bit
    for bit, either way; the counter says which way the call went."""
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.attention"), "_on_tpu",
        lambda: on_tpu)
    ks = jax.random.split(jax.random.key(len(shape)), 3)
    x = (3.0 * jax.random.normal(ks[0], shape, jnp.float32)).astype(dtype)
    w = 1.0 + 0.2 * jax.random.normal(ks[1], shape[-1:], jnp.float32)
    dy = jax.random.normal(ks[2], shape, jnp.float32).astype(dtype)
    rows = str(int(np.prod(shape[:-1])))
    before = _norm_paths()
    got, vjp = jax.vjp(lambda x, w: rms_norm(x, w, 1e-5), x, w)
    assert _norm_paths().get((path, rows), 0) == \
        before.get((path, rows), 0) + 1
    want, vjp0 = jax.vjp(_norm_as_it_was, x, w)
    for a, b in zip((got, *vjp(dy)), (want, *vjp0(dy))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    def program(x, w):
        return rms_norm(x, w, 1e-5)

    text = jax.jit(program).lower(x, w).as_text()
    if path == "row_major":
        minor_to_major = list(range(len(shape)))[::-1]
        assert text.count("@LayoutConstraint") == 1
        assert f"result_layouts = [dense<{minor_to_major}>" in text, text
    else:
        assert text == jax.jit(_norm_as_it_was).lower(x, w).as_text().replace(
            "_norm_as_it_was", "program")


class TestRope:
    def test_norm_preserved(self):
        cos, sin = rope_frequencies(32, 128)
        x = jax.random.normal(jax.random.key(0), (2, 4, 64, 32))
        out = apply_rope(x, cos, sin)
        np.testing.assert_allclose(np.linalg.norm(out, axis=-1),
                                   np.linalg.norm(x, axis=-1), rtol=1e-4)

    def test_position_zero_identity(self):
        cos, sin = rope_frequencies(16, 8)
        x = jax.random.normal(jax.random.key(0), (1, 1, 1, 16))
        np.testing.assert_allclose(apply_rope(x, cos, sin), x, rtol=1e-5)

    def test_explicit_positions_match_implicit(self):
        cos, sin = rope_frequencies(16, 64)
        x = jax.random.normal(jax.random.key(0), (1, 2, 10, 16))
        pos = jnp.arange(10)
        np.testing.assert_allclose(apply_rope(x, cos, sin, positions=pos),
                                   apply_rope(x, cos, sin), rtol=1e-5)


def _rope_paths():
    """ray_tpu_rope_path_total as {(path, rows, heads): count}."""
    return _counted("ray_tpu_rope_path_total", ("path", "rows", "heads"))


def _rotate_both_ways(x, g, positions=None, theta=1e6, **kw):
    """(rotate_heads, today's split formulation) on x [B, S, H, D]: values
    and the gradient under the cotangent g [B, H, S, D]."""
    D = x.shape[-1]
    cos, sin = rope_frequencies(D, 2048, theta)
    tables = rope_lane_tables(D, 2048, theta)
    got, vjp = jax.vjp(lambda x: rotate_heads(x, *tables, positions, **kw), x)
    want, vjp0 = jax.vjp(lambda x: apply_rope(
        jnp.swapaxes(x, 1, 2), cos, sin, positions), x)
    return (got, vjp(g)[0]), (want, vjp0(g)[0])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("B,H,S", [
    (2, 16, 1024),      # yi-coder / ouro: 16 query and 16 key heads
    (2, 32, 512),       # mistral's 32 query heads ...
    (2, 8, 512),        # ... and 8 key heads
    (1, 32, 1024),      # trinity: a row at a time, 32 query heads ...
    (1, 4, 1024),       # ... and 4 key heads
    (1, 16, 640),       # a sequence the row block does not divide
])
def test_rotate_and_place_kernel_is_the_split_rotation(B, H, S, dtype):
    """The rotate-and-place kernel pair (interpret mode) against
    ``apply_rope`` behind a transpose: values and, under a random
    cotangent, the gradient its transposed kernel hands back; float32
    inside and one rounding out, as the split formulation has it."""
    ks = jax.random.split(jax.random.key(H * S), 2)
    x = jax.random.normal(ks[0], (B, S, H, 128), jnp.float32).astype(dtype)
    g = jax.random.normal(ks[1], (B, H, S, 128), jnp.float32).astype(dtype)
    before = _rope_paths()
    got, want = _rotate_both_ways(x, g, interpret=True)
    # One rounding each: the two may differ by an ulp of the result's type
    # where a product and a sum were contracted differently.
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22
    for a, b, name, shape in zip(got, want, ("out", "dx"),
                                 ((B, H, S, 128), (B, S, H, 128))):
        assert a.dtype == dtype and a.shape == shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0, err_msg=name,
                                   atol=ulp * np.abs(b).max())
    tile = ("kernel", str(min(S, 512)), str(min(H, 8)))
    assert _rope_paths().get(tile, 0) == before.get(tile, 0) + 1


@pytest.mark.parametrize("D,positions", [(128, True), (32, False)])
def test_rotate_heads_falls_back_to_the_split_rotation(D, positions):
    """``positions`` given (a sequence shard's) or a head size that is
    neither the lane tile nor half of it (64 has the kernel since PR 47:
    tests/test_lfm2.py): ``apply_rope`` behind a transpose, bit for bit,
    although the kernel was asked for."""
    ks = jax.random.split(jax.random.key(D), 2)
    x = jax.random.normal(ks[0], (2, 96, 4, D), jnp.float32)
    g = jax.random.normal(ks[1], (2, 4, 96, D), jnp.float32)
    pos = jnp.arange(96)[::-1] + 7 if positions else None
    before = _rope_paths()
    got, want = _rotate_both_ways(x, g, pos, interpret=True)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _rope_paths().get(("xla", "96", "4"), 0) == \
        before.get(("xla", "96", "4"), 0) + 1


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        mesh = build_mesh(MeshSpec(sp=8))
        q, k, v = _qkv(jax.random.key(0), B=1, H=4, S=256, D=16)
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_gqa(self):
        mesh = build_mesh(MeshSpec(sp=4, dp=2))
        q, k, v = _qkv(jax.random.key(1), B=2, H=8, Hkv=2, S=128, D=16)
        out = ring_attention_sharded(q, k, v, mesh, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


class TestUlysses:
    def test_matches_reference(self):
        mesh = build_mesh(MeshSpec(sp=8))
        q, k, v = _qkv(jax.random.key(0), B=1, H=8, S=128, D=16)
        out = ulysses_attention_sharded(q, k, v, mesh, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


class TestMeshSharding:
    def test_mesh_spec_resolution(self):
        spec = MeshSpec(dp=-1, tp=2).resolved(8)
        assert spec.dp == 4 and spec.tp == 2

    def test_mesh_build_axes(self):
        mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        assert dict(zip(mesh.axis_names, mesh.devices.shape))["dp"] == 2
        assert mesh.devices.size == 8

    def test_logical_to_pspec(self):
        from ray_tpu.parallel import default_rules, logical_to_pspec
        p = logical_to_pspec(("batch", "seq", "embed"), default_rules())
        assert p[0] == ("dp", "fsdp")
        # embed maps to fsdp but fsdp already shards batch -> dropped
        assert p[2] is None

    def test_shard_pytree(self):
        from ray_tpu.parallel import default_rules, shard_pytree
        mesh = build_mesh(MeshSpec(dp=4, tp=2))
        tree = {"w": jnp.zeros((8, 16)), "b": jnp.zeros((16,))}
        logical = {"w": ("embed", "mlp"), "b": ("mlp",)}
        sharded = shard_pytree(tree, logical, mesh)
        assert sharded["w"].sharding.spec[1] == "tp"


# ------------------------------------------------------------- PolyNorm

def test_poly_norm_is_the_formula_and_so_are_its_gradients():
    """``s (p0 x^3/rms(x^3) + p1 x^2/rms(x^2) + p2 x/rms(x) + clip(p3))``
    against the formula written out in float64 numpy, and the gradients of x
    and of the four numbers against central differences of it (1e-6 steps
    in float64: 1e-6 relative).  A bias past its clamp has no gradient;
    bfloat16 in gives bfloat16 out, computed in float32."""
    from ray_tpu.ops.norms import poly_norm
    rng = np.random.default_rng(5)
    x = rng.normal(size=(6, 40))
    eps, scale, clamp = 1e-5, 0.5, 0.5

    def formula(x, p):
        n = lambda y: y / np.sqrt(np.mean(y * y, -1, keepdims=True) + eps)
        return scale * (p[0] * n(x ** 3) + p[1] * n(x ** 2) + p[2] * n(x)
                        + np.clip(p[3], -clamp, clamp))

    for p in (np.array([0.7, -0.4, 1.1, 0.3]), np.array([0.2, 0.9, -1.3, 0.8])):
        got = poly_norm(jnp.asarray(x, jnp.float32),
                        jnp.asarray(p, jnp.float32), scale, clamp, eps)
        np.testing.assert_allclose(got, formula(x, p), atol=2e-6)
        c = rng.normal(size=x.shape)        # the cotangent
        gx, gp = jax.grad(lambda x, p: jnp.sum(poly_norm(
            x, p, scale, clamp, eps) * c), argnums=(0, 1))(
                jnp.asarray(x, jnp.float32), jnp.asarray(p, jnp.float32))
        f = lambda x, p: np.sum(formula(x, p) * c)
        want_p = [(f(x, p + h) - f(x, p - h)) / 2e-6
                  for h in 1e-6 * np.eye(4)]
        np.testing.assert_allclose(gp, want_p, rtol=2e-4, atol=2e-5)
        assert (float(gp[3]) == 0.0) == (abs(p[3]) > clamp)
        at = (2, 7)
        h = np.zeros_like(x)
        h[at] = 1e-6
        np.testing.assert_allclose(
            gx[at], (f(x + h, p) - f(x - h, p)) / 2e-6, rtol=2e-4, atol=2e-5)
    low = poly_norm(jnp.asarray(x, jnp.bfloat16), jnp.asarray(p, jnp.bfloat16),
                    scale, clamp, eps)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(jnp.float32), formula(
        np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
                   np.float64),
        np.asarray(jnp.asarray(p, jnp.bfloat16).astype(jnp.float32),
                   np.float64)), atol=2e-2)


def test_an_activation_with_weights_gets_their_gradient_through_the_experts(
        monkeypatch):
    """``dropless_experts`` with ``activation="poly_norm"`` and its four
    numbers: the result and the gradients of the rows, the experts' weights
    and PolyNorm's numbers are those of a plain loop over the held experts;
    rows of the buffer that no group holds (made NaN here on their way out
    of every grouped product, as the Pallas kernels may leave them) reach
    neither the result nor any gradient."""
    from ray_tpu.ops import moe
    from ray_tpu.ops.norms import poly_norm
    T, E, M, X, k = 64, 16, 24, 8, 2
    ks = jax.random.split(jax.random.key(9), 6)
    x = jax.random.normal(ks[0], (T, E))
    router = jax.random.normal(ks[1], (E, X))
    w = {"gate": jax.random.normal(ks[2], (4, E, M)) / 4,
         "up": jax.random.normal(ks[3], (4, E, M)) / 4,
         "down": jax.random.normal(ks[4], (4, M, E)) / 4,
         "p": jnp.asarray([0.6, -0.5, 0.9, 0.2])}
    routing = moe.sigmoid_routing(x, router, jnp.zeros((X,)), k)
    kw = {"scale": 0.5, "clamp": 0.5, "eps": 1e-5}
    real = moe.grouped_matmul

    def holed(lhs, rhs, group_sizes, **more):
        out = real(lhs, rhs, group_sizes, **more)
        live = (jnp.arange(out.shape[0]) < jnp.sum(group_sizes))[:, None]
        return jnp.where(live, out, jnp.nan)

    monkeypatch.setattr(moe, "grouped_matmul", holed)

    def got(x, w):
        out, _ = moe.dropless_experts(
            x, routing, w["gate"], w["up"], w["down"], held_start=2,
            activation="poly_norm", act_weights={"p": w["p"], **kw})
        return out

    def want(x, w):
        out = jnp.zeros_like(x)
        for e in range(4):
            coef = jnp.sum(jnp.where(routing.expert_index == 2 + e,
                                     routing.weights, 0.0), axis=-1)
            h = poly_norm(x @ w["gate"][e], w["p"], **kw) * (x @ w["up"][e])
            out = out + coef[:, None] * (h @ w["down"][e])
        return out

    np.testing.assert_allclose(got(x, w), want(x, w), atol=1e-5)
    c = jax.random.normal(ks[5], x.shape)
    g = jax.grad(lambda x, w: jnp.sum(got(x, w) * c), argnums=(0, 1))(x, w)
    gw = jax.grad(lambda x, w: jnp.sum(want(x, w) * c), argnums=(0, 1))(x, w)
    for a, b in zip(jax.tree.leaves(gw), jax.tree.leaves(g)):
        assert bool(jnp.all(jnp.isfinite(b)))
        np.testing.assert_allclose(b, a, atol=2e-5, rtol=1e-4)
