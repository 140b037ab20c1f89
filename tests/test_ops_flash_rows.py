"""Flash attention with v and the result where the projections leave them
(PR 49), through the dispatcher and a llama layer, and a call in parts with
a group and a window (PR 57).  (Cut from ``tests/test_ops.py``, PR 59.)
"""

import importlib
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (
    attention, flash_attention, reference_attention, rope_lane_tables)

from ops_cases import _geometry_counts, _qkv

# ``ray_tpu.ops.attention`` the attribute is the function of that name.
attention_ops = importlib.import_module("ray_tpu.ops.attention")


# v and the result where the projections leave them (PR 49): B, H, Hkv, D,
# Dv, window, causal.
ROWS_CASES = {
    # Yi's and Ouro's layer call, Mistral's, Trinity's window layers.
    "no_group_16": (1, 16, 16, 128, 128, None, True),
    "group4": (1, 8, 2, 128, 128, None, True),
    "group8": (1, 8, 1, 128, 128, None, True),
    "group8_window": (1, 8, 1, 128, 128, 96, True),
    "not_causal": (1, 2, 2, 128, 128, None, False),
    # a group wider than a step: dk / dv per step's heads, summed outside
    "group16": (1, 16, 1, 128, 128, None, True),
    # several rows a call: a grid row's batch element and lane-block
    "two_rows_no_group": (2, 2, 2, 128, 128, None, True),
    "three_rows_group4_window": (3, 8, 2, 128, 128, 96, True),
    "two_rows_group16": (2, 16, 1, 128, 128, None, True),
    # values twice as wide as a tile: a head is two lane tiles of a row
    "values_256": (1, 2, 1, 128, 256, None, True),
    # head sizes whose lanes do not fall on tile edges in [B, S, H * D]
    # (LFM2's 64; latent attention's 192 / 128): turned at the edge, and
    # the kernels take today's head-major specs.
    "falls_back_d64": (1, 4, 2, 64, 64, None, True),
    "falls_back_d192v128": (1, 2, 2, 192, 128, None, True),
}


@pytest.mark.parametrize("case", ROWS_CASES)
def test_values_where_the_projections_leave_them(case):
    """``rows``: forward and every gradient of a call whose v and result lie
    as [B, S, heads, Dv] equal the head-major call's, which runs the same
    kernel bodies, and ``reference_attention``'s; the geometry counter says
    which kernels took them so, and says nothing where the shapes fell back
    to the head-major specs."""
    B, H, Hkv, D, Dv, window, causal = ROWS_CASES[case]
    S = 128
    ks = jax.random.split(jax.random.key(21), 4)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, Dv))
    do = jax.random.normal(ks[3], (B, H, S, Dv))

    def fwd_bwd(fn, rows=False):
        """(out, dq, dk, dv) of ``fn``, head-major whatever it takes."""
        turn = (lambda x: jnp.swapaxes(x, 1, 2)) if rows else (lambda x: x)
        out, vjp = jax.vjp(fn, q, k, turn(v))
        dq, dk, dv = vjp(turn(do))
        return turn(out), dq, dk, turn(dv)

    flash = partial(flash_attention, causal=causal, window=window,
                    block_q=64, block_k=64, interpret=True)
    before = _geometry_counts()
    got = fwd_bwd(partial(flash, rows=True), rows=True)
    after = _geometry_counts()
    head_major = fwd_bwd(flash)
    want = fwd_bwd(partial(reference_attention, causal=causal,
                           window=window))
    for a, b, c, x, name in zip(got, head_major, want, (do, q, k, v),
                                ("out", "dq", "dk", "dv")):
        assert a.shape == x.shape, name
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6, err_msg=name)
        np.testing.assert_allclose(a, c, atol=5e-4, rtol=1e-3, err_msg=name)

    engaged = D % 128 == 0 and Dv % 128 == 0
    # a head a grid step on the causal square: the one pass (PR 54)
    one_pass = causal and attention_ops._tiles(
        "bwd", S, S, max(D, Dv), H // Hkv, window) is not None
    for kernel in ("fwd", "bwd") if one_pass else ("fwd", "dq", "dkv"):
        name = attention_ops._kernel_name(f"flash_{kernel}", window, D, Dv)
        new = {tags for tags, n in after[name].items()
               if n > before.get(name, {}).get(tags, 0)}
        assert len(new) == 1, (name, new)
        assert dict(new.pop()).get("rows") == ("vo" if engaged else None)


def test_dispatcher_turns_rows_for_the_reference():
    """``attention`` off the TPU: the reference takes head-major arrays, so
    a call that says ``rows`` has v turned at the edge and its result
    back."""
    q, k, v = _qkv(jax.random.key(22), H=4, Hkv=2, S=32)
    out = attention(q, k, jnp.swapaxes(v, 1, 2), rows=True)
    np.testing.assert_allclose(jnp.swapaxes(out, 1, 2),
                               reference_attention(q, k, v), atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_no_transpose_beside_the_kernels_in_a_llama_layer(monkeypatch):
    """The gradient of ``llama.attention_branch`` at a head size of 128:
    between the projections and the six kernels (the rotary pair forward
    and back for q and k, flash forward and the backward's one pass, which
    serves a group since PR 60) nothing q-sized is
    transposed: the rotary kernels place q and k, and flash reads v and
    ``do`` and writes ``out`` and ``dv`` as the projections hold them."""
    from ray_tpu.models import llama
    from ray_tpu.ops.rope import rope_lane_tables
    from ray_tpu.parallel import mesh
    monkeypatch.setattr(mesh, "_GLOBAL_MESH", None)  # rows on ONE device
    cfg = llama.LlamaConfig(vocab_size=64, hidden=256, layers=1, heads=4,
                            kv_heads=2, head_dim=128, mlp_dim=256,
                            max_seq_len=128, dtype=jnp.float32,
                            attention_impl="flash_interpret")
    layer = jax.tree.map(lambda x: x[0], llama.init_params(
        cfg, jax.random.key(0))["blocks"])
    cos, sin = rope_lane_tables(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    h = jax.random.normal(jax.random.key(1), (2, 128, cfg.hidden))

    def loss(h, layer):
        return jnp.sum(llama.attention_branch(cfg, cos, sin, None, h, layer))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, layer)
    kernels, turned = [], []
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            kernels.append(eqn.params["name"] if "name" in eqn.params
                           else eqn.params["name_and_src_info"].name)
        elif eqn.primitive.name == "transpose":
            shape = eqn.invars[0].aval.shape
            if len(shape) == 4 and shape[-1] == cfg.head_dim:
                turned.append(shape)
    assert sorted(kernels) == sorted(
        ["rope_to_heads"] * 2 + ["rope_from_heads"] * 2
        + ["flash_fwd", "flash_bwd"]), kernels
    assert not turned, turned


def test_llama_layer_is_the_same_in_both_arrangements(monkeypatch):
    """``llama.attention_branch`` with v and the result as rows (one
    device) and head-major (a mesh): the same result and gradients."""
    from ray_tpu.models import llama
    from ray_tpu.ops.rope import rope_lane_tables
    from ray_tpu.parallel import mesh
    monkeypatch.setattr(mesh, "_GLOBAL_MESH", None)
    cfg = llama.LlamaConfig(vocab_size=64, hidden=256, layers=1, heads=4,
                            kv_heads=2, head_dim=128, mlp_dim=256,
                            max_seq_len=64, dtype=jnp.float32,
                            attention_impl="flash_interpret")
    assert llama._values_as_rows(cfg)
    layer = jax.tree.map(lambda x: x[0], llama.init_params(
        cfg, jax.random.key(0))["blocks"])
    cos, sin = rope_lane_tables(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    h = jax.random.normal(jax.random.key(1), (2, 64, cfg.hidden))

    def grads():
        return jax.value_and_grad(lambda h, layer: jnp.sum(jnp.sin(
            llama.attention_branch(cfg, cos, sin, None, h, layer))),
            argnums=(0, 1))(h, layer)

    rows = grads()
    monkeypatch.setattr(llama, "_values_as_rows", lambda cfg: False)
    for a, b in zip(jax.tree.leaves(rows), jax.tree.leaves(grads())):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# ------------------------------------------- a call in parts with a group
# (PR 57: latent keys decompressed into fewer key heads than query heads)

def _grouped_parts(group, Hkv=2, S=256, seed=21, dtype=jnp.float32):
    H = Hkv * group
    ks = jax.random.split(jax.random.key(seed), 5)
    return ((jax.random.normal(ks[0], (1, S, H, 128), dtype),
             jax.random.normal(ks[1], (1, H, S, 64), dtype)),
            (jax.random.normal(ks[2], (1, S, Hkv, 256), dtype),
             jax.random.normal(ks[3], (1, 1, S, 64), dtype)),
            jax.random.normal(ks[4], (1, S, H, 128), dtype))


@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("group", [5, 1])
def test_a_call_in_parts_with_a_group_and_a_window(group, window):
    """``kv`` [B, Sk, Hkv, Dn + Dv] under H = group x Hkv query heads: query
    head h reads key head h // group; forward and all four gradients (dq in
    both parts, dk and dv side by side added up over a group's heads in
    float32, the one rotary head's over all heads) against the reference on
    the operands put together, at 128 x 128 tiles over 256 tokens, so that
    the causal diagonal and the band's lower edge (96 back) each cross a
    tile: the one pass without a window, the pair with one.  Float32 on
    both sides: 2e-5 is the order of the sums."""
    q, k, do = _grouped_parts(group)

    def both(fn):
        out, vjp = jax.vjp(fn, q, k)
        return (out, *jax.tree.leaves(vjp(do)))

    got = both(lambda q, k: flash_attention(
        q, k, None, interpret=True, window=window, block_q=128, block_k=128))
    want = both(lambda q, k: attention(q, k, None, impl="reference",
                                       window=window))
    assert got[0].shape == (1, 256, 2 * group, 128)
    for a, b, name in zip(want, got, ("o", "dq_n", "dq_r", "dkv", "dk_r")):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(b, a, atol=2e-5, rtol=1e-4, err_msg=name)


def test_a_call_in_parts_at_the_geometry_tiles_picks_for_a_band(monkeypatch):
    """The windowed, grouped call with no blocks named: ``_tiles``' own
    answer for a window narrower than a block (a group's heads stacked in a
    step where it says so), in bfloat16 against the reference in float32 on
    the very inputs the kernels saw; the kernels' names carry the window
    and both head sizes, and the counter the group's geometry."""
    q, k, do = _grouped_parts(5, S=512, dtype=jnp.bfloat16)
    before = _geometry_counts()
    out, vjp = jax.vjp(lambda q, k: flash_attention(
        q, k, None, interpret=True, window=128), q, k)
    got = (out, *jax.tree.leaves(vjp(do)))
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    ref_out, ref_vjp = jax.vjp(lambda q, k: attention(
        q, k, None, impl="reference", window=128), f32(q), f32(k))
    want = (ref_out, *jax.tree.leaves(ref_vjp(f32(do))))
    for a, b, name in zip(want, got, ("o", "dq_n", "dq_r", "dkv", "dk_r")):
        scale = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(b.astype(jnp.float32), a,
                                   atol=2e-2 * scale, err_msg=name)
    after = _geometry_counts()
    new = {kernel for kernel in after if after[kernel] != before.get(kernel)}
    assert new == {"flash_fwd_d192v128_w128", "flash_dq_d192v128_w128",
                   "flash_dkv_d192v128_w128"}
    for kernel in new:
        # (the tags whose count rose: a file that ran before in this worker
        # may have counted the same geometry)
        tags = dict(next(t for t, n in after[kernel].items()
                         if n != before.get(kernel, {}).get(t)))
        assert tags["parts"] == "128+64" and tags["rows"] == "qkvo"
        want_t = attention_ops._tiles(kernel.split("_")[1], 512, 512, 192,
                                      5, 128, 64)
        assert (int(tags["block_q"]), int(tags["block_k"]),
                int(tags["heads_a_step"])) == want_t[:3]


def test_a_group_that_does_not_divide_the_heads_is_refused():
    q, k, _ = _grouped_parts(5)
    with pytest.raises(ValueError, match="H % Hkv == 0"):
        flash_attention((q[0][:, :, :9], q[1][:, :9]), k, None,
                        interpret=True)
