"""What the xing4 model (Xing4.0's block) brought to the kernels: flash
attention with a value head size of its own, whole and in the parts the
projections write, and the compiled step's scopes on the ``jnp`` and the
kernel path (interpreted).  (Cut from ``tests/test_xing4.py``, PR 59.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import xing4
from ray_tpu.ops.attention import flash_attention, reference_attention

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


# ------------------------------------------------- flash at 192 / 128

def _qkv(D, Dv, B=1, H=2, Hkv=2, S=256, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, H, S, D)),
            jax.random.normal(ks[1], (B, Hkv, S, D)),
            jax.random.normal(ks[2], (B, Hkv, S, Dv)),
            jax.random.normal(ks[3], (B, H, S, Dv)))


@pytest.mark.parametrize("D,Dv,Hkv", [(192, 128, 2), (192, 128, 1),
                                      (128, 128, 2), (64, 128, 2)])
def test_flash_with_a_value_head_size_of_its_own(D, Dv, Hkv):
    """Forward and the three gradients in interpret mode against
    ``reference_attention``: at latent attention's 192 / 128, under a
    group, at a key narrower than the value, and unchanged at 128 / 128."""
    q, k, v, do = _qkv(D, Dv, Hkv=Hkv)
    scale = 0.11
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=scale, block_q=128, block_k=128,
        interpret=True)
    plain = lambda q, k, v: reference_attention(q, k, v, causal=True,
                                                scale=scale)
    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    assert out.shape == (1, 2, 256, Dv)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for g, w in zip(vjp(do), want_vjp(do)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)


def _parts(B=1, H=2, S=256, Dn=128, Dr=64, Dv=128, seed=0):
    """A call in parts as ``xing4._mla`` hands it: (q_n [B, S, H, Dn], q_r
    [B, H, S, Dr]), (kv [B, S, H, Dn + Dv], the ONE k_r [B, 1, S, Dr]) and
    the result's cotangent [B, S, H, Dv]."""
    ks = jax.random.split(jax.random.key(seed), 5)
    return ((jax.random.normal(ks[0], (B, S, H, Dn)),
             jax.random.normal(ks[1], (B, H, S, Dr))),
            (jax.random.normal(ks[2], (B, S, H, Dn + Dv)),
             jax.random.normal(ks[3], (B, 1, S, Dr))),
            jax.random.normal(ks[4], (B, S, H, Dv)))


def _concatenated(q, k):
    """The 192-wide operands the parts stand for, head-major: q, k with the
    one rotary key head under every head, and v."""
    (q_n, q_r), (kv, k_r) = q, k
    Dn, H = q_n.shape[-1], q_n.shape[2]
    turn = lambda x: jnp.swapaxes(x, 1, 2)
    return (jnp.concatenate([turn(q_n), q_r], axis=-1),
            jnp.concatenate([turn(kv[..., :Dn]),
                             jnp.repeat(k_r, H, axis=1)], axis=-1),
            turn(kv[..., Dn:]))


@pytest.mark.parametrize("B,H,S,Dv,blocks", [
    (1, 2, 256, 128, 128), (2, 2, 256, 128, 128), (1, 4, 128, 256, None)],
    ids=["192v128", "two_rows", "192v256_default_blocks"])
def test_flash_in_parts_is_the_reference_on_the_concatenated_operands(
        B, H, S, Dv, blocks):
    """The score product in the parts the projections write, in interpret
    mode against ``reference_attention`` on the concatenated operands: the
    result, dq in both parts, dk without position and dv side by side as
    ``kv`` came, and the ONE rotary key head's gradient summed over the
    query heads; with a second batch element (the shared head's index map
    takes the row's batch element and no head)."""
    q, k, do = _parts(B, H, S, Dv=Dv)
    scale = 0.11
    flash = lambda q, k: flash_attention(
        q, k, None, causal=True, scale=scale, block_q=blocks, block_k=blocks,
        interpret=True)

    def plain(q, k):
        return jnp.swapaxes(reference_attention(
            *_concatenated(q, k), causal=True, scale=scale), 1, 2)

    out, vjp = jax.vjp(flash, q, k)
    want, want_vjp = jax.vjp(plain, q, k)
    assert out.shape == (B, S, H, Dv)
    np.testing.assert_allclose(out, want, atol=2e-5)
    (dq_n, dq_r), (dkv, dk_r) = vjp(do)
    (wq_n, wq_r), (wkv, wk_r) = want_vjp(do)
    assert dk_r.shape == (B, 1, S, 64) and dkv.shape == (B, S, H, 128 + Dv)
    for got, w in ((dq_n, wq_n), (dq_r, wq_r), (dkv[..., :128],
                   wkv[..., :128]), (dkv[..., 128:], wkv[..., 128:]),
                   (dk_r, wk_r)):
        assert got.shape == w.shape
        np.testing.assert_allclose(got, w, atol=2e-4)


def test_parts_off_the_lane_tiles_and_on_the_reference_are_put_together():
    """A call in parts may always say what it holds: where a part is not
    whole lane tiles the call is put together and goes the 192-wide way,
    and ``attention``'s reference path takes the same call."""
    from ray_tpu.ops.attention import attention
    q, k, do = _parts(H=2, S=128, Dn=64, Dr=32, Dv=64)
    want = jnp.swapaxes(reference_attention(*_concatenated(q, k)), 1, 2)
    np.testing.assert_allclose(
        flash_attention(q, k, None, interpret=True), want, atol=2e-5)
    np.testing.assert_allclose(
        attention(q, k, None, impl="reference"), want, atol=2e-6)
    with pytest.raises(ValueError, match="a call in parts takes"):
        flash_attention(q, k, do, interpret=True)


def test_flash_names_and_counts_both_head_sizes(monkeypatch):
    """A 192 / 128 call says its sizes in its kernels' names and in the
    geometry counter's tags, and in parts the parts and which operands lay
    as rows too, under the same names; a 128 / 128 call says what it said
    before."""
    from ray_tpu.util import telemetry
    seen = []
    monkeypatch.setattr(telemetry, "inc",
                        lambda name, value=1.0, tags=None: seen.append(
                            (name, tags)))
    for D in (192, 128):
        q, k, v, do = _qkv(D, 128, S=128)
        jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, interpret=True) * do), argnums=(0, 1, 2))).lower(q, k, v)
    q, k, do = _parts(S=128)
    jax.jit(jax.grad(lambda q, k: jnp.sum(flash_attention(
        q, k, None, interpret=True) * do), argnums=(0, 1))).lower(q, k)
    tags = [t for name, t in seen
            if name == "ray_tpu_flash_step_geometry_total"]
    # a key head a query head on the causal square: the backward is the one
    # pass (PR 54), two kernels a call and not three
    wide, plain, parts = tags[:2], tags[2:4], tags[4:]
    names = [f"flash_{k}_d192v128" for k in ("fwd", "bwd")]
    assert [t["kernel"] for t in wide] == names
    assert all(t["d_qk"] == "192" and t["d_v"] == "128" for t in wide)
    assert not any("parts" in t or "rows" in t for t in wide + plain)
    assert [sorted(t) for t in plain] == [
        ["block_k", "block_q", "heads_a_step", "kernel", "scores"]] * 2
    assert [t["kernel"] for t in plain] == ["flash_fwd", "flash_bwd"]
    assert [t["kernel"] for t in parts] == names
    assert all(t["parts"] == "128+64" and t["rows"] == "qkvo"
               and t["d_qk"] == "192" and t["d_v"] == "128"
               and t["heads_a_step"] == "1" for t in parts)
    # but for the two new tags a call in parts counts what a 192-wide does
    assert [{k: v for k, v in t.items() if k not in ("parts", "rows")}
            for t in parts] == wide


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_compiled_step_names_the_scopes_the_benchmark_sums(path, monkeypatch):
    """``hc_stream_roofline``, ``hc_device_share`` and ``mtp_device_share``
    are what ``benchmark/scopes.py`` finds under ``block/hc`` and ``mtp`` in
    the compiled step's text, forward and backward alike; on the kernel
    path (interpreted here) every one of the five kernels' operations is
    under a pass's scope, the module's under ``mtp`` too."""
    from benchmark import scopes
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    from ray_tpu.util import telemetry
    seen = []
    monkeypatch.setattr(
        telemetry, "inc", lambda name, value=1.0, tags=None: seen.append(
            tags["path"]) if name == "ray_tpu_hc_path_total" else None)
    cfg = xing4.xing4_tiny(experts_held=4, held_start=4, remat=True,
                           layer_rows=1)
    if path == "kernel":
        cfg = cfg.replace(hidden=128, attention_impl="flash_interpret")
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh, learning_rate=1e-3)
    params, state = jax.eval_shape(init_fn, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "loss_mask")}
    names = list(scopes.op_names(step_fn.lower(params, state, batch)
                                 .compile().as_text()).values())
    assert seen and set(seen) == {path}
    paths = {scopes.scope_path(n) for n in names}
    by = {"scopes": dict.fromkeys(paths, 1.0)}
    for scope in ("block/hc/maps", "block/hc/collect", "block/hc/deposit",
                  "block/attn/mla", "block/moe/experts", "mtp",
                  "mtp/block/hc", "mtp/block/moe"):
        assert scopes.seconds_under(by, scope) > 0, scope
    assert any("mtp" in n and "transpose(jvp(" in n for n in names)
    assert not any("jvp" in p or "while" in p for p in paths)
    if path == "kernel":
        for kernel, scope in (("hc_collect_n4", "collect"),
                              ("hc_pre_bwd_n4", "collect"),
                              ("hc_collect_bwd_n4", "collect"),
                              ("hc_deposit_n4", "deposit"),
                              ("hc_deposit_bwd_n4", "deposit")):
            mine = [n for n in names if f"/{kernel}/" in n]
            assert mine and all(f"block/hc/{scope}/{kernel}/" in n
                                for n in mine), kernel
            assert any("/mtp/" in n for n in mine), kernel
            assert any("transpose(jvp(" in n for n in mine), kernel
            # Forward (under the jvp), and recomputed under the remat.
            if not kernel.endswith("bwd_n4"):
                assert any("rematted_computation" in n for n in mine), kernel
                assert any("transpose(" not in n for n in mine), kernel
