"""Flash attention's kernels that hold several tiles a grid step (PR 52) and
the backward's one pass (PR 54) do the pair's work, and the calls that keep
the pair trace the parent's program.  (Cut from ``tests/test_ops.py``,
PR 59.)
"""

import importlib
import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import flash_attention, reference_attention

from ops_cases import _geometry_counts

# ``ray_tpu.ops.attention`` the attribute is the function of that name.
attention_ops = importlib.import_module("ray_tpu.ops.attention")


# Several tiles a grid step (PR 52): in parts or not, causal, Sq, Sk, q_offset.
WALK_CASES = {
    "parts_causal": (True, True, 1024, 1024, 0),
    "parts_not_causal": (True, False, 512, 1024, 0),
    # the offset cuts a major block: its q rows see 3 of its 4 tiles
    "parts_offset_cuts_a_major_block": (True, True, 512, 1024, 384),
    "one_part_causal": (False, True, 1024, 1024, 0),
    "one_part_not_causal": (False, False, 512, 1024, 0),
    "one_part_offset_cuts_a_major_block": (False, True, 512, 1024, 384),
}


@pytest.mark.parametrize("case", WALK_CASES)
def test_tiles_a_step_are_the_same_work(case, monkeypatch):
    """A 192 / 128 call whose grid steps walk 2 and up to 8 tiles of a major
    block (the last is what ``_tiles`` picks: 8 of 8 a side, or 4 of 4) gives,
    bit for bit, what the same call gives at one tile a step: the result, dq,
    dk, dv (in parts: dq in both, dk and dv side by side, the one rotary
    head's share), and the reference's within the tolerances the call in
    parts is held to.  The geometry counter says ``tiles_a_step`` where it
    is not 1.  The causal squares' backward is the one pass (PR 54), which
    walks ``_BWD_WALK`` tiles a step at the most."""
    in_parts, causal, Sq, Sk, q_offset = WALK_CASES[case]
    H, Dn, Dr, Dv, block = 2, 128, 64, 128, 128
    ks = jax.random.split(jax.random.key(52), 5)
    q_n, q_r, kv, k_r, do = (
        jax.random.normal(key, shape) for key, shape in zip(ks, (
            (1, Sq, H, Dn), (1, H, Sq, Dr), (1, Sk, H, Dn + Dv),
            (1, 1, Sk, Dr), (1, Sq, H, Dv))))
    turn = lambda x: jnp.swapaxes(x, 1, 2)
    q = jnp.concatenate([turn(q_n), q_r], axis=-1)
    k = jnp.concatenate([turn(kv[..., :Dn]), jnp.repeat(k_r, H, axis=1)],
                        axis=-1)
    v = turn(kv[..., Dn:])
    kw = dict(causal=causal, q_offset=q_offset, scale=0.11)
    # ``_tiles``' own answer at tiles of 128 x 128, which the interpreter
    # walks in seconds: a block a call names is one a grid step.
    monkeypatch.setattr(attention_ops, "_BLOCK", block)
    flash = partial(flash_attention, interpret=True, **kw)

    def fwd_bwd(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return jax.tree.leaves((out, vjp(do if out.shape == do.shape
                                         else turn(do))))

    def call():
        if in_parts:
            return fwd_bwd(lambda q_n, q_r, kv, k_r: flash(
                (q_n, q_r), (kv, k_r), None), q_n, q_r, kv, k_r)
        return fwd_bwd(flash, q, k, v)

    got, walks = {}, ((1,), (2, 1), attention_ops._WALK)
    assert walks[-1] == (8, 4, 2, 1)
    for walk in walks:
        monkeypatch.setattr(attention_ops, "_WALK", walk)
        before = _geometry_counts()
        got[walk] = call()
        after = _geometry_counts()
        one_pass = causal and Sq == Sk and not q_offset
        for kernel in ("fwd", "bwd") if one_pass else ("fwd", "dq", "dkv"):
            name = f"flash_{kernel}_d192v128"
            new = {tags for tags, n in after[name].items()
                   if n > before.get(name, {}).get(tags, 0)}
            assert len(new) == 1, (name, new)
            tiles = min(walk[0], (Sk if kernel in ("fwd", "dq") else Sq)
                        // block)
            if kernel == "bwd":
                tiles = min(tiles, attention_ops._BWD_WALK)
            assert dict(new.pop()).get("tiles_a_step") == (
                None if tiles == 1 else str(tiles)), name
    for walk in walks[1:]:
        for a, b in zip(got[walks[0]], got[walk]):
            assert a.shape == b.shape
            assert (np.asarray(a) == np.asarray(b)).all(), walk

    want = fwd_bwd(partial(reference_attention, **kw), q, k, v)
    if in_parts:
        out, dq_n, dq_r, dkv, dk_r = got[walks[-1]]
        got_one = (turn(out), jnp.concatenate([turn(dq_n), dq_r], axis=-1),
                   turn(dkv[..., :Dn]), turn(dkv[..., Dn:]))
        np.testing.assert_allclose(
            dk_r, want[2][..., Dn:].sum(axis=1, keepdims=True), atol=2e-4)
        want = (want[0], want[1], want[2][..., :Dn], want[3])
    else:
        got_one = got[walks[-1]]
    for a, b in zip(got_one, want):
        np.testing.assert_allclose(a, b, atol=2e-4)


def _kernels_since(before):
    """{kernel, its head sizes and window off its name: its tags} of the
    geometry counter's series that grew since ``before``."""
    after = _geometry_counts()
    return {re.sub(r"_[dw]\d.*", "", name): dict(tags)
            for name in after for tags, n in after[name].items()
            if n > before.get(name, {}).get(tags, 0)}


# The one pass against the pair (PR 54): Dn, Dr, Dv, in parts, rows, the
# walk (``_WALK``; None: blocks the call names, one tile a step), dtype.
ONE_PASS_CASES = {
    "d128_rows_one_tile": (128, 0, 128, False, True, None, jnp.float32),
    "d128_rows_bf16": (128, 0, 128, False, True, None, jnp.bfloat16),
    "d128_head_major": (128, 0, 128, False, False, None, jnp.float32),
    "parts_one_tile": (128, 64, 128, True, True, (1,), jnp.float32),
    "parts_two_tiles": (128, 64, 128, True, True, (2, 1), jnp.float32),
    "parts_two_tiles_bf16": (128, 64, 128, True, True, (2, 1), jnp.bfloat16),
    "d192v128_one_part_two_tiles": (128, 64, 128, False, False, (2, 1),
                                    jnp.float32),
}


@pytest.mark.parametrize("case", ONE_PASS_CASES)
def test_one_pass_is_the_pairs_work(case, monkeypatch):
    """``flash_bwd``, dq added up beside dk / dv in the K-major walk, against
    the pair of the same call (``_DQ_ROW`` 0: no dq fits, so ``_tiles``
    keeps the pair): dk and dv (in parts dk, dv and the rotary head's
    share) bit for bit, dq to the tolerance of ``rows`` against head-major,
    and all against the reference's.  The geometry counter names the one
    kernel where the pair's two were."""
    Dn, Dr, Dv, in_parts, rows, walk, dtype = ONE_PASS_CASES[case]
    H, S, block = 2, 512, 128
    ks = jax.random.split(jax.random.key(54), 5)
    q_n, q_r, kv, k_r, do = (
        jax.random.normal(key, shape, dtype) for key, shape in zip(ks, (
            (2, S, H, Dn), (2, H, S, Dr), (2, S, H, Dn + Dv),
            (2, 1, S, Dr), (2, S, H, Dv))))
    turn = lambda x: jnp.swapaxes(x, 1, 2)
    q = jnp.concatenate([turn(q_n), q_r], axis=-1)
    k = jnp.concatenate([turn(kv[..., :Dn]), jnp.repeat(k_r, H, axis=1)],
                        axis=-1)
    v = kv[..., Dn:] if rows else turn(kv[..., Dn:])
    if walk is None:
        flash = partial(flash_attention, interpret=True, scale=0.11,
                        block_q=block, block_k=block)
    else:
        monkeypatch.setattr(attention_ops, "_BLOCK", block)
        monkeypatch.setattr(attention_ops, "_WALK", walk)
        flash = partial(flash_attention, interpret=True, scale=0.11)

    def fwd_bwd(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return jax.tree.leaves(vjp((do if out.shape == do.shape
                                    else turn(do)).astype(out.dtype)))

    def call():
        before = _geometry_counts()
        if in_parts:
            got = fwd_bwd(lambda q_n, q_r, kv, k_r: flash(
                (q_n, q_r), (kv, k_r), None), q_n, q_r, kv, k_r)
        else:
            got = fwd_bwd(partial(flash, rows=rows), q, k, v)
        return got, _kernels_since(before)

    one, kernels = call()
    assert sorted(kernels) == ["flash_bwd", "flash_fwd"]
    tiles = 1 if walk is None else min(walk[0], attention_ops._BWD_WALK,
                                       S // block)
    assert kernels["flash_bwd"].get("tiles_a_step") == (
        None if tiles == 1 else str(tiles))
    assert kernels["flash_bwd"]["scores"] == "kq"
    assert kernels["flash_bwd"].get("rows") == (
        "qkvo" if in_parts else "vo" if rows else None)
    monkeypatch.setattr(attention_ops, "_DQ_ROW", 0)
    pair, kernels = call()
    assert sorted(kernels) == ["flash_dkv", "flash_dq", "flash_fwd"]

    dq = 2 if in_parts else 1           # q's gradient comes first
    f32 = lambda x: np.asarray(x, np.float32)
    for a, b in zip(one[dq:], pair[dq:]):
        assert a.dtype == dtype and (f32(a) == f32(b)).all()
    for a, b in zip(one[:dq], pair[:dq]):
        assert a.dtype == dtype and a.shape == b.shape
        np.testing.assert_allclose(
            f32(a), f32(b), atol=1e-6 if dtype == jnp.float32 else 2e-2,
            rtol=1e-6 if dtype == jnp.float32 else 2e-2)

    want = fwd_bwd(partial(reference_attention, scale=0.11),
                   *(x.astype(jnp.float32) for x in (q, k, turn(v) if rows
                                                     else v)))
    if in_parts:
        dq_n, dq_r, dkv, dk_r = one
        one = (jnp.concatenate([turn(dq_n), dq_r], axis=-1),
               turn(dkv[..., :Dn]), turn(dkv[..., Dn:]))
        np.testing.assert_allclose(
            f32(dk_r), want[1][..., Dn:].sum(axis=1, keepdims=True),
            atol=2e-4 if dtype == jnp.float32 else 0.5, rtol=3e-2)
        want = (want[0], want[1][..., :Dn], want[2])
    elif rows:
        one = (*one[:2], turn(one[2]))
    for a, b in zip(one, want):
        b = np.asarray(b)
        np.testing.assert_allclose(
            f32(a), b, atol=2e-4 if dtype == jnp.float32
            else 3e-2 * np.abs(b).max(), rtol=3e-2)


# The one pass under a GROUP at a head size of 128 or under (PR 60): group,
# key heads, head size, rows, dtype, window.  A query head a grid row, its
# share of dk / dv leaving in float32 and summed over the group outside.
GROUPED_CASES = {
    "group2_d128": (2, 2, 128, False, jnp.float32, None),
    "group4_d128_rows": (4, 1, 128, True, jnp.float32, None),
    "group4_d128_bf16": (4, 2, 128, False, jnp.bfloat16, None),
    "group8_d128_rows_bf16": (8, 1, 128, True, jnp.bfloat16, None),
    "group16_d128": (16, 1, 128, False, jnp.float32, None),
    "group2_d64_bf16": (2, 1, 64, False, jnp.bfloat16, None),
    "group4_d64": (4, 2, 64, False, jnp.float32, None),
    "group8_d64_rows": (8, 1, 64, True, jnp.float32, None),
    "group16_d64_bf16": (16, 1, 64, False, jnp.bfloat16, None),
    # the band's lower edge crosses a tile, 96 back; one tile a step
    "group4_d128_window": (4, 1, 128, False, jnp.float32, 96),
    "group8_d64_window_bf16": (8, 1, 64, False, jnp.bfloat16, 96),
}


@pytest.mark.parametrize("case", GROUPED_CASES)
def test_one_pass_under_a_group_is_the_pairs_work(case, monkeypatch):
    """``flash_bwd`` on a grouped call at ``_tiles``' own geometry (tiles of
    128 x 128 here, four a grid step and one under a window) against the
    pair of the same call, which stacks the group and adds it up inside
    dk/dv (``_DQ_ROW`` 0: no dq fits): dq as the un-grouped cases hold it,
    dk and dv to the order of a float32 sum said in another order (the
    pair adds tile by tile over the stacked heads, the one pass head by
    head), in bfloat16 to the result's own rounding; and all three against
    the reference's.  The counter names the one kernel and its shares."""
    group, Hkv, D, rows, dtype, window = GROUPED_CASES[case]
    B, H, S, block = 1, group * Hkv, 512, 128
    ks = jax.random.split(jax.random.key(60), 4)
    turn = lambda x: jnp.swapaxes(x, 1, 2)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k, v = (jax.random.normal(key, (B, Hkv, S, D), dtype) for key in ks[1:3])
    do = jax.random.normal(ks[3], (B, H, S, D), dtype)
    monkeypatch.setattr(attention_ops, "_BLOCK", block)
    flash = partial(flash_attention, interpret=True, scale=0.11,
                    window=window, rows=rows)

    def call():
        before = _geometry_counts()
        _, vjp = jax.vjp(flash, q, k, turn(v) if rows else v)
        dq, dk, dv = vjp(turn(do) if rows else do)
        return (dq, dk, turn(dv) if rows else dv), _kernels_since(before)

    one, kernels = call()
    assert sorted(kernels) == ["flash_bwd", "flash_fwd"]
    tags = kernels["flash_bwd"]
    assert (tags["heads_a_step"], tags["shares"], tags["scores"]) == (
        "1", str(group), "kq")
    assert tags.get("tiles_a_step") == (None if window else "4")
    assert "shares" not in kernels["flash_fwd"]
    monkeypatch.setattr(attention_ops, "_DQ_ROW", 0)
    pair, kernels = call()
    assert sorted(kernels) == ["flash_dkv", "flash_dq", "flash_fwd"]
    # the pair stacks up to 8 of a group: 16 are two stacks a key head
    assert kernels["flash_dkv"].get("shares") == (
        "2" if group == 16 else None)

    f32 = lambda x: np.asarray(x, np.float32)
    for a, b in zip(one, pair):
        assert a.dtype == dtype and a.shape == b.shape
        np.testing.assert_allclose(
            f32(a), f32(b), atol=1e-5 if dtype == jnp.float32 else 2e-2,
            rtol=1e-5 if dtype == jnp.float32 else 2e-2)
    want = jax.vjp(partial(reference_attention, scale=0.11, window=window),
                   *(x.astype(jnp.float32) for x in (q, k, v)))[1](
                       do.astype(jnp.float32))
    for a, b in zip(one, want):
        b = np.asarray(b)
        np.testing.assert_allclose(
            f32(a), b, atol=2e-4 if dtype == jnp.float32
            else 3e-2 * np.abs(b).max(), rtol=3e-2)


# What a grouped call's backward traces (PR 60): H, Hkv, Sq, Sk, D, and the
# call's q_offset and window; True: the one pass.
GROUPED_TRACES = {
    "lfm2_32_8_d64": ((32, 8, 8192, 8192, 64, 0, None), True),
    "nemotron_32_2": ((32, 2, 8192, 8192, 128, 0, None), True),
    "trinity_32_4": ((32, 4, 8192, 8192, 128, 0, None), True),
    "trinity_32_4_window": ((32, 4, 8192, 8192, 128, 0, 2048), True),
    "mistral_32_8": ((32, 8, 4096, 4096, 128, 0, None), True),
    "group2": ((4, 2, 1024, 1024, 128, 0, None), True),
    # the shapes the rule leaves: the pair, the group stacked
    "offset": ((8, 2, 1024, 2048, 128, 1024, None), False),
    "rectangle": ((8, 2, 1024, 2048, 128, 0, None), False),
    "dq_over_the_row": ((8, 2, 16384, 16384, 128, 0, None), False),
    "window_no_group": ((2, 2, 8192, 8192, 128, 0, 2048), False),
    "window_over_128": ((10, 2, 8192, 8192, 192, 0, 2048), False),
}


@pytest.mark.parametrize("case", GROUPED_TRACES)
def test_what_a_grouped_calls_backward_traces(case):
    """The traced program of a grouped call's gradient at the cells' shapes
    names ``flash_bwd`` and no ``flash_dq`` / ``flash_dkv``, with each query
    head's dk / dv a float32 result summed outside; the shapes the rule
    leaves name the pair and no ``flash_bwd``."""
    (H, Hkv, Sq, Sk, D, q_offset, window), one_pass = GROUPED_TRACES[case]
    bf16 = partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    Dv = min(D, 128)
    fn = partial(flash_attention, interpret=True, q_offset=q_offset,
                 window=window)

    def backward(q, k, v):
        out, vjp = jax.vjp(fn, q, k, v)
        return vjp(jnp.ones_like(out))

    text = str(jax.make_jaxpr(backward)(
        bf16((1, H, Sq, D)), bf16((1, Hkv, Sk, D)), bf16((1, Hkv, Sk, Dv))))
    names = set(re.findall(r"name=flash_(fwd|dq|dkv|bwd)", text))
    assert names == ({"fwd", "bwd"} if one_pass else {"fwd", "dq", "dkv"})
    if one_pass:
        assert f"float32[{H},{Sk},{D}]" in text       # a share a query head
        assert f"f32[1,{Hkv},{H // Hkv},{Sk},{D}]" in text


# Calls the one pass leaves alone trace the parent's program (PR 54): the
# sha256 of ``jax.make_jaxpr``'s text of each call's backward as commit
# 760264c printed it (its length beside it), kernel bodies, block specs and
# names and all.  A PR that changes the pair's kernels writes these anew;
# one that means to leave them alone sees here that it did.
PAIR_JAXPRS = {
    "group4": ("f3343529f3e6f1ed", 38558),
    "group8": ("60fee1898bdb6232", 50054),
    "window": ("da0aaa9250294410", 28688),
    "offset": ("2eab12dc5e07136a", 28247),
    "not_causal": ("879aea9a74267ec8", 24065),
    "unequal_blocks": ("442cd8ba58ad63d8", 28909),
    "parts_offset": ("8399ae628a6c50bc", 33077),
    "eva": ("0e40cd0b7a49592a", 48075),
}


@pytest.mark.parametrize("case", PAIR_JAXPRS)
def test_calls_that_keep_the_pair_trace_the_parents_program(case,
                                                             monkeypatch):
    import hashlib
    import importlib

    # The forward rule's two names (PR 58) are two equations more and no
    # other change: with them off the text is commit 760264c's.
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"),
                        "checkpoint_name", lambda x, name: x)

    from ray_tpu.ops.eva import eva_attention
    bf16 = partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    flash = partial(flash_attention, block_q=128, block_k=128,
                    interpret=True)
    q, k_long = bf16((1, 2, 256, 128)), bf16((1, 2, 512, 128))
    if case.startswith("group"):
        # Since PR 60 a group takes the one pass where its row's dq fits;
        # where none fits the group is stacked in the pair, as it was.
        monkeypatch.setattr(attention_ops, "_DQ_ROW", 0)
        k = bf16((1, 8 // int(case[5:]), 256, 128))
        fn, args = flash, (bf16((1, 8, 256, 128)), k, k)
    elif case == "parts_offset":
        fn = lambda a, b, c, d: flash((a, b), (c, d), None, q_offset=256)
        args = (bf16((1, 256, 2, 128)), bf16((1, 2, 256, 64)),
                bf16((1, 512, 2, 256)), bf16((1, 1, 512, 64)))
    elif case == "eva":
        fn = lambda q, k, v, ks, vs: eva_attention(
            q, k, v, ks, vs, 256, 16, impl="flash_interpret", block_q=128,
            block_k=128)
        args = (k_long,) * 3 + (bf16((1, 2, 32, 128)),) * 2
    else:
        fn, args = {
            "window": (partial(flash, window=96), (q, q, q)),
            "offset": (partial(flash, q_offset=128), (q, k_long, k_long)),
            "not_causal": (partial(flash, causal=False), (q, q, q)),
            "unequal_blocks": (partial(flash, block_q=64), (q, q, q)),
        }[case]

    def backward(*a):
        out, vjp = jax.vjp(fn, *a)
        return vjp(jnp.ones_like(out))

    text = str(jax.make_jaxpr(backward)(*args))
    assert "flash_bwd" not in text
    assert (hashlib.sha256(text.encode()).hexdigest()[:16],
            len(text)) == PAIR_JAXPRS[case]
