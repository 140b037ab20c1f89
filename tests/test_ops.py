"""Numerics tests for the ops layer on the 8-device CPU mesh."""

import importlib
import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (apply_rope, attention, flash_attention,
                         reference_attention, ring_attention,
                         rms_norm, rope_frequencies, rope_lane_tables,
                         rotate_heads)
from ray_tpu.ops.attention import (DIAGONAL, EMPTY, FIRST, INTERIOR, KI,
                                   KIND, LAST, QI, block_schedule)
from ray_tpu.ops.ring_attention import ring_attention_sharded
from ray_tpu.ops.ulysses import ulysses_attention_sharded
from ray_tpu.parallel import MeshSpec, build_mesh

# ``ray_tpu.ops.attention`` the attribute is the function of that name.
attention_ops = importlib.import_module("ray_tpu.ops.attention")


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


def _counted(name, keys):
    """A counter of the catalog as {its tags' values under ``keys``: count}."""
    from ray_tpu.util import metrics
    _by_name, acc = metrics._aggregate_snapshots()
    return {tuple(dict(tags)[k] for k in keys): value
            for tags, value in acc.get(name, {}).values()}


def _norm_paths():
    """ray_tpu_norm_path_total as {(path, rows): count}."""
    return _counted("ray_tpu_norm_path_total", ("path", "rows"))


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


def _qkv(key, B=2, H=4, Hkv=None, S=128, D=32, dtype=jnp.float32):
    Hkv = Hkv or H
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, H, S, D), dtype),
            jax.random.normal(ks[1], (B, Hkv, S, D), dtype),
            jax.random.normal(ks[2], (B, Hkv, S, D), dtype))


class TestFlashAttention:
    def test_matches_reference_causal(self):
        q, k, v = _qkv(jax.random.key(0))
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=64,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    def test_matches_reference_noncausal(self):
        q, k, v = _qkv(jax.random.key(1), S=64)
        ref = reference_attention(q, k, v, causal=False)
        out = flash_attention(q, k, v, causal=False, block_q=32,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    def test_gqa(self):
        q, k, v = _qkv(jax.random.key(2), H=8, Hkv=2, S=64)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=32,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    def test_dispatcher_cpu_fallback(self):
        q, k, v = _qkv(jax.random.key(3), S=32)
        out = attention(q, k, v)  # on CPU -> reference path
        np.testing.assert_allclose(out, reference_attention(q, k, v),
                                   atol=1e-6)

    def test_multi_k_block_online_softmax(self):
        # block_k < Sk exercises the m/l/acc carry across K blocks.
        q, k, v = _qkv(jax.random.key(4), S=128)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=64,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_backward_matches_reference(self, causal):
        q, k, v = _qkv(jax.random.key(5), S=128)
        do = jax.random.normal(jax.random.key(6), q.shape)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * do)

        ref_fn = loss(lambda q, k, v: reference_attention(
            q, k, v, causal=causal))
        fl_fn = loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=64, interpret=True))
        gr = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(fl_fn, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gr, gf, ("dq", "dk", "dv")):
            np.testing.assert_allclose(b, a, atol=5e-4, rtol=1e-3,
                                       err_msg=name)

    def test_backward_gqa_offset(self):
        # GQA group-sum of dk/dv plus a ring-style q_offset.
        B, H, Hkv, Sq, Sk, D = 1, 4, 2, 64, 128, 32
        ks = jax.random.split(jax.random.key(7), 4)
        q = jax.random.normal(ks[0], (B, H, Sq, D))
        k = jax.random.normal(ks[1], (B, Hkv, Sk, D))
        v = jax.random.normal(ks[2], (B, Hkv, Sk, D))
        do = jax.random.normal(ks[3], (B, H, Sq, D))

        gr = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
            q, k, v, causal=True, q_offset=64) * do), argnums=(0, 1, 2))(
                q, k, v)
        gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=32, block_k=64, q_offset=64,
            interpret=True) * do), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gr, gf, ("dq", "dk", "dv")):
            np.testing.assert_allclose(b, a, atol=5e-4, rtol=1e-3,
                                       err_msg=name)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("blocks", [(64, 64), (64, 32)])
    def test_three_blocks_a_side(self, dtype, blocks):
        # 3 x 3 (and 3 x 6) blocks: interior and diagonal steps both occur,
        # and in the k-major walk of dk/dv later k blocks start at later q
        # rows.  Grouped-query heads: dk/dv are float32 per query head.
        block_q, block_k = blocks
        q, k, v = _qkv(jax.random.key(8), B=1, H=4, Hkv=2, S=192,
                       dtype=dtype)
        do = jax.random.normal(jax.random.key(9), q.shape, dtype)

        def fwd_bwd(fn, *args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(do.astype(out.dtype))

        got = fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k,
            interpret=True), q, k, v)
        # The reference in float32 on the very inputs the kernel saw.
        want = fwd_bwd(lambda q, k, v: reference_attention(
            q, k, v, causal=True),
            *(x.astype(jnp.float32) for x in (q, k, v)))
        tol = 5e-4 if dtype == jnp.float32 else 3e-2
        for a, b, name in zip(want, got, ("out", "dq", "dk", "dv")):
            assert b.dtype == dtype, name
            a = np.asarray(a)
            np.testing.assert_allclose(
                np.asarray(b, np.float32), a, atol=tol * np.abs(a).max(),
                rtol=tol, err_msg=name)


# (Sq, Sk, block_q, block_k, q_offset, causal) -> steps by kind, or None
# where only the properties are checked.
SCHEDULES = {
    "cells_4096_512": ((4096, 4096, 512, 512, 0, True),
                       {INTERIOR: 28, DIAGONAL: 8, EMPTY: 0}),
    "cells_noncausal": ((4096, 4096, 512, 512, 0, False),
                        {INTERIOR: 64, DIAGONAL: 0, EMPTY: 0}),
    "smoke_2048_512": ((2048, 2048, 512, 512, 0, True),
                       {INTERIOR: 6, DIAGONAL: 4, EMPTY: 0}),
    "one_block": ((128, 128, 128, 128, 0, True),
                  {INTERIOR: 0, DIAGONAL: 1, EMPTY: 0}),
    # Non-square blocks, the step counts written out: a q block of 1,024
    # sees 2, 4, 6, 8 k blocks of 512 (20 pairs = 40 units of 512 x 512
    # where 512s do 36); 256-row q blocks under 512-key blocks cover the
    # same 36 units in 72 half-unit steps; the k-major mirror image.
    "bq1024_bk512": ((4096, 4096, 1024, 512, 0, True),
                     {INTERIOR: 12, DIAGONAL: 8, EMPTY: 0}),
    "bq256_bk512": ((4096, 4096, 256, 512, 0, True),
                    {INTERIOR: 56, DIAGONAL: 16, EMPTY: 0}),
    "bq512_bk1024": ((4096, 4096, 512, 1024, 0, True),
                     {INTERIOR: 12, DIAGONAL: 8, EMPTY: 0}),
    "bq256_bk1024_8k": ((8192, 8192, 256, 1024, 0, True),
                        {INTERIOR: 112, DIAGONAL: 32, EMPTY: 0}),
    "bq64_bk128": ((128, 128, 64, 128, 0, True), None),
    "bq32_bk64": ((128, 128, 32, 64, 0, True), None),
    "bq64_bk32": ((192, 192, 64, 32, 0, True), None),
    "three_a_side": ((192, 192, 64, 64, 0, True),
                     {INTERIOR: 3, DIAGONAL: 3, EMPTY: 0}),
    "noncausal_bq32": ((64, 64, 32, 64, 0, False), None),
    "ring_shard_offset": ((64, 128, 32, 64, 64, True), None),
    # Sk > Sq + q_offset: no q row reaches the second k block.
    "k_block_unseen": ((64, 128, 32, 64, 0, True), None),
    "offset_off_the_blocks": ((128, 256, 32, 64, 48, True), None),
    # Several tiles a grid step (a 7th entry; PR 52): the streamed side's
    # blocks are major blocks of that many tiles.  A head of latent
    # attention's at 8,192 tokens: 24 steps for the 136 tiles of 512 x 512
    # (40 at four tiles a step).
    "walk_8192_512_8": ((8192, 8192, 512, 512, 0, True, 8), None),
    "walk_8192_512_4": ((8192, 8192, 512, 512, 0, True, 4), None),
    "walk_1024_512_2": ((1024, 1024, 512, 512, 0, True, 2), None),
    "walk_noncausal": ((256, 512, 64, 64, 0, False, 4), None),
    # The offset cuts a major block, and a k block is beyond every q row.
    "walk_offset_cuts_a_major_block": ((256, 512, 64, 64, 96, True, 4),
                                       None),
}
# (steps, tiles walked) of the cases whose counts are written out.
WALKS = {"walk_8192_512_8": (24, 136), "walk_8192_512_4": (40, 136),
         "walk_1024_512_2": (2, 3)}


@pytest.mark.parametrize("major", ["q", "k"])
@pytest.mark.parametrize("case", SCHEDULES)
def test_block_schedule(case, major):
    """The schedule alone, no kernel: every visible element lies in
    exactly one step, no step is wholly masked, an interior step has no
    masked element, and FIRST / LAST bracket each resident block."""
    (Sq, Sk, bq, bk, off, causal, *tiles), counts = SCHEDULES[case]
    if tiles:
        # The table of a walk lists (resident block, major block) pairs;
        # the checks below hold of those as of any pair of blocks.
        t = attention_ops.Tiles(bq, bk, 1, "qk" if major == "q" else "kq",
                                tiles[0])
        _check_the_walk(case, major, Sq, Sk, off, causal, t)
        bq, bk = t.major
    sched = block_schedule(Sq, Sk, bq, bk, off, causal, major)
    assert sched.dtype == np.int32 and sched.shape[0] == 5
    visible = np.ones((Sq, Sk), bool)
    if causal:
        visible = (np.arange(Sq)[:, None] + off) >= np.arange(Sk)[None, :]

    covered = np.zeros((Sq, Sk), int)
    for qi, ki, kind, _, _ in sched.T:
        tile = visible[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
        if kind == EMPTY:
            continue
        covered[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk] += 1
        assert tile.any(), (qi, ki)
        assert tile.all() == (kind == INTERIOR), (qi, ki, kind)
    assert covered.max() <= 1
    assert (covered[visible] == 1).all()

    # The resident blocks come in order, each as one run of steps whose
    # streamed blocks ascend, opened by FIRST and closed by LAST.
    res, streamed = (QI, KI) if major == "q" else (KI, QI)
    n_res = (Sq // bq) if major == "q" else (Sk // bk)
    starts = np.flatnonzero(sched[FIRST])
    ends = np.flatnonzero(sched[LAST])
    assert list(sched[res][starts]) == list(range(n_res))
    assert len(starts) == len(ends)
    for a, b in zip(starts, ends):
        assert a <= b
        assert (sched[res][a:b + 1] == sched[res][a]).all()
        assert (np.diff(sched[streamed][a:b + 1]) > 0).all()
        assert sched[FIRST][a:b + 1].sum() == sched[LAST][a:b + 1].sum() == 1
        # An EMPTY step stands alone, for a block that sees nothing.
        if (sched[KIND][a:b + 1] == EMPTY).any():
            assert a == b
            r = sched[res][a]
            seen = (visible[r * bq:(r + 1) * bq] if major == "q"
                    else visible[:, r * bk:(r + 1) * bk])
            assert not seen.any()
    assert ends[-1] == sched.shape[1] - 1

    if counts is not None:
        assert {kind: int((sched[KIND] == kind).sum())
                for kind in counts} == counts
    unseen = {"k_block_unseen": 1, "offset_off_the_blocks": 1,
              "walk_offset_cuts_a_major_block": 2}.get(case, 0)
    assert (sched[KIND] == EMPTY).sum() == (unseen if major == "k" else 0)

    # What the kernels read: one int32 a step, nothing lost in the packing.
    A = attention_ops
    packed = A._packed_schedule(Sq, Sk, bq, bk, off, causal, major)
    assert packed.dtype == np.int32 and packed.shape == (sched.shape[1],)
    assert (A._step_qi(packed) == sched[QI]).all()
    assert (A._step_ki(packed) == sched[KI]).all()
    for bit, row in ((A._RUN_BIT, sched[KIND] != EMPTY),
                     (A._FIRST_BIT, sched[FIRST]), (A._LAST_BIT, sched[LAST])):
        assert ((packed & bit != 0) == row.astype(bool)).all()


def _check_the_walk(case, major, Sq, Sk, off, causal, t):
    """The tiles the kernels walk inside the steps of a table of major
    blocks (``_visible_tiles``, as a kernel asks it of a step) are the
    steps of the table of one tile a step, in its order."""
    bq, bk, tiles = t.block_q, t.block_k, t.tiles
    sched = block_schedule(Sq, Sk, *t.major, off, causal, major)
    walked = []
    for qi, ki, kind, _, _ in sched.T:
        if kind == EMPTY:
            continue
        first, stop = (int(x) for x in attention_ops._visible_tiles(
            qi, ki, bq, bk, off, causal, t.scores, tiles))
        assert 0 <= first < stop <= tiles, (qi, ki)
        walked += [(qi, ki * tiles + j) if major == "q"
                   else (qi * tiles + j, ki) for j in range(first, stop)]
    one = block_schedule(Sq, Sk, bq, bk, off, causal, major)
    assert walked == [(qi, ki) for qi, ki, kind, _, _ in one.T
                      if kind != EMPTY]
    if case in WALKS:
        assert (sched.shape[1], len(walked)) == WALKS[case]


def test_packed_schedule_holds_the_longest_side():
    A = attention_ops
    n = A._BLOCK_MASK + 1                      # blocks a side that fit
    for major, decode in (("q", A._step_qi), ("k", A._step_ki)):
        sides = (n, 1) if major == "q" else (1, n)
        packed = A._packed_schedule(*sides, 1, 1, 0, False, major)
        assert packed.dtype == np.int32 and (packed > 0).all()
        assert (decode(packed) == np.arange(n)).all()
    with pytest.raises(ValueError, match="blocks a side"):
        A._packed_schedule(2 * n, 8, 1, 8, 0, False, "q")


def test_k_block_no_q_sees_gets_zero_gradient():
    # Sk > Sq + q_offset: the kernel still writes dk / dv of the k block
    # that no q row reaches, as zeros.
    ks = jax.random.split(jax.random.key(10), 4)
    q = jax.random.normal(ks[0], (1, 2, 64, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    do = jax.random.normal(ks[3], q.shape)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * do),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=64, interpret=True))
    want = grads(lambda q, k, v: reference_attention(q, k, v, causal=True))
    for a, b, name in zip(want, got, ("dq", "dk", "dv")):
        np.testing.assert_allclose(b, a, atol=5e-4, rtol=1e-3, err_msg=name)
    assert not np.asarray(got[1][:, :, 64:]).any()
    assert not np.asarray(got[2][:, :, 64:]).any()


# (Sq, Sk, D, group, window) -> (block_q, block_k, heads a step) of forward,
# dq and dk/dv, and with a fourth entry the tiles a grid step walks (1
# where none is given).  From the chip's tables of step 0 (PERF.md, PR 33;
# PR 52 for the tiles a step of a head size over 128).
TILES = {
    # yi-coder-1.5b.train-sft4k: no group to stack, so larger pairs.
    "yi_4096": ((4096, 4096, 128, 1, None),
                [(1024, 1024, 1)] * 3),
    # mistral-7b-v0.3.train-fsdp4: 4 query heads a key head, 2,048 rows.
    "mistral_4096_group4": ((4096, 4096, 128, 4, None),
                            [(512, 512, 4)] * 3),
    # trinity-mini.train-moe8k, full and window layers: 8 heads, 4,096 rows
    # a step; the forward takes its keys 256 at a time.
    "trinity_8192_group8": ((8192, 8192, 128, 8, None),
                            [(512, 256, 8), (512, 512, 8), (512, 512, 8)]),
    "trinity_8192_group8_window": ((8192, 8192, 128, 8, 2048),
                                   [(512, 256, 8), (512, 512, 8),
                                    (512, 512, 8)]),
    "tokens_128k": ((131072, 131072, 128, 1, None), [(1024, 1024, 1)] * 3),
    # A ring shard (q_offset != 0 in the call): shapes alone decide.
    "ring_shard": ((4096, 8192, 128, 1, None), [(1024, 1024, 1)] * 3),
    # Lengths the larger blocks do not divide fall back, and do not raise.
    "not_divided_4608": ((4608, 4608, 128, 1, None), [(512, 512, 1)] * 3),
    "not_divided_1536": ((1536, 1536, 128, 1, None), [(512, 512, 1)] * 3),
    # Too short for the larger pairs to pay (chip_smoke's 2,048).
    "smoke_2048": ((2048, 2048, 128, 1, None), [(512, 512, 1)] * 3),
    # A window and no group: large pairs waste at both edges of the band.
    "window_no_group": ((8192, 8192, 128, 1, 2048), [(512, 512, 1)] * 3),
    "head_dim_256": ((4096, 4096, 256, 4, None), [(512, 512, 1, 8)] * 3),
    # Latent attention's 192 / 128 (kanana-2-30b-a3b.train-mla8k,
    # xing4.0-29b-a4b.train-mhc8k): eight tiles of 512 x 512 a grid step
    # where they divide the streamed side, else four, two, the one.
    "latent_8192": ((8192, 8192, 192, 1, None), [(512, 512, 1, 8)] * 3),
    "latent_2048": ((2048, 2048, 192, 1, None), [(512, 512, 1, 4)] * 3),
    "latent_1024": ((1024, 1024, 192, 1, None), [(512, 512, 1, 2)] * 3),
    "latent_512": ((512, 512, 192, 1, None), [(512, 512, 1, 1)] * 3),
    "latent_1536": ((1536, 1536, 192, 1, None), [(512, 512, 1, 1)] * 3),
    # The streamed side decides: k's in forward and dq, q's in dk/dv.
    "latent_ring_shard": ((1024, 4096, 192, 1, None),
                          [(512, 512, 1, 8), (512, 512, 1, 8),
                           (512, 512, 1, 2)]),
    "latent_window": ((8192, 8192, 192, 1, 2048), [(512, 512, 1)] * 3),
    # A group wider than a step: the most heads that divide it, up to 8.
    "group16": ((4096, 4096, 128, 16, None),
                [(512, 256, 8), (512, 512, 8), (512, 512, 8)]),
    "group3_short": ((192, 192, 32, 3, None), [(192, 192, 3)] * 3),
}


# The backward of the same shapes (PR 54): the one pass's (block_q, block_k,
# heads a step, tiles a step), ``flash_bwd`` in place of dq and dk/dv where
# the call is the causal square, or None: the pair.  One query head a grid
# step, no window, Sq == Sk and the row's float32 dq, lane-padded, inside
# ``_DQ_ROW`` (6 MiB); four tiles a step at the most.
ONE_PASS = {
    "yi_4096": (1024, 1024, 1, 1),              # 2 MiB of dq a row
    "mistral_4096_group4": None,                # groups stacked: the pair
    "trinity_8192_group8": None,
    "trinity_8192_group8_window": None,
    "tokens_128k": None,                        # 64 MiB of dq a row
    "ring_shard": None,                         # a rectangle
    "not_divided_4608": (512, 512, 1, 1),
    "not_divided_1536": (512, 512, 1, 1),
    "smoke_2048": (512, 512, 1, 1),
    "window_no_group": None,
    "head_dim_256": (512, 512, 1, 4),           # 4 MiB, a head a step
    "latent_8192": None,        # in one part [Sq, 192] pads to 256: 8 MiB
    "latent_2048": (512, 512, 1, 4),
    "latent_1024": (512, 512, 1, 2),
    "latent_512": (512, 512, 1, 1),
    "latent_1536": (512, 512, 1, 1),
    "latent_ring_shard": None,
    "latent_window": None,
    "group16": None,
    "group3_short": None,
    # the budget: 6 MiB of dq fit, 8 and 16 do not
    "tokens_12k": (1024, 1024, 1, 1),
    "tokens_16k": None,
    "tokens_32k": None,
    "latent_6144": (512, 512, 1, 4),
}
# In parts (``Dr`` 64: the rotary lanes' sums lie along the lanes, unpadded).
ONE_PASS_IN_PARTS = {
    "latent_8192": (512, 512, 1, 4),            # 4 + 2 MiB: both latent cells
    "latent_1024": (512, 512, 1, 2),
    "latent_group5": (512, 512, 1, 4),          # a head a row under a group
    "latent_group5_window": None,
    "latent_window": None,
    "latent_ring_shard": None,
}
TILES.update({
    # a group under a head size over 128 (PR 57): a head a row and the walk
    # without a window; under a window narrower than a block the group's
    # five heads one step, 128 x 256 (dk/dv 256 x 128)
    "latent_group5": ((8192, 8192, 192, 5, None), [(512, 512, 1, 8)] * 3),
    "latent_group5_window": ((8192, 8192, 192, 5, 128),
                             [(128, 256, 5), (128, 256, 5), (256, 128, 5)]),
    "latent_group5_wide_window": ((8192, 8192, 192, 5, 2048),
                                  [(512, 512, 1)] * 3),
    "latent_narrow_window": ((8192, 8192, 192, 1, 128), [(512, 512, 1)] * 3),
    "tokens_12k": ((12288, 12288, 128, 1, None), [(1024, 1024, 1)] * 3),
    "tokens_16k": ((16384, 16384, 128, 1, None), [(1024, 1024, 1)] * 3),
    "tokens_32k": ((32768, 32768, 128, 1, None), [(1024, 1024, 1)] * 3),
    "latent_6144": ((6144, 6144, 192, 1, None), [(512, 512, 1, 4)] * 3),
})


@pytest.mark.parametrize("case", TILES)
def test_tiles(case):
    args, want = TILES[case]
    for kind, (block_q, block_k, heads, *tiles) in zip(
            ("fwd", "dq", "dkv"), want):
        assert attention_ops._tiles(kind, *args) == (
            block_q, block_k, heads, "kq" if kind == "dkv" else "qk",
            *(tiles or [1])), kind
    for table, Dr in ((ONE_PASS, 0), (ONE_PASS_IN_PARTS, 64)):
        if case in table:
            one = table[case]
            assert attention_ops._tiles("bwd", *args, Dr=Dr) == (
                one and (*one[:3], "kq", one[3])), Dr


@pytest.mark.parametrize("causal,q_offset", [(False, 0), (True, 64)])
def test_default_geometry_off_the_causal_square(causal, q_offset):
    """``causal=False`` and a ring shard's ``q_offset`` with the blocks
    ``_tiles`` picks, forward and gradients."""
    ks = jax.random.split(jax.random.key(15), 4)
    q = jax.random.normal(ks[0], (1, 4, 64, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    do = jax.random.normal(ks[3], q.shape)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * do),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, q_offset=q_offset, interpret=True))
    want = grads(lambda q, k, v: reference_attention(
        q, k, v, causal=causal, q_offset=q_offset))
    for a, b, name in zip(want, got, ("dq", "dk", "dv")):
        np.testing.assert_allclose(b, a, atol=5e-4, rtol=1e-3, err_msg=name)


def _geometry_counts():
    """ray_tpu_flash_step_geometry_total as {kernel: {tags: count}}."""
    from ray_tpu.util import metrics
    _by_name, acc = metrics._aggregate_snapshots()
    out = {}
    for tags, value in acc.get("ray_tpu_flash_step_geometry_total",
                               {}).values():
        tags = dict(tags)
        out.setdefault(tags.pop("kernel"), {})[
            tuple(sorted(tags.items()))] = value
    return out


@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("group", [4, 8])
def test_a_key_heads_query_heads_share_a_step(group, window):
    """Grouped-query attention: the group's heads are one grid step, its
    rows stacked in forward and dq, its dk / dv added up inside the kernel
    and handed out per key head in the inputs' dtype; against the
    reference in float32 on the very inputs the kernels saw."""
    dtype = jnp.bfloat16
    q, k, v = _qkv(jax.random.key(11), B=2, H=2 * group, Hkv=2, S=192,
                   dtype=dtype)
    do = jax.random.normal(jax.random.key(12), q.shape, dtype)
    before = _geometry_counts()

    def fwd_bwd(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(do.astype(out.dtype))

    got = fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=True, window=window),
        q, k, v)
    want = fwd_bwd(lambda q, k, v: reference_attention(
        q, k, v, window=window), *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b, x, name in zip(want, got, (q, q, k, v),
                             ("out", "dq", "dk", "dv")):
        assert b.dtype == dtype and b.shape == x.shape, name
        a = np.asarray(a)
        np.testing.assert_allclose(
            np.asarray(b, np.float32), a, atol=3e-2 * np.abs(a).max(),
            rtol=3e-2, err_msg=name)

    # Which geometry each kernel took is counted where it is chosen.
    after = _geometry_counts()
    w = "" if window is None else f"_w{window}"
    for kernel, scores in (("fwd", "qk"), ("dq", "qk"), ("dkv", "kq")):
        # a head size that is not 128 (32 here) is in the name and a tag
        tags = (("block_k", "64"), ("block_q", "64"), ("d", "32"),
                ("heads_a_step", str(group)), ("scores", scores))
        name = f"flash_{kernel}_d32{w}"
        assert after[name][tags] > before.get(name, {}).get(tags, 0), name


def test_group_wider_than_a_step_is_summed_outside():
    """A group of more heads than a step takes (16 > 8): the steps hold 8,
    dk / dv leave per step's heads in float32 and are summed after."""
    q, k, v = _qkv(jax.random.key(13), B=1, H=16, Hkv=1, S=128)
    do = jax.random.normal(jax.random.key(14), q.shape)
    assert attention_ops._tiles("dkv", 128, 128, 32, 16).heads == 8

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * do),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=True))
    want = grads(lambda q, k, v: reference_attention(q, k, v))
    for a, b, name in zip(want, got, ("dq", "dk", "dv")):
        np.testing.assert_allclose(b, a, atol=5e-4, rtol=1e-3, err_msg=name)


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
        after = _geometry_counts()
        return got, {
            re.sub(r"_d\d.*", "", name): dict(tags)
            for name in after for tags, n in after[name].items()
            if n > before.get(name, {}).get(tags, 0)}

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
    between the projections and the seven kernels (the rotary pair forward
    and back for q and k, flash forward, dq, dk/dv) nothing q-sized is
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
        + ["flash_fwd", "flash_dq", "flash_dkv"]), kernels
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
        tags = dict(next(iter(set(after[kernel]) - set(
            before.get(kernel, {})))))
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
