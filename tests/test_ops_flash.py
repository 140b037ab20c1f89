"""Flash attention on the CPU (interpreted): against the reference, its block
schedules, the geometry ``_tiles`` picks, and a key head's query heads in
one step.  (Cut from ``tests/test_ops.py`` by kernel family, PR 59; the
calls with values as rows, in parts and in a llama layer:
``tests/test_ops_flash_rows.py``; several tiles a step and the one pass:
``tests/test_ops_flash_walk.py``.)
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention, flash_attention, reference_attention
from ray_tpu.ops.attention import (DIAGONAL, EMPTY, FIRST, INTERIOR, KI,
                                   KIND, LAST, QI, block_schedule)

from ops_cases import _geometry_counts, _qkv

# ``ray_tpu.ops.attention`` the attribute is the function of that name.
attention_ops = importlib.import_module("ray_tpu.ops.attention")


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
TILES.update({
    # the grouped cells' other shapes (PR 60): forward, dq and dk/dv stack
    "nemotron_8192_group16": ((8192, 8192, 128, 16, None),
                              [(512, 256, 8), (512, 512, 8), (512, 512, 8)]),
    "lfm2_8192_group4_d64": ((8192, 8192, 64, 4, None),
                             [(512, 512, 4)] * 3),
    "group4_16k": ((16384, 16384, 128, 4, None), [(512, 512, 4)] * 3),
    "group4_ring_shard": ((4096, 8192, 128, 4, None), [(512, 512, 4)] * 3),
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
    # mimo-v2-flash.train-sink8k (PR 62), 192 / 128 in ONE part: a window
    # layer's 16 query heads over 2 key heads are the band's stacked 8, the
    # full layer's 16 over one key head a head a row at 8 tiles a step
    "mimo_window_group8": ((8192, 8192, 192, 8, 128),
                           [(128, 256, 8), (128, 256, 8), (256, 128, 8)]),
    "mimo_full_group16": ((8192, 8192, 192, 16, None),
                          [(512, 512, 1, 8)] * 3),
})


# The backward of the same shapes (PR 54): the one pass's (block_q, block_k,
# heads a step, tiles a step), ``flash_bwd`` in place of dq and dk/dv where
# the call is the causal square, or None: the pair.  One query head a grid
# step, Sq == Sk and the row's float32 dq, lane-padded, inside ``_DQ_ROW``
# (6 MiB); four tiles a step at the most; a window only under a group at a
# head size of 128 or under.
ONE_PASS = {
    "yi_4096": (1024, 1024, 1, 1),              # 2 MiB of dq a row
    # a group at 128 or under (PR 60): a query head a grid row all the
    # same, dk/dv's shares summed outside; one tile a step under a window
    "mistral_4096_group4": (512, 512, 1, 4),
    "trinity_8192_group8": (512, 512, 1, 4),
    "trinity_8192_group8_window": (512, 512, 1, 1),
    "nemotron_8192_group16": (512, 512, 1, 4),
    "lfm2_8192_group4_d64": (512, 512, 1, 4),   # [8192, 64] pads to 4 MiB
    "group4_16k": None,                         # 8 MiB of dq a row
    "group4_ring_shard": None,
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
    "group16": (512, 512, 1, 4),
    "group3_short": (192, 192, 1, 1),
    # the budget: 6 MiB of dq fit, 8 and 16 do not
    "tokens_12k": (1024, 1024, 1, 1),
    "tokens_16k": None,
    "tokens_32k": None,
    "latent_6144": (512, 512, 1, 4),
    "mimo_window_group8": None,     # a window at a head size over 128
    "mimo_full_group16": None,      # in one part [8192, 192] pads to 8 MiB
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


@pytest.mark.parametrize("backward", ["one_pass", "pair"])
@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("group", [4, 8])
def test_a_key_heads_query_heads_share_a_step(group, window, backward,
                                              monkeypatch):
    """Grouped-query attention: the group's heads are one grid step of the
    forward, their rows stacked.  The backward is the one pass (PR 60): a
    query head a grid row, its share of dk / dv summed over the group
    outside; where no row's dq fits (``_DQ_ROW`` 0 here) it is the pair,
    the group stacked in dq and its dk / dv added up inside the kernel.
    Either way dk / dv come back per key head in the inputs' dtype;
    against the reference in float32 on the very inputs the kernels saw."""
    dtype = jnp.bfloat16
    if backward == "pair":
        monkeypatch.setattr(attention_ops, "_DQ_ROW", 0)
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
    for kernel, scores in ((("fwd", "qk"), ("dq", "qk"), ("dkv", "kq"))
                           if backward == "pair"
                           else (("fwd", "qk"), ("bwd", "kq"))):
        # a head size that is not 128 (32 here) is in the name and a tag
        tags = (("block_k", "64"), ("block_q", "64"), ("d", "32"),
                ("heads_a_step", str(group)), ("scores", scores))
        if kernel == "bwd":
            tags = tags[:3] + (("heads_a_step", "1"), ("scores", scores),
                               ("shares", str(group)))
        name = f"flash_{kernel}_d32{w}"
        assert after[name][tags] > before.get(name, {}).get(tags, 0), name


@pytest.mark.parametrize("backward", ["one_pass", "pair"])
def test_group_wider_than_a_step_is_summed_outside(backward, monkeypatch):
    """A group of more heads than a step takes (16 > 8): the pair's steps
    hold 8, dk / dv leave per step's heads in float32 and are summed after
    (two shares); the one pass sums sixteen."""
    q, k, v = _qkv(jax.random.key(13), B=1, H=16, Hkv=1, S=128)
    do = jax.random.normal(jax.random.key(14), q.shape)
    assert attention_ops._tiles("dkv", 128, 128, 32, 16).heads == 8
    assert attention_ops._tiles("bwd", 128, 128, 32, 16).heads == 1
    if backward == "pair":
        monkeypatch.setattr(attention_ops, "_DQ_ROW", 0)
        assert attention_ops._tiles("bwd", 128, 128, 32, 16) is None

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * do),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=True))
    want = grads(lambda q, k, v: reference_attention(q, k, v))
    for a, b, name in zip(want, got, ("dq", "dk", "dv")):
        np.testing.assert_allclose(b, a, atol=5e-4, rtol=1e-3, err_msg=name)


# A learned sink (PR 62): (H, Hkv, S, D, Dv, window, blocks, rows, dtype).
# The forward starts a row's online softmax at the sink; the backward
# kernels are the call's without one, and db is formed beside them.
SINKS = {
    # a window narrower than the row, the group's 4 heads one step
    "window_group4": (8, 2, 256, 48, 32, 40, (64, 64), False, jnp.float32),
    # 16 query heads over ONE key head: two steps' shares summed outside
    "full_group16": (16, 1, 128, 48, 32, None, (64, 64), False, jnp.float32),
    # the cell's window geometry at its head sizes: 128 x 256, 8 stacked
    "band_192_128": (8, 1, 512, 192, 128, 128, (None, None), False,
                     jnp.float32),
    # the one pass makes dq beside dk/dv from the delta it is handed
    "one_pass": (4, 4, 128, 32, 32, None, (64, 64), False, jnp.float32),
    # v and the result as rows, in place at 128
    "rows_128": (2, 1, 128, 128, 128, None, (64, 64), True, jnp.float32),
    "bf16": (8, 2, 256, 48, 32, 40, (64, 64), False, jnp.bfloat16),
}


def _sink_case(case):
    H, Hkv, S, D, Dv, window, (bq, bk), rows, dtype = SINKS[case]
    ks = jax.random.split(jax.random.key(21), 6)
    q = jax.random.normal(ks[0], (1, H, S, D), dtype)
    k = jax.random.normal(ks[1], (1, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (1, S, Hkv, Dv) if rows
                          else (1, Hkv, S, Dv), dtype)
    sink = 1.0 + jax.random.normal(ks[3], (H,), jnp.float32)
    do = jax.random.normal(ks[4], (1, S, H, Dv) if rows else (1, H, S, Dv),
                           dtype)
    dlse = 0.3 * jax.random.normal(ks[5], (1, H, S), jnp.float32)
    flash = lambda q, k, v, **kw: flash_attention(
        q, k, v, block_q=bq, block_k=bk, interpret=True, window=window,
        rows=rows, **kw)
    return q, k, v, sink, do, dlse, flash, window, rows, dtype


@pytest.mark.parametrize("case", SINKS)
def test_sink_matches_the_reference_forward_and_backward(case):
    """The result, the log-sum-exp that holds the sink, and every gradient,
    the sink's among them and with a cotangent on the LSE too, against
    ``reference_attention``'s extra column in float32 on the very inputs;
    the forward kernel is counted under its sink's name and the backward
    kernels under the names they have without one."""
    q, k, v, sink, do, dlse, flash, window, rows, dtype = _sink_case(case)
    before = _geometry_counts()

    def fwd_bwd(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return out + vjp((do.astype(out[0].dtype), dlse))

    got = fwd_bwd(lambda q, k, v, b: flash(q, k, v, sink=b, lse=True),
                  q, k, v, sink)

    def reference(q, k, v, b):
        v = jnp.swapaxes(v, 1, 2) if rows else v
        out, lse = reference_attention(q, k, v, window=window, sink=b,
                                       lse=True)
        return (jnp.swapaxes(out, 1, 2) if rows else out), lse

    want = fwd_bwd(reference, *(x.astype(jnp.float32) for x in (q, k, v)),
                   sink)
    tol = 5e-4 if dtype == jnp.float32 else 3e-2
    for a, b, name in zip(want, got,
                          ("out", "lse", "dq", "dk", "dv", "db")):
        a = np.asarray(a)
        assert b.shape == a.shape, name
        np.testing.assert_allclose(
            np.asarray(b, np.float32), a, atol=tol * np.abs(a).max(),
            rtol=tol, err_msg=name)
    assert got[1].dtype == got[5].dtype == jnp.float32
    after = _geometry_counts()
    grew = {name for name in after
            if sum(after[name].values()) > sum(before.get(name,
                                                          {}).values())}
    assert any(n.startswith("flash_fwd") and n.endswith("_sink")
               for n in grew), grew
    assert not any(n.endswith("_sink") for n in grew
                   if not n.startswith("flash_fwd")), grew


@pytest.mark.parametrize("case", ["window_group4", "one_pass", "rows_128"])
def test_a_sink_that_takes_no_mass_is_the_call_without_one(case):
    """Bit for bit: a row that starts at (m, l) = (-1e30, 1) scales that 1
    away at its first key, as a row that starts at (-inf, 0) has nothing to
    scale; and ``sink=None`` is the call that does not name a sink (the
    same kernels by the same names: nothing of the sink is traced)."""
    q, k, v, _, do, _, flash, _, _, _ = _sink_case(case)
    H = q.shape[1]

    def fwd_bwd(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(do)

    plain = fwd_bwd(flash)
    for other in (fwd_bwd(lambda q, k, v: flash(q, k, v, sink=None)),
                  fwd_bwd(lambda q, k, v: flash(
                      q, k, v, sink=jnp.full((H,), -1e30, jnp.float32)))):
        for a, b in zip(plain, other):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    text = str(jax.make_jaxpr(lambda q, k, v: flash(q, k, v, sink=None))(
        q, k, v))
    assert "sink" not in text
    with pytest.raises(ValueError, match="sink"):
        flash(q, k, v, lse=True)
    with pytest.raises(ValueError, match="a sink a query head"):
        flash(q, k, v, sink=jnp.zeros((H + 1,), jnp.float32))
