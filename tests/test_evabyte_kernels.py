"""EVA attention (ops/eva.py) against the plain reference
(benchmark/reference_evabyte.py) on the CPU: the chunk summaries and the
attention on both paths (``jnp`` and the kernels, interpreted), at other
blocks, in the two limits that are plain causal attention, who sees what,
the tables at the cell's shape, what is refused, and the geometry counter.
(Cut from ``tests/test_evabyte.py``, PR 59.)"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_evabyte as ref  # noqa: E402
from benchmark import roofline_eva  # noqa: E402
from ray_tpu.ops import eva  # noqa: E402
from ray_tpu.ops.attention import (EMPTY, KIND,  # noqa: E402
                                   reference_attention)


#: (row, window, chunk): 4 windows of 64 in chunks of 8, 3 of 128 in 16s
GEOMETRIES = [(256, 64, 8), (384, 128, 16)]


IMPLS = ["reference", "flash_interpret"]


def _qkv(seq, seed=0, B=1, H=2, D=16):
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k, v, g = (jax.random.normal(ks[i], (B, H, seq, D), jnp.float32)
                  for i in range(4))
    mu, phi = (jax.random.normal(ks[4 + i], (H, D), jnp.float32)
               for i in range(2))
    return q, k, v, g, mu, phi


def _bshd(a):
    """[B, H, S, D] (the program's) <-> [B, S, H, D] (the reference's)."""
    return jnp.swapaxes(a, 1, 2)


# ------------------------------------------------------------------- ops

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("seq,window,chunk", GEOMETRIES)
def test_summaries_match_the_reference(seq, window, chunk, impl):
    """Values and the gradients in k, v, mu and phi."""
    _, k, v, _, mu, phi = _qkv(seq)
    gk, gv = (jax.random.normal(jax.random.key(7 + i),
                                (1, 2, seq // chunk, 16)) for i in range(2))

    def loss(fn, lay):
        def f(k, v, mu, phi):
            ks, vs = fn(lay(k), lay(v), mu, phi, chunk)
            return jnp.sum(lay(ks) * gk) + jnp.sum(lay(vs) * gv), (lay(ks),
                                                                   lay(vs))
        return jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2, 3), has_aux=True))(k, v, mu, phi)

    with jax.default_matmul_precision("highest"):
        (_, got), grads = loss(lambda *a: eva.eva_summaries(*a, impl=impl),
                               lambda a: a)
        (_, want), want_grads = loss(ref.summaries, _bshd)
    for a, b in zip(got + grads, want + want_grads):
        assert float(jnp.linalg.norm(b)) > 0
        assert float(ref.relative_distance(a, b)) < 1e-5


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("seq,window,chunk", GEOMETRIES)
def test_attention_matches_the_reference(seq, window, chunk, impl):
    """Values and the gradients in q, k, v and both summaries."""
    q, k, v, g, mu, phi = _qkv(seq)
    ks, vs = eva.reference_summaries(k, v, mu, phi, chunk)

    def loss(fn, lay):
        def f(*a):
            out = lay(fn(*(lay(x) for x in a), window, chunk))
            return jnp.sum(out * g), out
        return jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2, 3, 4), has_aux=True))(q, k, v, ks, vs)

    with jax.default_matmul_precision("highest"):
        (_, got), grads = loss(
            lambda *a: eva.eva_attention(*a, impl=impl), lambda a: a)
        (_, want), want_grads = loss(ref.eva_attention, _bshd)
    for a, b in zip((got,) + grads, (want,) + want_grads):
        assert float(jnp.linalg.norm(b)) > 0
        assert float(ref.relative_distance(a, b)) < 1e-5


@pytest.mark.parametrize("blocks", [(32, 32, 8), (64, 16, 16), (16, 64, 32)])
def test_attention_kernels_at_other_blocks(blocks):
    """A q block smaller than the window, token and summary blocks of
    other sizes: the same values and gradients."""
    q, k, v, g, mu, phi = _qkv(256)
    ks, vs = eva.reference_summaries(k, v, mu, phi, 8)
    block_q, block_k, block_s = blocks

    def grads(**kw):
        return jax.grad(lambda *a: jnp.sum(
            eva.eva_attention(*a, 64, 8, **kw) * g),
            argnums=(0, 1, 2, 3, 4))(q, k, v, ks, vs)

    with jax.default_matmul_precision("highest"):
        got = grads(impl="flash_interpret", block_q=block_q, block_k=block_k,
                    block_s=block_s)
        want = grads(impl="reference")
    for a, b in zip(got, want):
        assert float(ref.relative_distance(a, b)) < 1e-5


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("limit", ["window_covers_the_row", "chunk_of_one"])
def test_the_two_limits_are_plain_causal_attention(limit, impl):
    """With ``window >= S`` no summary is visible; with ``chunk == 1`` a
    summary is its own key and value, and the earlier windows are seen
    whole."""
    q, k, v, g, mu, phi = _qkv(128)
    window, chunk = (256, 8) if limit == "window_covers_the_row" else (32, 1)
    ks, vs = eva.eva_summaries(k, v, mu, phi, chunk, impl=impl)
    if chunk == 1:
        np.testing.assert_allclose(ks, k, atol=1e-6)
        np.testing.assert_allclose(vs, v, atol=1e-6)

    def grads(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * g),
                                  argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got, got_grads = grads(lambda q, k, v: eva.eva_attention(
            q, k, v, *eva.eva_summaries(k, v, mu, phi, chunk, impl=impl),
            window, chunk, impl=impl))
        want, want_grads = grads(
            lambda q, k, v: reference_attention(q, k, v, causal=True))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert float(ref.relative_distance(a, b)) < 1e-5


@pytest.mark.parametrize("impl", IMPLS)
def test_who_sees_what_by_perturbation(impl):
    """A change at position p moves no output before p; a change in the
    query's own window reaches it only through the local set; the first
    window sees no summary."""
    seq, window, chunk, p = 256, 64, 8, 100            # p in window 1
    q, k, v, _, mu, phi = _qkv(seq)
    bump = lambda a: a.at[:, :, p].add(1.0)

    def out(k_att, v_att, k_pool, v_pool):
        ks, vs = eva.eva_summaries(k_pool, v_pool, mu, phi, chunk, impl=impl)
        return eva.eva_attention(q, k_att, v_att, ks, vs, window, chunk,
                                 impl=impl)

    base = out(k, v, k, v)
    moved = lambda o: np.abs(np.asarray(o - base)).max(axis=(0, 1, 3)) > 1e-6
    # everywhere: nothing before p, p itself, and every later window
    everywhere = moved(out(bump(k), bump(v), bump(k), bump(v)))
    assert not everywhere[:p].any() and everywhere[p:].all()
    # through the summaries alone: not the query's own window (64..127),
    # every window after it
    remote = moved(out(k, v, bump(k), bump(v)))
    assert not remote[:128].any() and remote[128:].all()
    # through the tokens alone: the rest of p's window and nothing else
    local = moved(out(bump(k), bump(v), k, v))
    assert local[p:128].all() and not local[:p].any() \
        and not local[128:].any()
    # the first window sees no summary at all
    ks, vs = eva.eva_summaries(k, v, mu, phi, chunk, impl=impl)
    other = moved(eva.eva_attention(q, k, v, ks + 1.0, vs - 1.0, window,
                                    chunk, impl=impl))
    assert not other[:window].any() and other[window:].all()


def test_the_tables_at_the_cells_shape():
    """32,768 / 2,048 / 16 at 512 x 512: a head's forward walk takes 144
    summary steps and 160 token steps, dk/dv the 160, the summaries'
    gradients 120 and one empty step for the last window's; the pairs are
    the roofline's."""
    sched, taken = eva.eva_schedule(32768, 2048, 16, 512, 512, 512, "q")
    assert taken[1].sum() == 144 and (taken[1] == 0).sum() == 160
    assert sched.shape[1] == 304 and (sched[KIND] != EMPTY).all()
    # window 0's ten token steps; then a q block's summaries before its
    # tokens (window 1: one block of summaries, then 1, 2, .. token blocks)
    assert not taken[1][:10].any()
    assert taken[1][10:15].tolist() == [1, 0, 1, 0, 0]
    assert eva.eva_schedule(32768, 2048, 16, 512, 512, 512, "k")[0].shape[1] \
        == 160
    dsum, _ = eva.eva_schedule(32768, 2048, 16, 2048, 512, 128, "s")
    assert dsum.shape[1] == 121 and (dsum[KIND] == EMPTY).sum() == 1
    local, remote = roofline_eva.visible_pairs(32768, 2048, 16)
    assert (local, remote) == (32768 * 2049 / 2, 2048 * 2048 / 16 * 120)
    assert round(local / 1e6, 2) == 33.57 and round(remote / 1e6, 2) == 31.46
    assert roofline_eva.visible_pairs(2048, 2048, 16)[1] == 0
    packed = eva._pack(sched, taken)
    assert packed.dtype == np.int32 and packed.size == 608


@pytest.mark.parametrize("what", ["a ragged row", "a ragged window",
                                  "grouped heads", "a mesh"])
def test_what_eva_does_not_do_is_refused_by_name(what):
    q, k, v, _, mu, phi = _qkv(128)
    ks, vs = eva.reference_summaries(k, v, mu, phi, 8)
    if what == "a ragged row":
        with pytest.raises(ValueError, match="whole windows"):
            eva.eva_attention(q, k, v, ks, vs, 48, 8, impl="flash_interpret")
    elif what == "a ragged window":
        with pytest.raises(ValueError, match="whole chunks"):
            eva.eva_attention(q, k, v, ks, vs, 64, 24, impl="reference")
    elif what == "grouped heads":
        with pytest.raises(ValueError, match="a key head a query head"):
            eva.eva_attention(q, k[:, :1], v[:, :1], ks, vs, 64, 8)
    else:
        class Mesh:
            size = 4
        with pytest.raises(NotImplementedError, match="mesh"):
            eva.eva_attention(q, k, v, ks, vs, 64, 8, mesh=Mesh())


@pytest.mark.parametrize("S_,H", [(32768, 32), (4096, 16)])
def test_rotary_and_flash_tiles_at_the_cells_row(S_, H):
    """``rotate_heads`` and ``_tiles`` meet B = 1, S = 32,768, H = 32 for
    the first time: a step's tile is what the other cells' is."""
    from ray_tpu.ops import rope
    from ray_tpu.ops.attention import _tiles
    assert rope._tile(S_, H) == (512, 8)
    for kind in ("fwd", "dq", "dkv"):
        t = _tiles(kind, 2048, 2048, 128, 1)
        assert (t.block_q, t.block_k, t.heads) == (512, 512, 1)


def test_kernels_count_their_geometry():
    from ray_tpu.util import metrics as metrics_mod
    metrics_mod._reset_for_tests()
    q, k, v, g, mu, phi = _qkv(256)
    jax.grad(lambda k: jnp.sum(eva.eva_attention(
        q, k, v, *eva.eva_summaries(k, v, mu, phi, 8, impl="flash_interpret"),
        64, 8, impl="flash_interpret") * g))(k)
    text = metrics_mod.prometheus_text()
    for kernel in ("eva_fwd_w64c8", "eva_dq_w64c8", "eva_dkv_w64c8",
                   "eva_dsum_w64c8", "eva_pool_fwd_c8", "eva_pool_bwd_c8"):
        assert f'kernel="{kernel}"' in text, kernel
    # 4 windows of 8 summaries in blocks of 32: a q block of window w > 0
    # takes one summary step; every q block its one token step
    line = next(l for l in text.splitlines() if 'kernel="eva_fwd_w64c8"' in l)
    assert 'summary_steps="3"' in line and 'token_steps="4"' in line, line
    metrics_mod._reset_for_tests()
