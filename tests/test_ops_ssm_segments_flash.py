"""Document boundaries in the attention a packed row runs (``segment_ids``):
a packed ``flash_attention`` call (the one pass and the pair) is the
reference on each document alone, with boundaries on a tile's edge, one token
after it, and several inside one tile; ids that name one document give what
no ids give, bit for bit; a call without ids traces the kernels it traced.
The helpers are ``tests/ssm_segments_cases.py``'s."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ssm_segments_cases import LENGTHS, _alone, _close, _ids

A = importlib.import_module("ray_tpu.ops.attention")

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


@pytest.mark.parametrize("pair", [False, True], ids=["one-pass", "pair"])
@pytest.mark.parametrize("heads", [(4, 1), (2, 2)], ids=["group4", "mha"])
def test_a_packed_flash_call_is_its_documents_alone(heads, pair, monkeypatch):
    """Against the reference on each document alone, forward and the three
    gradients; the stacked group and a head a row, the one pass and the
    pair; two rows whose documents differ."""
    if pair:
        monkeypatch.setattr(A, "_DQ_ROW", 0)
    H, Hkv = heads
    S, D = sum(LENGTHS), 64
    rows = [LENGTHS, (S,)]
    k = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(k[0], (2, H, S, D))
    kk = jax.random.normal(k[1], (2, Hkv, S, D))
    v = jax.random.normal(k[2], (2, Hkv, S, D))
    weight = jax.random.normal(k[3], (2, H, S, D))
    ids = jnp.concatenate([_ids(r) for r in rows])
    flash = lambda q, kk, v: A.flash_attention(
        q, kk, v, scale=0.2, segment_ids=ids, interpret=True, block_q=128,
        block_k=128)

    def loss(*a):
        out = flash(*a)
        return jnp.sum(out * weight), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, kk, v)
    for r, lengths in enumerate(rows):
        one = lambda a: jnp.swapaxes(a[r:r + 1], 1, 2)      # [1, S, H, D]
        ref = lambda q, kk, v: jnp.swapaxes(A.reference_attention(
            *(jnp.swapaxes(t, 1, 2) for t in (q, kk, v)), scale=0.2), 1, 2)
        want_o, want = _alone(ref, tuple(map(one, (q, kk, v))),
                              one(weight), lengths, (0, 1, 2), ())
        _close(one(out), want_o, 2e-5)
        _close([one(g) for g in grads], want, 2e-4)
    # and the reference with ids says the same
    _close(out, A.reference_attention(q, kk, v, scale=0.2, segment_ids=ids),
           2e-5)


def test_flash_with_ids_of_one_document_is_flash_without_bit_for_bit():
    k = jax.random.split(jax.random.key(1), 4)
    q, kk, v, w = (jax.random.normal(k[i], (1, 4 if i in (0, 3) else 2, 256,
                                             64)) for i in range(4))
    run = lambda ids: jax.value_and_grad(lambda q, kk, v: jnp.sum(
        A.flash_attention(q, kk, v, segment_ids=ids, interpret=True,
                          block_q=128, block_k=128) * w),
        argnums=(0, 1, 2))(q, kk, v)
    for x, y in zip(jax.tree.leaves(run(None)),
                    jax.tree.leaves(run(jnp.zeros((1, 256), jnp.int32)))):
        np.testing.assert_array_equal(x, y)


def test_a_call_without_ids_traces_the_kernels_it_traced():
    """Names and operand counts of the kernels in a traced call: without ids
    ``flash_fwd`` / ``flash_bwd`` with one table, with them ``flash_seg_*``
    with two scalar-prefetch operands and the ids' two blocks."""
    q = jnp.zeros((1, 2, 256, 128))

    def kernels(ids):
        text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
            A.flash_attention(q, q, q, segment_ids=ids, interpret=True,
                              block_q=128, block_k=128))))(q))
        import re
        return sorted(set(re.findall(r"name=(flash_\w+)", text))
                      - {"flash_out", "flash_lse"})   # checkpoint names

    assert kernels(None) == ["flash_bwd", "flash_fwd"]
    assert kernels(jnp.zeros((1, 256), jnp.int32)) == ["flash_seg_bwd",
                                                      "flash_seg_fwd"]


def test_what_a_call_with_ids_does_not_take_is_refused_by_name():
    q = jnp.zeros((1, 2, 256, 64))
    ids = jnp.zeros((1, 256), jnp.int32)
    for kw in ({"window": 128}, {"causal": False}, {"q_offset": 128},
               {"sink": jnp.zeros((2,))}):
        with pytest.raises(NotImplementedError, match="segment_ids"):
            A.flash_attention(q, q, q, segment_ids=ids, interpret=True, **kw)
    with pytest.raises(NotImplementedError, match="segment_ids"):
        A.flash_attention(q, q, q, segment_ids=ids[:, :128], interpret=True)
