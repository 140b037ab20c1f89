"""Document boundaries in the convolution a packed row runs
(``segment_ids``): a packed row's outputs and gradients are those of each
document run alone as a row of its own; the Pallas pair (interpreted)
against the ``jnp`` form with boundaries on a tile's and a chunk's first
row, on their last, round a chunk's edge and inside the rows a rotation
wraps; a call without ids lowers no mask operand.  The helpers are
``tests/ssm_segments_cases.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm
from ssm_segments_cases import _alone, _close, _ids

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def test_a_packed_convolution_is_its_documents_alone():
    lengths = (7, 1, 2, 20, 3)
    S = sum(lengths)
    k = jax.random.split(jax.random.key(4), 4)
    c, w, b = (jax.random.normal(k[0], (1, S, 6)),
               jax.random.normal(k[1], (4, 6)), jax.random.normal(k[2], (6,)))
    weight = jax.random.normal(k[3], (1, S, 6))
    ids = _ids(lengths)
    y = ssm.causal_conv(c, w, b, ids)
    grads = jax.grad(lambda *a: jnp.sum(ssm.causal_conv(*a, ids) * weight),
                     argnums=(0, 1, 2))(c, w, b)
    want_y, want = _alone(ssm.causal_conv, (c, w, b), weight, lengths, (0,),
                          (1, 2))
    _close(y, want_y, 1e-6)
    _close(grads, want, 1e-5)


#: where documents start in a row of 4,096 tokens: two forward tiles of
#: 2,048 and four backward tiles of 1,024, each worked through in chunks of
#: 64 tokens at 512 lanes (32 and 16 a tile) and of 256 at 128 lanes.  Most
#: chunks hold no start and run unmasked beside the ones that do.
CONV_ROWS = {
    "several": (300, 303, 304, 306, 512, 1536, 3000),
    "tile-first-row": (1024, 2048, 3072),       # a document starts a tile
    "tile-last-row": (1023, 2047, 3071, 4095),  # ... on a tile's last row
    "inside-the-wrapped-rows": (1025, 1026, 2049, 2050, 2052, 3079),
    "chunk-first-row": (64, 128, 320, 768, 1088, 2112, 2304, 3840),
    "chunk-last-row": (63, 191, 255, 1151, 2303, 3903),
    # within three tokens either side of a chunk's edge inside a tile
    "round-a-chunk-edge": (125, 126, 129, 131, 317, 323, 1213, 1219, 2557,
                           2561, 2563, 3645, 3650),
    "one-document": (),
}


@functools.partial(jax.jit, static_argnames="impl")
def _pair_and_its_gradients(c, w, b, ids, weight, impl):
    """One program a dtype of ``c`` and an ``impl`` for every row of
    ``CONV_ROWS``: the ids are an operand ([1, 4096] int32 in all of them)."""
    def loss(c, w, b):
        y = jnp.concatenate(ssm.causal_conv(
            c, w, b, ids, start=512, split=(512, 128), impl=impl), -1)
        return jnp.sum(y.astype(jnp.float32) * weight), y
    (_, y), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
        c, w, b)
    return y, grads


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("row", list(CONV_ROWS))
def test_the_convolution_pair_is_the_jnp_form_on_a_packed_row(row, dtype):
    """The Pallas pair interpreted against the ``jnp`` form on the columns
    512.. of a wider array, in a part of 512 lanes and one of 128: the
    forward to the last bit, dc, dw and db to an accumulation order;
    boundaries on a tile's and a chunk's first row, on their last, round a
    chunk's edge and inside the rows a rotation wraps."""
    S = 4096
    edges = (0,) + CONV_ROWS[row] + (S,)
    ids = _ids(tuple(np.diff(edges)))
    k = jax.random.split(jax.random.key(5), 4)
    c = jax.random.normal(k[0], (1, S, 1280)).astype(dtype)
    w, b = jax.random.normal(k[1], (4, 640)), jax.random.normal(k[2], (640,))
    weight = jax.random.normal(k[3], (1, S, 640))
    y, grads = _pair_and_its_gradients(c, w, b, ids, weight,
                                       "kernel_interpret")
    want_y, want = _pair_and_its_gradients(c, w, b, ids, weight, "xla")
    assert y.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want_y, np.float32))
    # the columns the convolution does not read get no gradient
    assert not np.asarray(grads[0][..., :512], np.float32).any()
    assert not np.asarray(grads[0][..., 1152:], np.float32).any()
    _close([g.astype(jnp.float32) for g in grads],
           [g.astype(jnp.float32) for g in want],
           1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_one_number_a_token_carries_every_taps_mask():
    """``_since_start``: tokens since the document's (and the row's) start,
    clipped at K - 1; an id that comes back after another document names a
    new document."""
    ids = jnp.asarray([[7, 7, 7, 7, 7, 2, 7, 7, 3, 3, 3, 3]], jnp.int32)
    np.testing.assert_array_equal(
        ssm._since_start(ids, 4), [[0, 1, 2, 3, 3, 0, 0, 1, 0, 1, 2, 3]])
    np.testing.assert_array_equal(
        ssm._since_start(ids, 2), [[0, 1, 1, 1, 1, 0, 0, 1, 0, 1, 1, 1]])


def test_a_convolution_without_ids_lowers_no_mask_operand():
    """The pair's operands in a traced call: c, the taps and the bias (the
    backward: c, the rows before a tile, the cotangent, taps, bias); with
    ids two more each way: a flag a chunk (by scalar prefetch) and the
    tokens since a document's start."""
    c, w, b = (jnp.zeros((1, 2048, 128)), jnp.zeros((4, 128)),
               jnp.zeros((128,)))

    def operands(ids):
        out = {}

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    out[eqn.params["name"]] = len(eqn.invars)
                for v in eqn.params.values():
                    for x in v if isinstance(v, (list, tuple)) else [v]:
                        if hasattr(x, "eqns") or hasattr(x, "jaxpr"):
                            walk(getattr(x, "jaxpr", x))
        walk(jax.make_jaxpr(jax.grad(lambda c: jnp.sum(ssm.causal_conv(
            c, w, b, ids, impl="kernel_interpret"))))(c).jaxpr)
        return out

    assert operands(None) == {"ssm_conv_fwd": 3, "ssm_conv_bwd": 5}
    assert operands(jnp.zeros((1, 2048), jnp.int32)) == {
        "ssm_conv_fwd": 5, "ssm_conv_bwd": 7}

