"""Document boundaries in the scan a packed row runs (``segment_ids``): a
packed row's outputs and gradients are those of each document run alone as
a row of its own, for ``ssd_scan`` (both paths, the kernels in ``interpret``,
at one group of many heads, which the kernels take in slices, and at
Nemotron's eight heads a group), with boundaries on a chunk's edge, one
token after it, and several inside one chunk; ids that name one document give
what no ids give, bit for bit.  The convolution's cases are
``tests/test_ops_ssm_segments_conv.py``'s and flash's
``tests/test_ops_ssm_segments_flash.py``'s; the helpers of the three are
``tests/ssm_segments_cases.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm
from ssm_segments_cases import LENGTHS, _alone, _close, _ids, _scan_args

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


@pytest.mark.parametrize("shape,interpret", [
    ((4, 8, 2, 16, 16), False),         # the jnp form, two groups
    ((16, 64, 1, 128, 128), True),      # ONE group of 16 heads: two slices
    ((16, 64, 2, 128, 128), True),      # eight heads a group, as Nemotron's
], ids=["jnp", "kernels-one-wide-group", "kernels-eight-a-group"])
def test_a_packed_scan_is_its_documents_alone(shape, interpret):
    H, P, G, N, Q = shape
    lengths = LENGTHS if Q == 128 else tuple(n // 8 + 1 for n in LENGTHS)
    S = sum(lengths)
    args, weight = _scan_args(S, H, P, G, N)
    ids = _ids(lengths)
    with jax.default_matmul_precision("highest"):
        packed = lambda *a: ssm.ssd_scan(*a, Q, segment_ids=ids,
                                         interpret=interpret)

        def loss(*a):
            y = packed(*a)
            return jnp.sum(y * weight), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True))(*args)
        want_y, want = _alone(lambda *a: ssm.ssd_scan(*a, Q), args, weight,
                              lengths, (0, 1, 3, 4), (2, 5))
    _close(y, want_y, 1e-4)
    _close(grads, want, 2e-4)


def test_the_wide_group_goes_through_in_slices_and_the_count_says_so():
    """Granite's 64 heads of 64 on one B and C are eight grid rows of 512
    channels; Nemotron's eight a group are one, as they were."""
    from ray_tpu.util import telemetry
    assert ssm._slices(64, 64) == 8 and ssm._slices(8, 64) == 1
    assert ssm._slices(16, 128) == 4 and ssm._slices(3, 64) == 1
    args, _ = _scan_args(256, 16, 64, 1, 128)
    seen = []
    real = telemetry.inc
    telemetry.inc = lambda name, *a, tags=None, **k: seen.append((name, tags))
    try:
        ssm.ssd_scan(*args, 128, segment_ids=_ids((100, 156)),
                     interpret=True)
    finally:
        telemetry.inc = real
    assert ("ray_tpu_ssm_path_total", {
        "path": "kernel", "chunk": "128", "segments": "yes",
        "group_channels": "1024"}) in seen


def test_ids_of_one_document_are_no_ids_bit_for_bit():
    args, weight = _scan_args(300, 4, 8, 2, 16)
    one = jnp.full((1, 300), 3, jnp.int32)
    for interpret, a in ((False, args),
                         (True, _scan_args(256, 8, 64, 1, 128)[0])):
        S = a[0].shape[1]
        np.testing.assert_array_equal(
            ssm.ssd_scan(*a, 16 if not interpret else 128,
                         interpret=interpret),
            ssm.ssd_scan(*a, 16 if not interpret else 128,
                         segment_ids=one[:, :S], interpret=interpret))
    c = jax.random.normal(jax.random.key(1), (1, 300, 6))
    w = jax.random.normal(jax.random.key(2), (4, 6))
    b = jax.random.normal(jax.random.key(3), (6,))
    np.testing.assert_array_equal(ssm.causal_conv(c, w, b),
                                  ssm.causal_conv(c, w, b, one))
    g = lambda ids: jax.grad(lambda c, w, b: jnp.sum(
        ssm.causal_conv(c, w, b, ids) * weight[:, :, 0, :6]),
        argnums=(0, 1, 2))(c, w, b)
    for x, y in zip(g(None), g(one)):
        np.testing.assert_array_equal(x, y)
    carry, cut = ssm.chunk_carry(args[1], args[2], 16, one)
    np.testing.assert_allclose(carry, ssm.chunk_carry(args[1], args[2], 16),
                               rtol=1e-6)
    assert float(cut) == 0


def test_chunk_carry_with_ids_counts_the_chunks_a_boundary_cuts():
    dt = jnp.full((1, 64, 2), 0.1)
    A_ = -jnp.ones((2,))
    ids = _ids((16, 20, 28))            # starts at 16 and 36: chunks 1, 2
    carry, cut = ssm.chunk_carry(dt, A_, 16, ids)
    assert float(cut) == 2
    np.testing.assert_allclose(carry, np.exp(-1.6), rtol=1e-6)
    # every chunk cut: the mean over none is 1 by convention
    carry, cut = ssm.chunk_carry(dt, A_, 16, _ids((1,) * 64))
    assert float(cut) == 4 and float(carry) == 1.0

