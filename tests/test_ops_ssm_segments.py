"""Document boundaries in the three ops a packed row runs (``segment_ids``):
a packed row's outputs and gradients are those of each document run alone as
a row of its own, for ``ssd_scan`` (both paths, the kernels in ``interpret``,
at one group of many heads, which the kernels take in slices, and at
Nemotron's eight heads a group), ``causal_conv`` and ``flash_attention``
(the one pass and the pair), with boundaries on a chunk's or tile's edge, one
token after it, and several inside one chunk; ids that name one document give
what no ids give, bit for bit; a call without ids traces the kernels it
traced."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm

A = importlib.import_module("ray_tpu.ops.attention")

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")

#: at a chunk / tile of 128: a boundary on the edge (128), one token after
#: the next (257), several inside one chunk (260, 263, 300), a long tail
LENGTHS = (128, 129, 3, 3, 37, 212)


def _ids(lengths, rows=1):
    return jnp.asarray(np.tile(np.repeat(np.arange(len(lengths)), lengths),
                               (rows, 1)), jnp.int32)


def _scan_args(S, H, P, G, N, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(k[0], (1, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (1, S, H)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (H,), maxval=2.0)),
            0.3 * jax.random.normal(k[3], (1, S, G, N)),
            0.3 * jax.random.normal(k[4], (1, S, G, N)),
            jax.random.normal(k[5], (H,))), jax.random.normal(
                k[6], (1, S, H, P))


def _alone(fn, args, weight, lengths, by_token, shared):
    """(outputs laid end to end, gradients) of ``fn`` run on each document
    as a row of its own: ``by_token`` the indices of the arguments that lie
    [1, S, ...], ``shared`` of those whose gradients add up."""
    outs, grads, at = [], [jnp.zeros_like(a) for a in args], 0
    for n in lengths:
        part = slice(at, at + n)
        mine = [a[:, part] if i in by_token else a
                for i, a in enumerate(args)]
        outs.append(fn(*mine))
        g = jax.grad(lambda *a: jnp.sum(fn(*a) * weight[:, part]),
                     argnums=tuple(range(len(args))))(*mine)
        for i in by_token:
            grads[i] = grads[i].at[:, part].set(g[i])
        for i in shared:
            grads[i] = grads[i] + g[i]
        at += n
    return jnp.concatenate(outs, 1), grads


def _close(got, want, rtol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=rtol * float(jnp.max(jnp.abs(w))) + 1e-6)


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
        y = packed(*args)
        grads = jax.grad(lambda *a: jnp.sum(packed(*a) * weight),
                         argnums=tuple(range(6)))(*args)
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

    @functools.partial(jax.jit, static_argnames="impl")
    def both(c, w, b, impl):
        def loss(c, w, b):
            y = jnp.concatenate(ssm.causal_conv(
                c, w, b, ids, start=512, split=(512, 128), impl=impl), -1)
            return jnp.sum(y.astype(jnp.float32) * weight), y
        (_, y), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            c, w, b)
        return y, grads

    y, grads = both(c, w, b, "kernel_interpret")
    want_y, want = both(c, w, b, "xla")
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
    out = flash(q, kk, v)
    grads = jax.grad(lambda *a: jnp.sum(flash(*a) * weight),
                     argnums=(0, 1, 2))(q, kk, v)
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


def test_what_a_call_with_ids_does_not_take_is_refused_by_name():
    q = jnp.zeros((1, 2, 256, 64))
    ids = jnp.zeros((1, 256), jnp.int32)
    for kw in ({"window": 128}, {"causal": False}, {"q_offset": 128},
               {"sink": jnp.zeros((2,))}):
        with pytest.raises(NotImplementedError, match="segment_ids"):
            A.flash_attention(q, q, q, segment_ids=ids, interpret=True, **kw)
    with pytest.raises(NotImplementedError, match="segment_ids"):
        A.flash_attention(q, q, q, segment_ids=ids[:, :128], interpret=True)
