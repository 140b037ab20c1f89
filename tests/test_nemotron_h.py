"""The nemotron_h model (Nemotron-3-Nano's hybrid stack) against its plain
reference, and the pieces it brought: the chunked scan against the
token-by-token recurrence, the causal convolution, the gated group norm,
un-gated experts in ``ops/moe.py``, flash attention at 16 query heads a key
head, the share of an expert-parallel layer, and the train step's state and
report."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import afmoe, nemotron_h
from ray_tpu.ops import moe, ssm
from ray_tpu.ops.attention import flash_attention, reference_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_nemotron_h as ref  # noqa: E402
from benchmark.archs import nemotron_h as arch  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "kinds": "".join(cfg.kinds), "H": cfg.heads, "Hkv": cfg.kv_heads,
            "D": cfg.head_dim, "Hm": cfg.mamba_heads,
            "P": cfg.mamba_head_dim, "N": cfg.ssm_state,
            "G": cfg.ssm_groups, "K": cfg.conv_kernel, "Q": cfg.chunk_size,
            "Me": cfg.moe_mlp_dim, "Ms": cfg.shared_mlp_dim,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "eps": cfg.norm_eps}


@functools.lru_cache(maxsize=None)
def _setup(seed=0, rows=2, seq=56, **kw):
    """Tiny widths that keep two heads a state group, a head size that is not
    the state size, 16 query heads a key head, 8 experts, the pattern
    ``MEM*EM`` and a row of three chunks of 16 plus a remainder of 8.
    Made once a configuration of this module (nothing writes into what it
    returns), the parameters under one ``jax.jit``: run eagerly the
    initialisation is one program a leaf shape."""
    cfg = nemotron_h.nemotron_h_tiny(**kw)

    @jax.jit
    def make(key, shake_key):
        params = nemotron_h.init_params(cfg, key)
        keys = iter(jax.random.split(shake_key, 64))

        def shake(path, a):
            name = str(path[-1])
            if "norm" in name or "'D'" in name:     # away from one
                return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
            return a

        params = jax.tree_util.tree_map_with_path(shake, params)
        # A selection bias large enough to change which experts are chosen.
        bias = 0.3 * jax.random.normal(
            next(keys), (cfg.expert_layers, cfg.num_experts))
        return params, bias

    params, bias = make(jax.random.key(seed), jax.random.key(seed + 1))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (rows, seq), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (rows, seq),
                                              dtype=np.int32))}
    return cfg, params, bias, batch


def _recurrence(X, dt, A, B, C, D):
    """``ssd_scan``'s arguments through the reference's token-by-token
    recurrence."""
    Bt, S, H, P = X.shape
    G, N = B.shape[2:]
    R = H // G
    return ref.recurrence(X.reshape(Bt, S, G, R, P), dt.reshape(Bt, S, G, R),
                          A.reshape(G, R), B, C, D.reshape(G, R)
                          ).reshape(Bt, S, H, P)


def _scan_inputs(seed=0, Bt=2, S=50, H=4, P=6, G=2, N=5):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (Bt, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (Bt, S, H)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (Bt, S, G, N)),
            jax.random.normal(k[4], (Bt, S, G, N)),
            jax.random.normal(k[5], (H,)))


def test_model_matches_reference_loss_and_every_gradient():
    """The program's loss and the gradient of every leaf (``A_log``,
    ``dt_bias``, ``D``, the convolution's and the gated norm's among them), on
    a share of the experts (2 of 8 from the fifth), against ``jax.grad`` of
    the reference's pieces put together and against the reference's walk in
    blocks (the judged leaves; what the chip's check runs).  Float32 on both
    sides."""
    cfg, params, bias, batch = _setup(experts_held=2, held_start=4)
    assert params["layers"][1]["w_up"].shape[0] == 2
    assert "w_gate" not in params["layers"][1]
    s = _sizes(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda p: nemotron_h.loss_and_report(p, batch, cfg, {"bias": bias}),
        has_aux=True))(params)

    def whole(params):
        lg = ref.logits(params, bias, batch["tokens"], s)
        t = batch["tokens"]
        targets = jnp.concatenate([t[:, 1:], jnp.zeros_like(t[:, :1])], 1)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, targets[..., None], -1)[..., 0]
        mask = batch["loss_mask"].astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.sum(mask)

    want, want_grads = jax.jit(jax.value_and_grad(whole))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and float(jnp.linalg.norm(w)) > 0, path
        assert float(jnp.linalg.norm(g - w)) < 1e-3 * float(
            jnp.linalg.norm(w)), jax.tree_util.keystr(path)
    w_loss, judged, tops = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert abs(float(w_loss) - float(want)) < 1e-5 * float(want)
    assert float(ref.relative_distance(arch.judged_of(grads), judged)) < 1e-3
    assert float(ref.relative_distance(arch.ssm_of(grads),
                                       arch.ssm_of(judged))) < 1e-3
    # The step's report holds the routers' choices the reference makes.
    assert report["top"].shape == tops.shape == (2, 2 * 56, 4)
    assert float(ref.routing_mismatch_share(report["top"], tops, 8)) == 0
    assert 0 < float(report["ssm_chunk_carry"]) < 1


def test_remat_rows_at_a_time_and_loss_chunks_do_not_change_the_loss():
    cfg, params, bias, batch = _setup(experts_held=4)
    run = jax.jit(lambda p, c: nemotron_h.loss_fn(p, batch, c, {"bias": bias}),
                  static_argnums=1)
    plain = run(params, cfg)
    other = run(params,
                cfg.replace(remat="full", layer_rows=1, loss_chunks=4))
    assert abs(float(plain) - float(other)) < 1e-5 * float(plain)


def _value_and_grads(f, args):
    return jax.jit(jax.value_and_grad(
        lambda *a: (lambda y: (jnp.sum(jnp.sin(y)), y))(f(*a)),
        tuple(range(6)), has_aux=True))(*args)


@pytest.fixture(scope="module")
def scan_case():
    """(arguments, the recurrence's result, its gradient in all six)."""
    args = _scan_inputs()
    (_, want), grads = _value_and_grads(_recurrence, args)
    return args, want, grads


@pytest.mark.parametrize("chunk", [16, 10, 25, 64])
def test_chunked_scan_is_the_recurrence(scan_case, chunk):
    """Chunks that do and do not divide a row of 50, one chunk longer than
    the row: forward and ``jax.grad`` in all six arguments."""
    args, want, want_grads = scan_case
    (_, got), grads = _value_and_grads(lambda *a: ssm.ssd_scan(*a, chunk),
                                       args)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    for g, w in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * max(
            1.0, float(jnp.max(jnp.abs(w))))


def test_scan_kernels_are_the_recurrence_in_interpret_mode():
    """The Pallas pair (``ssd_fwd_q128`` / ``ssd_bwd_q128``) at sizes that
    tile (two heads of 64 channels a group, a state of 128, chunks of 128)
    on rows of two chunks and a remainder: forward and the gradient in all
    six arguments against the token-by-token recurrence, and the second row
    starts from a zero state."""
    args = _scan_inputs(seed=3, Bt=2, S=300, H=4, P=64, G=2, N=128)
    args = args[:3] + (0.3 * args[3], 0.3 * args[4], args[5])
    assert ssm._kernels(args[0], args[3], 128, True)
    assert not ssm._kernels(args[0], args[3], 64, True)
    (_, want), want_grads = _value_and_grads(_recurrence, args)
    (_, got), grads = _value_and_grads(
        lambda *a: ssm.ssd_scan(*a, 128, interpret=True), args)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    for g, w in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * max(
            1.0, float(jnp.max(jnp.abs(w))))
    X, dt, A, B, C, D = args
    alone = ssm.ssd_scan(X[1:], dt[1:], A, B[1:], C[1:], D, 128,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got[1:]), np.asarray(alone),
                               atol=1e-6)


def test_no_state_leaks_between_rows():
    """A row's result does not depend on the row before it in the batch:
    every row starts from a zero state."""
    X, dt, A, B, C, D = _scan_inputs()
    both = ssm.ssd_scan(X, dt, A, B, C, D, 16)
    alone = ssm.ssd_scan(X[1:], dt[1:], A, B[1:], C[1:], D, 16)
    np.testing.assert_allclose(np.asarray(both[1:]), np.asarray(alone),
                               atol=1e-6)
    other = ssm.ssd_scan(X.at[0].mul(3.0), dt, A, B, C, D, 16)
    np.testing.assert_array_equal(np.asarray(other[1]), np.asarray(both[1]))


def test_a_chunk_hands_its_state_on():
    """Dropping the carry changes every chunk after the first and nothing in
    it; ``chunk_carry`` is the mean of exp(sum of dt A) over whole chunks."""
    X, dt, A, B, C, D = _scan_inputs(S=48)
    whole = ssm.ssd_scan(X, dt, A, B, C, D, 16)
    first = ssm.ssd_scan(X[:, :16], dt[:, :16], A, B[:, :16], C[:, :16], D,
                         16)
    second = ssm.ssd_scan(X[:, 16:32], dt[:, 16:32], A, B[:, 16:32],
                          C[:, 16:32], D, 16)
    np.testing.assert_allclose(np.asarray(whole[:, :16]), np.asarray(first),
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(whole[:, 16:32] - second))) > 1e-2
    want = jnp.mean(jnp.exp(jnp.sum(
        (dt * A).reshape(2, 3, 16, 4), axis=2)))
    assert abs(float(ssm.chunk_carry(dt, A, 16)) - float(want)) < 1e-6


def test_convolution_sees_nothing_after_t_and_nothing_before_the_row():
    k = jax.random.split(jax.random.key(3), 3)
    c = jax.random.normal(k[0], (2, 12, 5))
    w, b = jax.random.normal(k[1], (4, 5)), jax.random.normal(k[2], (5,))
    out = ssm.causal_conv(c, w, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.convolution(c, w, b)),
                               atol=1e-6)
    # Position 0 reads its own token under the last tap, and the bias.
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(jax.nn.silu(b + w[3] * c[:, 0])),
        atol=1e-6)
    # The backward that is written out is the one JAX derives from the plain
    # form, in all three arguments.
    do = jax.random.normal(jax.random.key(7), out.shape)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * do), (0, 1, 2))(
        c, w, b)
    for got, want in zip(grads(ssm.causal_conv), grads(ref.convolution)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
    # A change at t moves nothing before t, nothing past t + 3, no other
    # channel and no other row.
    moved = ssm.causal_conv(c.at[0, 6, 2].add(1.0), w, b) - out
    changed = np.argwhere(np.abs(np.asarray(moved)) > 0)
    assert set(map(tuple, changed)) == {(0, t, 2) for t in (6, 7, 8, 9)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_convolution_pair_is_the_jnp_form_without_ids(dtype):
    """The Pallas pair interpreted against the ``jnp`` form as the mixer
    calls it: the columns after the gate's of a wider array, X, B and C
    written apart; two rows (a row reads nothing of another), each one tile
    of 2,048 tokens forward and two backward, worked through in chunks.  The
    forward to the last bit, the gradients to an accumulation order."""
    k = jax.random.split(jax.random.key(11), 4)
    c = jax.random.normal(k[0], (2, 2048, 512)).astype(dtype)
    w, b = jax.random.normal(k[1], (4, 384)), jax.random.normal(k[2], (384,))
    do = jax.random.normal(k[3], (2, 2048, 384))

    @functools.partial(jax.jit, static_argnames="impl")
    def both(c, w, b, impl):
        def loss(c, w, b):
            y = jnp.concatenate(ssm.causal_conv(
                c, w, b, start=128, split=(128, 128, 128), impl=impl), -1)
            return jnp.sum(y.astype(jnp.float32) * do), y
        (_, y), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            c, w, b)
        return y, grads

    y, grads = both(c, w, b, "kernel_interpret")
    want_y, want = both(c, w, b, "xla")
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want_y, np.float32))
    for got, to in zip(grads, want):
        got, to = np.asarray(got, np.float32), np.asarray(to, np.float32)
        np.testing.assert_allclose(
            got, to, atol=(1e-2 if dtype == jnp.bfloat16 else 1e-5)
            * np.abs(to).max())
    # a row starts from zeros, across tiles too
    moved = both(c.at[0].add(1.0), w, b, "kernel_interpret")[0]
    np.testing.assert_array_equal(np.asarray(moved[1], np.float32),
                                  np.asarray(y[1], np.float32))


def test_the_convolution_takes_the_jnp_form_where_a_row_is_not_tiles():
    """On a TPU a row that is not whole tiles of 2,048 tokens, or parts that
    are not whole lane tiles, take ``jnp``, and the counter says which."""
    import importlib
    from ray_tpu.util import telemetry
    att = importlib.import_module("ray_tpu.ops.attention")
    seen = []
    real, on_tpu = telemetry.inc, att._on_tpu
    telemetry.inc = lambda name, *a, tags=None, **k: seen.append((name, tags))
    att._on_tpu = lambda: True
    try:
        k = jax.random.split(jax.random.key(3), 3)
        w, b = jax.random.normal(k[1], (4, 128)), jnp.zeros((128,))
        ssm.causal_conv(jax.random.normal(k[0], (1, 1536, 128)), w, b)
        ssm.causal_conv(jax.random.normal(k[0], (1, 2048, 192)), w, b,
                        jnp.zeros((1, 2048), jnp.int32), start=64)
    finally:
        telemetry.inc, att._on_tpu = real, on_tpu
    assert [s for s in seen if s[0] == "ray_tpu_ssm_conv_path_total"] == [
        ("ray_tpu_ssm_conv_path_total",
         {"path": "xla", "taps": "4", "segments": "no"}),
        ("ray_tpu_ssm_conv_path_total",
         {"path": "xla", "taps": "4", "segments": "yes"})]
    # the cells' shapes tile: Granite's parts from column 4,096 of 8,512,
    # Nemotron's of 10,304
    tiles = lambda S, widths, backward: [
        ssm._cc_tile(S, lo, at.stop - at.start, backward)
        for lo, at in ssm._columns(4096, widths)]
    assert tiles(32768, (4096, 128, 128), False) == [
        (2048, 512), (2048, 128), (2048, 128)]
    assert tiles(8192, (4096, 1024, 1024), True) == [(1024, 512)] * 3
    assert ssm._cc_tile(1536, 0, 384, False) is None


def test_a_forced_kernel_on_a_shape_that_does_not_tile_names_the_shape():
    w, b = jnp.zeros((4, 128)), jnp.zeros((128,))
    with pytest.raises(ValueError, match="128 channels from column 0 of "
                                         "rows of 1536 tokens"):
        ssm.causal_conv(jnp.zeros((1, 1536, 128)), w, b,
                        impl="kernel_interpret")
    with pytest.raises(ValueError, match="column 64"):
        jax.grad(lambda c: jnp.sum(ssm.causal_conv(
            c, w, b, start=64, impl="kernel_interpret")))(
                jnp.zeros((1, 2048, 192)))


def test_gated_group_norm_norms_each_group_alone():
    k = jax.random.split(jax.random.key(4), 3)
    y, z = (jax.random.normal(k[i], (2, 7, 12)) for i in (0, 1))
    g = 1 + 0.1 * jax.random.normal(k[2], (12,))
    out = ssm.gated_group_norm(y, z, g, 3, 1e-5)
    v = (y * jax.nn.silu(z)).reshape(2, 7, 3, 4)
    want = (v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-5)
            ).reshape(2, 7, 12) * g
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)

    def plain(y, z, g):
        v = (y * jax.nn.silu(z)).reshape(2, 7, 3, 4)
        return (v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-5)
                ).reshape(2, 7, 12) * g

    do = jax.random.normal(jax.random.key(8), out.shape)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * do), (0, 1, 2))(
        y, z, g)
    for got, w in zip(grads(lambda *a: ssm.gated_group_norm(*a, 3, 1e-5)),
                      grads(plain)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(w), atol=2e-5)
    # Scaling one group's channels leaves the other groups as they were.
    other = ssm.gated_group_norm(y.at[..., :4].mul(5.0), z, g, 3, 1e-5)
    np.testing.assert_allclose(np.asarray(other[..., 4:]),
                               np.asarray(out[..., 4:]), atol=1e-6)


def _experts_inputs(T=24, E=16, M=12, X=8, Xh=4, k=3):
    key = jax.random.split(jax.random.key(5), 6)
    x = jax.random.normal(key[0], (T, E))
    routing = moe.sigmoid_routing(
        x, jax.random.normal(key[1], (E, X)),
        0.1 * jax.random.normal(key[2], (X,)), k, 2.5)
    w = [jax.random.normal(key[3 + i], shape) / 4 for i, shape in enumerate(
        [(Xh, E, M), (Xh, E, M), (Xh, M, E)])]
    return x, routing, w


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
@pytest.mark.parametrize("activation", ["relu2", "silu"])
def test_experts_against_a_dense_loop(activation, gated):
    """Every form the layer takes, a gate or none under either activation,
    in the routed experts and in ``afmoe._feed_forward`` (the shared one):
    the two agree on what they accept."""
    x, routing, (w_gate, w_up, w_down) = _experts_inputs()
    if not gated:
        w_gate = None
    start = 2
    out, (held, dropped) = moe.dropless_experts(
        x, routing, w_gate, w_up, w_down, held_start=start,
        activation=activation)
    act = moe.ACTIVATIONS[activation]
    expert = lambda e: (act(x @ w_gate[e]) * (x @ w_up[e]) if gated
                        else act(x @ w_up[e])) @ w_down[e]
    want = jnp.zeros_like(x)
    for e in range(w_up.shape[0]):
        coef = jnp.sum(jnp.where(routing.expert_index == start + e,
                                 routing.weights, 0.0), axis=-1)
        want = want + coef[:, None] * expert(e)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)
    assert int(dropped) == 0 and int(held) == int(
        jnp.sum(routing.counts[start:start + 4]))
    if activation == "relu2" and not gated:
        want = ref.held_experts(x, routing.expert_index, routing.weights,
                                w_up, w_down, start)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-4)
    shared = afmoe._feed_forward(x[None], w_gate[0] if gated else None,
                                 w_up[0], w_down[0], jnp.float32, activation)
    np.testing.assert_allclose(np.asarray(shared[0]), np.asarray(expert(0)),
                               atol=1e-4)


def test_gated_experts_give_to_the_bit_what_they_gave():
    """With a gate nothing changed: the call without the new argument, the
    call that names ``silu``, and the expression the layer was before this
    argument existed give the same bits, forward and gradient."""
    # 25 tokens: a buffer of T * k rows, which every call takes at once.
    x, routing, (w_gate, w_up, w_down) = _experts_inputs(T=25)

    def before(x, w_gate, w_up, w_down):
        Xh = w_gate.shape[0]
        local = routing.expert_index - 1
        local = jnp.where((local >= 0) & (local < Xh), local, Xh)
        at, _ = moe._places(local, Xh, moe.buffer_rows(*local.shape))
        rows = moe.rows_of_tokens(x, at)
        mm = lambda a, b: moe.grouped_matmul(a, b, at.sizes)
        h = jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up)
        return moe.tokens_from_rows(mm(h, w_down), routing.weights, at)

    now = lambda *a, **kw: moe.dropless_experts(a[0], routing, *a[1:],
                                                held_start=1, **kw)[0]
    args = (x, w_gate, w_up, w_down)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                               (0, 1, 2, 3))(*args)
    for other in (lambda *a: now(*a, activation="silu"), before):
        np.testing.assert_array_equal(np.asarray(now(*args)),
                                      np.asarray(other(*args)))
        for g, w in zip(grads(now), grads(other)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """The routed parts of all 8 shares of one expert layer (one expert
    each), with the shared expert counted once, equal the uncut reference
    layer."""
    cfg, params, bias, _ = _setup()
    layer = params["layers"][1]
    h = jax.random.normal(jax.random.key(9), (2, 24, cfg.hidden))
    s = _sizes(cfg)
    want, _ = ref.experts(h, layer, bias[0], s)
    shared = afmoe._feed_forward(h, None, layer["shared_up"],
                                 layer["shared_down"], cfg.dtype, "relu2")
    total = shared
    for e in range(cfg.num_experts):
        share = cfg.replace(experts_held=1, held_start=e)
        part = {**layer, "w_up": layer["w_up"][e:e + 1],
                "w_down": layer["w_down"][e:e + 1]}
        out, loads = afmoe._moe(share, h, part, bias[0], act="relu2")
        total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-4)


@pytest.mark.parametrize("Hkv,group", [(2, 16), (1, 16)])
def test_flash_at_sixteen_query_heads_a_key_head(Hkv, group):
    """Flash in interpret mode at a group of 16 (two stacks of 8 a key head)
    against ``reference_attention``: forward and the three gradients."""
    B, S, D = 1, 256, 128
    k = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(k[0], (B, Hkv * group, S, D))
    kk, v = (jax.random.normal(k[i], (B, Hkv, S, D)) for i in (1, 2))
    do = jax.random.normal(k[3], q.shape)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=True)
    plain = lambda q, k, v: reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(flash(q, kk, v)),
                               np.asarray(plain(q, kk, v)), atol=2e-3)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * do), (0, 1, 2))(
        q, kk, v)
    for g, w in zip(grads(flash), grads(plain)):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-3 * max(
            1.0, float(jnp.max(jnp.abs(w))))


def test_flash_takes_the_group_of_sixteen_in_stacks_of_eight():
    import importlib
    # the module: ``ray_tpu.ops.attention`` the attribute is the function
    att = importlib.import_module("ray_tpu.ops.attention")
    for kind in ("fwd", "dq", "dkv"):
        t = att._tiles(kind, 8192, 8192, 128, 16)
        assert t.heads == 8 and t.block_q == 512
        assert t.block_k == (256 if kind == "fwd" else 512)
        # the six cells' shapes keep their geometry
        assert att._tiles(kind, 8192, 8192, 128, 8) == t
    assert att._tiles("fwd", 4096, 4096, 128, 1).block_q == 1024


def test_train_step_trains_through_model_module_and_reports():
    """``make_lm_train_step`` finds the model by its configuration's module,
    carries the selection bias as state and reports the loads and the chunk
    carry."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import (StepState, make_lm_train_step,
                                       model_module)
    cfg = nemotron_h.nemotron_h_tiny(experts_held=4, held_start=2,
                                     layer_rows=1, remat="full",
                                     loss_chunks=2)
    assert model_module(cfg) is nemotron_h
    init_fn, step_fn, place = make_lm_train_step(
        cfg, build_mesh(MeshSpec(), devices=jax.devices()[:1]),
        learning_rate=1e-3)
    params, state = init_fn(jax.random.key(0))
    assert isinstance(state, StepState)
    assert state.model["bias"].shape == (2, 8)
    batch = place({"tokens": np.random.default_rng(0).integers(
        0, 256, (2, 56), dtype=np.int32)})
    losses = []
    for _ in range(3):
        params, state, m = step_fn(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[2] < losses[0]
    assert m["moe_choices"].shape == (2, 112, 4)
    assert float(m["moe_dropped"]) == 0
    assert 0 < float(m["ssm_chunk_carry"]) < 1
    assert float(jnp.max(jnp.abs(state.model["bias"]))) > 0


def test_a_mesh_and_a_pipeline_are_refused_by_name():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    cfg, params, bias, batch = _setup()
    with pytest.raises(NotImplementedError, match="pp_microbatches"):
        nemotron_h.loss_fn(params, batch, cfg.replace(pp_microbatches=2))
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(fsdp=2),
                                   devices=jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="nemotron_h on a mesh"):
            nemotron_h.loss_fn(params, batch, cfg)
        with pytest.raises(NotImplementedError, match="ssd_scan on a mesh"):
            ssm.ssd_scan(*_scan_inputs(), 16)
    finally:
        set_global_mesh(before)


def test_mamba_start_is_the_published_one():
    cfg = nemotron_h.nemotron_h_tiny(mamba_heads=256, mamba_head_dim=2,
                                     ssm_groups=2, layers=1, pattern="M")
    layer = jax.jit(lambda key: nemotron_h.init_params(cfg, key))(
        jax.random.key(1))["layers"][0]
    A = np.exp(np.asarray(layer["A_log"]))
    step = np.asarray(jax.nn.softplus(layer["dt_bias"]))
    assert 1.0 <= A.min() < 2.5 and 14.0 < A.max() <= 16.0
    assert 1e-3 * 0.999 <= step.min() < 2e-3 and 0.05 < step.max() <= 0.1001
    np.testing.assert_array_equal(np.asarray(layer["D"]), 1.0)


def test_published_stack_is_built_but_not_run():
    """The published configuration's shapes: 52 layers of 23 M, 23 E and 6 *,
    31.58 B parameters; the cell's share 1,267,091,328."""
    cfg = nemotron_h.NemotronHConfig()
    kinds = cfg.kinds
    assert (kinds.count("M"), kinds.count("E"), kinds.count("*")) == (23, 23,
                                                                      6)
    assert nemotron_h.num_params(cfg) == 31577937344
    share = cfg.replace(layers=13, experts_held=16, vocab_size=16384)
    assert "".join(share.kinds) == "MEMEM*EMEMEM*"
    assert nemotron_h.num_params(share) == 1267091328
    axes = nemotron_h.param_logical_axes(share)
    shapes = nemotron_h.param_shapes(share)
    flat = lambda t, leaf: jax.tree.leaves(t, is_leaf=leaf)
    for ax, (shape, *_rest) in zip(
            flat(axes, lambda x: isinstance(x, tuple)),
            flat(shapes, nemotron_h._lm.is_shape)):
        assert len(ax) == len(shape)
    with pytest.raises(ValueError, match="pattern"):
        cfg.replace(pattern="ME-M", layers=4).kinds


@pytest.mark.parametrize("fault", ["none", "rate_x400", "a_norm_moved",
                                   "decay_x1000"])
def test_the_update_is_judged_where_no_first_step_can_move_a_weight(fault):
    """``kinds/train_ssm.update_mismatch``: a sound AdamW step with bf16
    moments on weights at 1, spread round 0.5 and near zero reads 0, with
    the elements a step does move counted beside; a rate, a decay or a
    write that is off by enough to show is called."""
    from benchmark.kinds.train_ssm import update_mismatch
    opts = {"learning_rate": 1e-5, "adamw": {
        "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}}
    rng = np.random.default_rng(3)
    bf16 = lambda x: jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
    start = {"norm": bf16(np.ones(512)),
             "conv_w": bf16(rng.normal(size=(4, 2048)) * 0.5),
             "conv_b": bf16(rng.normal(size=2048) * 3e-3)}
    want = {k: (rng.normal(size=v.shape) * 10.0 ** rng.uniform(
        -9, -2, v.shape)).astype(np.float32) for k, v in start.items()}
    lr, decay = opts["learning_rate"], 0.1
    if fault == "rate_x400":
        lr *= 400
    if fault == "decay_x1000":
        decay *= 1000

    def step(p, g):
        p = np.asarray(p.astype(jnp.float32))
        mu = np.asarray(bf16(0.1 * g).astype(jnp.float32)) / 0.1
        nu = np.asarray(bf16(0.05 * g * g).astype(jnp.float32)) / 0.05
        return np.asarray(bf16(p - lr * (mu / (np.sqrt(nu) + 1e-8)
                                         + decay * p)).astype(jnp.float32))

    after = {k: step(start[k], want[k]) for k in start}
    if fault == "a_norm_moved":
        after["norm"] = np.where(np.arange(512) == 7, 1.0078125,
                                 after["norm"])
    got = update_mismatch(after, want, start, opts)
    assert 0 < got["step_update_sign_decides"] < 0.2
    if fault == "none":
        assert got["step_update_mismatch"] == 0, got
    else:
        assert got["step_update_mismatch"] > 0 and got["step_update_off_at"]

