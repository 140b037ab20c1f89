"""What outlives a layer's remat (PR 58): the flash kernels' two results are
named (``ops.attention.FLASH_OUT`` / ``FLASH_LSE``), ``_lm.remat`` keeps the
names it is handed, and ``_lm.flash_keep`` hands them over where the whole
step's fit a sixteenth of the device.  On the CPU: interpret kernels at small
shapes, jaxprs counted, nothing compiled for a described chip."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import _lm
from ray_tpu.ops.attention import FLASH_LSE, FLASH_OUT, flash_attention

NAMES = (FLASH_OUT, FLASH_LSE)
B, S, H, E = 2, 128, 2, 32


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def forward_kernels(fn, *args) -> int:
    """The forward flash kernels in ``fn``'s jaxpr (a scanned body's once)."""
    text = [str(eqn.params.get("name_and_src_info", eqn.params.get("name")))
            for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]
    assert text, "no Pallas call in the jaxpr"
    return sum("flash_fwd" in name for name in text)


def _attend(case):
    """(head size of the weights' projection, x [rows, S, H, D] -> the
    call's result [rows, S, H, Dv]) for a kind of flash call."""
    if case == "parts":
        def parts(p):                   # p [rows, S, H, 128 + 64 + 128]
            q_n, q_r, v = p[..., :128], p[..., 128:192], p[..., 192:]
            kv = jnp.concatenate([q_n, v], axis=-1)
            k_r = jnp.swapaxes(q_r[:, :, :1], 1, 2)
            return flash_attention((q_n, jnp.swapaxes(q_r, 1, 2)), (kv, k_r),
                                   None, interpret=True)
        return 320, parts
    D = 64 if case == "d64" else 128
    window = 32 if case == "window" else None

    def plain(p):
        q = jnp.swapaxes(p, 1, 2)
        return flash_attention(q, q, p, interpret=True, window=window,
                               rows=True)
    return D, plain


def _stack(case):
    """(loss(keep, x, ws) of a two-layer stack as the models build theirs:
    a ``lax.scan`` over the layers, the layer under ``_lm.remat`` a row at
    a time by ``lax.map``; x; the layers' weights)."""
    D, attend = _attend(case)

    def layer(x, w):
        w_in, w_out = w
        o = attend(jnp.einsum("bse,ehd->bshd", x, w_in))
        return x + jnp.einsum("bshd,hde->bse", o, w_out)

    def loss(keep, x, ws):
        one = _lm.remat(layer, "full", keep)

        def body(x, w):
            y = jax.lax.map(lambda rows: one(rows, w),
                            x.reshape(B, 1, S, E))
            return y.reshape(x.shape), None
        x, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(x * x)

    keys = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(keys[0], (B, S, E), jnp.float32)
    Dv = 128 if case == "parts" else D
    ws = (0.2 * jax.random.normal(keys[1], (2, E, H, D), jnp.float32),
          0.2 * jax.random.normal(keys[2], (2, H, Dv, E), jnp.float32))
    return loss, x, ws


CASES = ["plain", "window", "d64", "parts"]


@pytest.mark.parametrize("case", CASES)
def test_the_forward_kernel_runs_once_a_layer_with_the_names_kept(case):
    """The gradient's jaxpr of a two-layer stack (one scanned body) holds
    the forward kernel once with the names kept and twice without, through
    the row map and the scan."""
    loss, x, ws = _stack(case)
    grad = lambda keep: jax.grad(lambda ws: loss(keep, x, ws))
    assert forward_kernels(grad(()), ws) == 2
    assert forward_kernels(grad(NAMES), ws) == 1


@pytest.mark.parametrize("case", CASES)
def test_loss_and_gradients_are_the_same_bits_with_and_without(case):
    loss, x, ws = _stack(case)
    run = lambda keep: jax.jit(jax.value_and_grad(
        lambda x, ws: loss(keep, x, ws), argnums=(0, 1)))(x, ws)
    (want, want_g), (got, got_g) = run(()), run(NAMES)
    assert np.isfinite(want) and float(want) == float(got)
    for a, b in zip(jax.tree.leaves(want_g), jax.tree.leaves(got_g)):
        assert np.any(np.asarray(a)) and np.array_equal(a, b)


@pytest.fixture
def limit(monkeypatch):
    """Sets the device's ``bytes_limit`` as ``_lm.flash_keep`` reads it."""
    return lambda n: monkeypatch.setattr(_lm, "_bytes_limit", lambda: n)


SHAPE = (4, 8, 2, 16)                   # [B, S, H, Dv]
#: out (bf16) + lse (float32) of 3 calls of SHAPE
NEED = 3 * 4 * 8 * 2 * (16 * 2 + 4)


@pytest.mark.parametrize("bytes_limit, mode, calls, chips, kept", [
    (None, "full", 3, 1, False),                    # the CPU states none
    (16 * NEED, "full", 3, 1, True),                # equal to the budget
    (16 * NEED, True, 3, 1, True),
    (16 * (NEED - 1), "full", 3, 1, False),         # one byte over it
    (16 * NEED // 4, "full", 3, 4, True),           # a row a chip
    (16 * NEED // 4, "full", 3, 3, False),          # 4 rows over 3: 2 a chip
    (16 * NEED // 2, "full", 3, 3, True),
    (1 << 40, "full", 0, 1, False),                 # no flash call to keep
    (1 << 40, "dots", 3, 1, False),                 # not full remat's
    (1 << 40, "dots_nobatch", 3, 1, False),
    (1 << 40, False, 3, 1, False)])
def test_the_rule_at_its_edges(limit, bytes_limit, mode, calls, chips, kept):
    limit(bytes_limit)
    got = _lm.flash_keep(mode, calls, SHAPE, jnp.bfloat16, chips=chips)
    assert got == (NAMES if kept else ())


def test_the_constant_splits_the_benchmarks_cells_as_its_comment_says(limit):
    """The table beside ``FLASH_KEEP_SHARE``, against a v5e's limit."""
    limit(16_909_926_400)
    cells = {"lfm2": (2, (4, 8192, 32, 64)), "ling": (1, (4, 8192, 32, 128)),
             "nemotron": (2, (4, 8192, 32, 128)),
             "motif": (4, (1, 8192, 80, 128)),
             "xing4.0": (6, (2, 8192, 32, 128)),
             "mistral": (28, (4, 4096, 32, 128), 4),
             "yi": (24, (4, 4096, 16, 128)),
             "trinity": (9, (4, 8192, 32, 128)),
             "ouro": (48, (4, 4096, 16, 128)),
             "kanana": (12, (4, 8192, 32, 128))}
    kept = {name for name, (calls, shape, *chips) in cells.items()
            if _lm.flash_keep("full", calls, shape, jnp.bfloat16, *chips)}
    assert kept == {"lfm2", "ling", "nemotron", "motif", "xing4.0", "mistral"}
    assert _lm.FLASH_KEEP_SHARE == 1 / 16


def _dense(x, w):
    return jnp.sum(jnp.tanh(x @ w) ** 2)


def _named(x, w):
    y = jax.ad_checkpoint.checkpoint_name(jnp.tanh(x @ w), FLASH_OUT)
    return jnp.sum(jnp.sin(y) ** 2)


def _grad_text(fn):
    x, w = jnp.ones((4, 8)), jnp.ones((8, 8))
    return str(jax.make_jaxpr(jax.grad(fn, argnums=1))(x, w))


def test_remat_with_nothing_to_keep_is_the_program_it_was():
    want = jax.checkpoint(
        _named, policy=jax.checkpoint_policies.nothing_saveable)
    for mode in (True, "full"):
        assert _grad_text(_lm.remat(_named, mode)) == _grad_text(want)
        # ... and with the name kept it is another one
        assert _grad_text(_lm.remat(_named, mode, NAMES)) != _grad_text(want)


@pytest.mark.parametrize("mode", ["dots", "dots_nobatch", False])
def test_the_other_modes_take_no_notice_of_the_names(mode):
    assert _grad_text(_lm.remat(_named, mode, NAMES)) == _grad_text(
        _lm.remat(_named, mode))
    if mode is False:
        assert _lm.remat(_named, mode, NAMES) is _named


def test_remat_refuses_a_mode_it_does_not_know():
    with pytest.raises(ValueError, match="unknown remat mode"):
        _lm.remat(_dense, "mlp_only", NAMES)


def _counter_lines():
    from ray_tpu.util import metrics as metrics_mod
    return [line for line in metrics_mod.prometheus_text().splitlines()
            if line.startswith("ray_tpu_remat_kept_total{")]


def test_the_counter_says_what_the_rule_saw_and_did(limit):
    from ray_tpu.util import metrics as metrics_mod
    from ray_tpu.util import telemetry
    name = "ray_tpu_remat_kept_total"
    assert telemetry.CATALOG[name]["type"] == "counter"
    assert tuple(telemetry.CATALOG[name]["tag_keys"]) == (
        "names", "kept", "calls", "bytes", "budget")
    metrics_mod._reset_for_tests()
    limit(16 * NEED)
    _lm.flash_keep("full", 3, SHAPE, jnp.bfloat16)
    _lm.flash_keep("full", 6, SHAPE, jnp.bfloat16)
    limit(None)
    _lm.flash_keep("full", 3, SHAPE, jnp.bfloat16)
    lines = _counter_lines()
    assert len(lines) == 3 and all(
        'names="flash_out+flash_lse"' in line for line in lines)
    kept, = [line for line in lines if 'kept="true"' in line]
    assert all(tag in kept for tag in (
        'calls="3"', f'bytes="{NEED}"', f'budget="{NEED}"'))
    assert any(f'bytes="{2 * NEED}"' in line and 'kept="false"' in line
               and f'budget="{NEED}"' in line for line in lines)
    assert any('budget="None"' in line and 'kept="false"' in line
               for line in lines)


# ---- the stacks: every model that puts flash under ``_lm.remat`` asks the
# rule once a stack traced and hands every layer's remat what it said.

#: model -> the attention calls of its tiny configuration's step
STACKS = {"llama": 2, "ouro": 3 * 4, "afmoe": 5, "xing4": 4,
          "deepseek_v3": 3, "motif": 6, "nemotron_h": 1, "lfm2": 2,
          "bailing_hybrid": 1}


def _model_case(name, **kw):
    """(loss(params), the parameters' shapes) of a tiny model under full
    remat, a row a layer call where the model has the option."""
    module = importlib.import_module(f"ray_tpu.models.{name}")
    if name == "llama":
        cfg = module.llama_tiny().replace(remat="full", **kw)
    else:
        cfg = getattr(module, f"{name}_tiny")(remat="full", **kw, **(
            {} if name == "ouro" else {"layer_rows": 1}))
    tokens = jnp.zeros((2, min(cfg.max_seq_len, 128)), jnp.int32)
    params = jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.key(0)))
    return lambda p: module.loss_fn(p, {"tokens": tokens}, cfg), params


@pytest.mark.parametrize("name", list(STACKS))
def test_every_stack_asks_the_rule_and_hands_on_what_it_says(
        name, limit, monkeypatch):
    from ray_tpu.parallel.mesh import set_global_mesh
    from ray_tpu.util import metrics as metrics_mod
    set_global_mesh(None)
    # (Llama's attention, Ouro's too, counts its calls only where the
    # configuration lets them be flash's)
    loss, params = _model_case(name, **(
        {"attention_impl": "auto"} if name == "ouro" else {}))
    remat, seen = _lm.remat, []

    def recording(block, mode, keep=()):
        seen.append((mode, keep))
        return remat(block, mode, keep)
    monkeypatch.setattr(_lm, "remat", recording)
    for bytes_limit in (1 << 40, None):
        limit(bytes_limit)
        metrics_mod._reset_for_tests()
        del seen[:]
        jax.eval_shape(lambda p: loss(p), params)   # a trace of its own
        line, = _counter_lines()        # one a stack traced
        assert f'calls="{STACKS[name]}"' in line, line
        assert ('kept="true"' in line) == bool(bytes_limit), line
        assert seen and all(got == ("full", NAMES if bytes_limit else ())
                            for got in seen), seen


def test_a_models_gradient_holds_the_forward_kernel_once_a_layer(limit):
    """Llama's scanned block with interpret kernels: the gradient's jaxpr
    holds the forward kernel twice without a limit, once with room."""
    loss, params = _model_case("llama", attention_impl="flash_interpret",
                               head_dim=128, max_seq_len=128)
    counts = []
    for bytes_limit in (None, 1 << 40):
        limit(bytes_limit)
        counts.append(forward_kernels(jax.grad(loss), params))
    assert counts == [2, 1]


def test_on_a_mesh_a_chip_counts_its_own_rows_and_heads(limit):
    """Llama on a {fsdp: 2, tp: 2} mesh: the rule is asked about a call's
    result as the island splits it, rows over fsdp and heads over tp."""
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import set_global_mesh
    from ray_tpu.util import metrics as metrics_mod
    cfg = llama.llama_tiny().replace(attention_impl="flash_interpret",
                                     remat="full", head_dim=128)
    tokens = jnp.zeros((4, 128), jnp.int32)
    one = cfg.layers * 4 * 128 * cfg.heads * (128 * 2 + 4)
    limit(16 * one // 4)
    try:
        for spec, chips in ((MeshSpec(), 1), (MeshSpec(fsdp=2, tp=2), 4)):
            set_global_mesh(build_mesh(spec, devices=jax.devices()[:chips]))
            metrics_mod._reset_for_tests()
            keep = llama.flash_keep(cfg, tokens, cfg.layers)
            assert keep == (NAMES if chips == 4 else ())
            line, = _counter_lines()
            assert f'bytes="{one // chips}"' in line
    finally:
        set_global_mesh(None)
