"""The xing4 model (Xing4.0's block) against its plain reference: both losses
and every gradient; yarn's rotary table, the prediction module's targets,
what is refused, the published stack.  (Cut in PR 59, so that the suite's
last files are short ones: remat and rows at a time, the hyper-connection
passes and Sinkhorn's maps, one lane, the share of an expert-parallel layer
in ``tests/test_xing4_layers.py``; flash attention with a value head size
of its own and the compiled step's scopes in
``tests/test_xing4_kernels.py``; the train step and the cell's rehearsal in
``tests/test_xing4_cell.py``.)"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import _lm, xing4
from ray_tpu.ops.rope import rope_frequencies

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_xing4 as ref  # noqa: E402
from benchmark.archs import xing4_0 as arch  # noqa: E402

from xing4_cases import _setup, _sizes  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


def test_model_matches_reference_both_losses_and_every_gradient():
    """The program's loss, its two parts and the gradient of every leaf, on a
    share of the experts (2 of 8 from the fifth), against ``jax.grad`` of
    the reference's pieces put together (every leaf) and against the
    reference's walk in blocks (the judged leaves; what the chip's check
    runs).  Float32 on both sides; two evaluations of the reference alone
    differ by up to 1e-3 in a gradient here (the walk against the whole), so
    a leaf is held to 3e-2 of its norm and the judged tree to 1e-2."""
    cfg, params, bias, batch = _setup(seq=32, experts_held=2, held_start=4)
    assert params["moe"]["w_gate"].shape[1] == 2
    s = _sizes(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda p: xing4.loss_and_report(p, batch, cfg, {"bias": bias}),
        has_aux=True))(params)

    def whole(params):
        X = ref._lanes(params["embed"][batch["tokens"]], s["n"])
        for w, b in ref._stack(params, bias, s):
            X, _ = ref.layer(X, w, b, s)
        return ref.tail(jnp.sum(X, axis=2), params["final_norm"],
                        params["lm_head"], params["embed"], params["mtp"],
                        bias[-1], batch["tokens"], batch["loss_mask"], s)

    (want, (main, module, _)), want_grads = jax.jit(jax.value_and_grad(
        whole, has_aux=True))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert abs(float(report["main_loss"]) - float(main)) < 1e-5 * float(main)
    assert abs(float(report["mtp_loss"]) - float(module)) < 1e-5 * float(
        module)
    assert abs(float(loss) - float(main + 0.3 * module)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and float(jnp.linalg.norm(w)) > 0, path
        assert float(jnp.linalg.norm(g - w)) < 3e-2 * float(
            jnp.linalg.norm(w)), jax.tree_util.keystr(path)
    w_loss, parts, judged, tops = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert abs(float(w_loss) - float(want)) < 1e-5 * float(want)
    assert abs(float(parts["mtp_loss"]) - float(module)) < 1e-5
    assert float(ref.relative_distance(arch.judged_of(grads), judged)) < 1e-2
    np.testing.assert_array_equal(tops, report["top"])
    np.testing.assert_array_equal(
        ref.routing(params, bias, batch["tokens"], s), tops)
    # The int8 control is another function: it fails where rounding passes.
    _, _, control, _ = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s, quant="int8")
    assert float(ref.relative_distance(control, judged)) > 3e-2


def test_yarn_table_is_the_closed_form_at_three_positions():
    cfg = xing4.Xing4Config()
    cos, sin = rope_frequencies(64, 8192, 10000.0, cfg.yarn)
    lo = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                    / (2 * math.log(10000)))
    hi = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                   / (2 * math.log(10000)))
    assert (lo, hi) == (10, 23)
    for pos in (1, 777, 8191):
        for i in (0, 9, 10, 16, 23, 31):
            theta = 10000.0 ** (-2 * i / 64)
            ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
            freq = theta * (1 - ramp) + theta / 64 * ramp
            assert abs(float(cos[pos, i]) - math.cos(pos * freq)) < 2e-3
            assert abs(float(sin[pos, i]) - math.sin(pos * freq)) < 2e-3
    # mscale = mscale_all_dim: the tables are not scaled, the scores are.
    assert cfg.yarn.table_scale == 1.0
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 1.41589 ** 2) < 1e-5
    np.testing.assert_allclose(jnp.cos(ref.yarn_angles(64, _sizes(cfg))),
                               cos[:64], atol=2e-3)


def test_module_is_judged_on_token_t_plus_2_up_to_s_minus_3():
    tokens = jnp.arange(10, 18)[None]                   # S = 8
    targets, mask, _ = _lm.targets_and_mask({"tokens": tokens})
    t2, m2 = xing4.mtp_targets_and_mask(targets, mask)
    np.testing.assert_array_equal(t2[0, :6], tokens[0, 2:])
    np.testing.assert_array_equal(m2[0], [1, 1, 1, 1, 1, 1, 0, 0])
    # Under a mask with holes the module follows the main loss's, a
    # position on.
    mask = jnp.asarray([[1, 0, 1, 1, 0, 1, 1, 0]], jnp.float32)
    np.testing.assert_array_equal(
        xing4.mtp_targets_and_mask(targets, mask)[1][0],
        [0, 1, 1, 0, 1, 1, 0, 0])


def test_a_mesh_and_a_pipeline_are_refused_by_name():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    cfg = xing4.xing4_tiny()
    params = jax.eval_shape(lambda k: xing4.init_params(cfg, k),
                            jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    with pytest.raises(NotImplementedError, match="pp_microbatches"):
        jax.eval_shape(lambda p, b: xing4.loss_fn(
            p, b, cfg.replace(pp_microbatches=2)), params, batch)
    before = get_global_mesh()
    try:
        set_global_mesh(build_mesh(MeshSpec(fsdp=2),
                                   devices=jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="xing4 on a mesh"):
            jax.eval_shape(lambda p, b: xing4.loss_fn(p, b, cfg), params,
                           batch)
    finally:
        set_global_mesh(before)


def test_published_stack_is_built_but_not_run():
    cfg = xing4.Xing4Config()
    shapes = jax.eval_shape(
        lambda k: xing4.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        xing4.num_params(cfg)
    assert shapes["moe"]["w_gate"].shape == (38, 64, 3584, 1024)
    assert shapes["moe"]["hc_attn_phi"].shape == (38, 14336, 24)
    assert shapes["dense"]["wq_b"].shape == (2, 768, 32, 192)
    # The benchmark's cut: its layout is the program's, its count the
    # issue's.
    with open(os.path.join(
            ROOT, "benchmark/configs/xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    s = arch.sizes_of(config)
    cut = arch.program_config(s, 8192, config["train"])
    shape_of = lambda tree: jax.tree.map(lambda x: x[0], tree,
                                         is_leaf=_lm.is_shape)
    assert shape_of(arch.shapes(s)) == shape_of(xing4.param_shapes(cut))
    assert arch.parameters(s)["held"] == xing4.num_params(cut) == \
        config["parameters"] == 913473348
    assert s == {**_sizes(cut),
                 "bias_update_rate": cut.bias_update_rate}
