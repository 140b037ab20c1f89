"""The motif model (Motif-3-Beta's block) against its plain reference: loss,
its parts and every gradient, window and full layers on the one latent stack
under a key head shared by five query heads, the differential pair and its
gate, PolyNorm in all three kinds of feed-forward, with and without the
prediction module.  (The two cases are this file alone: they are the
longest of the suite.  The pieces, the train step, its scopes and counters,
the published stack and the cell's rehearsal: ``tests/test_motif_cell.py``.)"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import motif

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_motif as ref  # noqa: E402
from benchmark.archs import Motif as arch  # noqa: E402

from motif_cases import _setup, _sizes  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")


@pytest.mark.parametrize("module", [1, 0])
def test_model_matches_reference_loss_and_every_gradient(module):
    """The program's loss, its parts and its gradients, on a share of the
    experts (2 of 8 from the fifth), window (16 of 32 tokens) and full
    layers both in the stack (one scanned body, the kind a traced flag),
    with and without the prediction module, against the reference's walk
    in blocks (the judged leaves of every kind; what the chip's check
    runs) and, without the module, against ``jax.grad`` of the reference's
    pieces put together (EVERY leaf; with the module that one compile is
    two minutes of this suite, and the module's own leaves are Xing4.0's,
    judged there).  Float32 on both sides; two evaluations of the reference
    alone differ by up to 1e-3 in a gradient here (the walk against the
    whole), so a leaf is held to 3e-2 of its norm and the judged tree to
    1e-2, as Xing4.0's; PolyNorm's four numbers a module to 1e-1: each is
    one sum over every token and channel of its module of products of
    mixed sign, where float32's order of summation shows (the reference's
    whole, jitted and not, differs from itself by 2.2e-2 of ``mlp_poly``'s
    norm with the module in the stack; alone the activation's gradients
    are exact, ``tests/test_ops.py``)."""
    cfg, params, bias, batch = _setup(experts_held=2, held_start=4,
                                      mtp_layers=module, layers=3,
                                      sliding_window_period=2)
    assert params["moe"]["w_gate"].shape[1] == 2
    # window, full, window (and the module's full): three layers, as
    # Xing4.0's test has, because the stream amplifies float32's rounding
    # about fivefold a layer (a relative 1e-6 in the embedding is 2e-4 after
    # three layers and 5e-4 after five; Xing4.0's reference 1e-4 after
    # three), and five layers' gradients differ between two evaluations of
    # the reference alone by more than 3e-2 of a leaf.
    assert [cfg.full(i) for i in range(4)] == [False, True, False, True]
    s = _sizes(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda p: motif.loss_and_report(p, batch, cfg, {"bias": bias}),
        has_aux=True))(params)
    want, parts, judged, tops = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for name in ("main_loss", "mtp_loss"):
        assert abs(float(report[name]) - float(parts[name])) < 1e-5 * max(
            float(parts[name]), 1.0), name
    assert (float(parts["mtp_loss"]) > 0) == bool(module)
    assert float(ref.relative_distance(arch.judged_of(grads), judged)) < 1e-2
    for alone in (arch.norms_of, arch.maps_of, arch.polys_of,
                  arch.lambdas_of):
        assert float(ref.relative_distance(alone(grads), alone(judged))) \
            < 1e-2, alone.__name__
    np.testing.assert_array_equal(tops, report["top"])
    if module:
        return

    def whole(params):
        X = ref._lanes(params["embed"][batch["tokens"]], s["n"])
        for w, b, full in ref._stack(params, bias, s):
            X, _ = ref.layer(X, w, b, s, full)
        return ref.tail(jnp.sum(X, axis=2), params["final_norm"],
                        params["lm_head"], params["embed"], None, bias[-1],
                        batch["tokens"], batch["loss_mask"], s)[0]

    whole_loss, want_grads = jax.jit(jax.value_and_grad(whole))(params)
    assert abs(float(whole_loss) - float(want)) < 1e-5 * float(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and float(jnp.linalg.norm(w)) > 0, path
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(g - w)) < (
            1e-1 if "poly" in name else 3e-2) * float(
            jnp.linalg.norm(w)), name
    np.testing.assert_array_equal(
        ref.routing(params, bias, batch["tokens"], s), tops)
    # Next-token logits too (the module is a training part).
    np.testing.assert_allclose(
        motif.forward(params, batch["tokens"], cfg, {"bias": bias}),
        ref.logits(params, bias, batch["tokens"], s), atol=2e-3)
    # The int8 control is another function: it fails where rounding passes.
    _, _, control, _ = ref.loss_judged_grads_and_routing(
        params, bias, batch["tokens"], batch["loss_mask"], s, quant="int8")
    assert float(ref.relative_distance(control, judged)) > 3e-2
