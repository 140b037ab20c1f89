"""The granite_hybrid model (Granite-4.0-H-Micro's stack: a mixer and a
feed-forward in every layer, one B and C for all the mixer's heads, four
multipliers, a tied head) against its plain reference, with and without
document boundaries, and the property that ties packing to the model: a
packed row is its documents run alone."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import granite_hybrid, nemotron_h

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_granite_hybrid as ref  # noqa: E402
from benchmark.archs import granitemoehybrid as arch  # noqa: E402

from ssm_segments_cases import _ids  # noqa: E402

pytestmark = pytest.mark.usefixtures("no_mesh_left_by_another_file")

#: documents of a row of 56 at a chunk of 16: boundaries on a chunk's edge
#: (16), one token after it (33), and several inside one chunk (36, 39)
LENGTHS = (16, 17, 3, 3, 17)


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "kinds": "".join(arch.LETTER[k] for k in cfg.kinds),
            "H": cfg.heads, "Hkv": cfg.kv_heads, "D": cfg.head_dim,
            "M": cfg.mlp_dim, "Hm": cfg.mamba_heads,
            "P": cfg.mamba_head_dim, "N": cfg.ssm_state,
            "G": cfg.ssm_groups, "K": cfg.conv_kernel, "Q": cfg.chunk_size,
            "eps": cfg.norm_eps,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling}


@functools.lru_cache(maxsize=None)
def _setup(seed=0, rows=2, seq=56, **kw):
    """Tiny widths (``granite_hybrid_tiny``), the parameters made once under
    one ``jax.jit`` with the norms and ``D`` shaken away from one."""
    cfg = granite_hybrid.granite_hybrid_tiny(**kw)

    @jax.jit
    def make(key, shake_key):
        params = granite_hybrid.init_params(cfg, key)
        keys = iter(jax.random.split(shake_key, 64))

        def shake(path, a):
            name = str(path[-1])
            if "norm" in name or "'D'" in name:
                return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
            return a

        return jax.tree_util.tree_map_with_path(shake, params)

    params = make(jax.random.key(seed), jax.random.key(seed + 1))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (rows, seq), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (rows, seq),
                                              dtype=np.int32))}
    return cfg, params, batch


def _whole(params, batch, ids, s):
    """The reference's loss by ``jax.grad`` of its pieces put together."""
    lg = ref.logits(params, batch["tokens"], ids, s)
    t = batch["tokens"]
    targets = jnp.concatenate([t[:, 1:], jnp.zeros_like(t[:, :1])], 1)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, targets[..., None], -1)[..., 0]
    mask = batch["loss_mask"].astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.sum(mask)


@pytest.mark.parametrize("packed", [False, True], ids=["one-document",
                                                       "packed"])
def test_model_matches_reference_loss_and_every_gradient(packed):
    """The program's loss and the gradient of every leaf, the tied table's
    among them, against ``jax.grad`` of the reference put together, and the
    judged leaves against the reference's walk in blocks (what the chip's
    check runs).  Float32 on both sides."""
    cfg, params, batch = _setup()
    s = _sizes(cfg)
    ids = _ids(LENGTHS, 2) if packed else None
    full = {**batch, **({"segment_ids": ids} if packed else {})}
    (loss, report), grads = jax.jit(jax.value_and_grad(
        lambda p: granite_hybrid.loss_and_report(p, full, cfg),
        has_aux=True))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: _whole(p, batch, ids, s)))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=2e-3, atol=2e-6 + 2e-4 * float(jnp.max(jnp.abs(w))),
            err_msg=jax.tree_util.keystr(path))
    walk_loss, walked = ref.loss_and_judged_grads(
        params, batch["tokens"], batch["loss_mask"], ids, s)
    np.testing.assert_allclose(walk_loss, want_loss, rtol=2e-5)
    assert float(ref.relative_distance(walked, arch.judged_of(want))) < 1e-4
    assert float(ref.relative_distance(arch.judged_of(grads), walked)) < 1e-3
    assert ("pack_pairs_share" in report) == packed
    if packed:
        assert float(report["pack_documents_a_row"]) == len(LENGTHS)
        np.testing.assert_allclose(
            report["pack_pairs_share"],
            sum(n * n for n in LENGTHS) / 56 ** 2, rtol=1e-6)
        # chunks of 16 over 56 tokens: three whole ones; a document starts
        # in the second (16), the third (33, 36, 39): two mixers, two rows
        assert float(report["ssm_chunks_with_boundary"]) == 2 * 2 * 2


def test_a_packed_row_is_its_documents_run_alone():
    """Outputs and gradients of a packed row equal those of each document
    run as a row of its own (the sum of their losses' gradients)."""
    cfg, params, batch = _setup(rows=1)
    tokens, mask = batch["tokens"], batch["loss_mask"].astype(jnp.float32)
    ids = _ids(LENGTHS)
    logits = jax.jit(lambda p: granite_hybrid.forward(
        p, tokens, cfg, segment_ids=ids))(params)
    weight = jax.random.normal(jax.random.key(5), logits.shape)
    packed = jax.jit(jax.grad(lambda p: jnp.sum(granite_hybrid.forward(
        p, tokens, cfg, segment_ids=ids) * weight)))(params)

    @jax.jit
    def one(p, tokens, weight):
        """A document's logits and gradients: one program a length (16, 17
        and 3: the lengths are the case's and stay as they are)."""
        def loss(p):
            out = granite_hybrid.forward(p, tokens, cfg)
            return jnp.sum(out * weight), out
        (_, out), g = jax.value_and_grad(loss, has_aux=True)(p)
        return out, g

    alone, total, at = [], None, 0
    for n in LENGTHS:
        part = slice(at, at + n)
        out, g = one(params, tokens[:, part], weight[:, part])
        alone.append(out)
        total = g if total is None else jax.tree.map(jnp.add, total, g)
        at += n
    np.testing.assert_allclose(logits, jnp.concatenate(alone, 1), rtol=1e-4,
                               atol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(packed),
                            jax.tree.leaves(total)):
        np.testing.assert_allclose(
            g, w, rtol=1e-3, atol=1e-5 + 1e-4 * float(jnp.max(jnp.abs(w))),
            err_msg=jax.tree_util.keystr(path))
    del mask


def test_without_ids_a_batch_is_what_it_was_and_ids_of_one_document_too():
    """No ``segment_ids`` and ids that name one document give the same
    loss; the report then names no packing."""
    cfg, params, batch = _setup()
    plain, report = granite_hybrid.loss_and_report(params, batch, cfg)
    same, _ = granite_hybrid.loss_and_report(
        params, {**batch, "segment_ids": jnp.full((2, 56), 7)}, cfg)
    np.testing.assert_allclose(plain, same, rtol=1e-6)
    assert set(report) == {"ssm_chunk_carry"}


def test_layers_under_remat_and_rows_at_a_time_give_the_same_loss():
    cfg, params, batch = _setup()
    full = {**batch, "segment_ids": _ids(LENGTHS, 2)}
    want, _ = granite_hybrid.loss_and_report(params, full, cfg)
    for kw in ({"remat": True}, {"layer_rows": 1, "remat": "full"},
               {"loss_chunks": 4}):
        got, report = jax.jit(lambda p, kw=kw: granite_hybrid.loss_and_report(
            p, full, cfg.replace(**kw)))(params)
        np.testing.assert_allclose(got, want, rtol=1e-5, err_msg=str(kw))
        assert float(report["ssm_chunks_with_boundary"]) == 8, kw


def test_parameters_and_the_mixer_it_shares():
    """The published sizes count 3.19 B parameters, the first ten layers
    with the tied table 952 M (ISSUE 65's table); the mixer's body is
    ``nemotron_h._mixer`` itself."""
    cfg = granite_hybrid.GraniteHybridConfig()
    assert cfg.kinds.count("attention") == 4 and cfg.kinds[5] == "attention"
    assert cfg.mamba_dim == 2 * cfg.hidden and cfg.conv_dim == 4352
    assert granite_hybrid.num_params(cfg) == 3_191_396_096
    assert granite_hybrid.num_params(cfg.replace(layers=10)) == 951_991_232
    assert granite_hybrid.nemotron_h is nemotron_h
    assert "lm_head" not in granite_hybrid.param_shapes(cfg)


def test_a_mesh_is_refused_by_name():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import set_global_mesh
    cfg, params, batch = _setup()
    try:
        set_global_mesh(build_mesh(MeshSpec(fsdp=2),
                                   devices=jax.devices()[:2]))
        with pytest.raises(NotImplementedError, match="granite_hybrid on a"):
            granite_hybrid.loss_fn(params, batch, cfg)
        from ray_tpu.ops.attention import attention
        q = jnp.zeros((2, 2, 16, 8))
        with pytest.raises(NotImplementedError, match="segment_ids on a"):
            attention(q, q, q, impl="flash_interpret",
                      mesh=build_mesh(MeshSpec(fsdp=2),
                                      devices=jax.devices()[:2]),
                      segment_ids=jnp.zeros((2, 16), jnp.int32))
    finally:
        set_global_mesh(None)
    with pytest.raises(NotImplementedError, match="pp_microbatches"):
        granite_hybrid.loss_fn(params, batch, cfg.replace(pp_microbatches=2))


def test_train_step_takes_segment_ids_and_reports_the_packing():
    """``make_lm_train_step`` hands a batch's ``segment_ids`` to the loss,
    and the step's metrics carry the report."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import set_global_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    cfg, _, batch = _setup()
    try:
        init_fn, step_fn, place = make_lm_train_step(
            cfg, build_mesh(MeshSpec(), devices=jax.devices()[:1]),
            learning_rate=1e-3)
        params, state = init_fn(jax.random.key(0))
        full = place({**batch, "segment_ids": _ids(LENGTHS, 2)})
        first = None
        for _ in range(3):
            params, state, m = step_fn(params, state, full)
            first = float(m["loss"]) if first is None else first
        assert float(m["loss"]) < first
        assert float(m["pack_documents_a_row"]) == len(LENGTHS)
        assert float(m["ssm_chunks_with_boundary"]) == 8
        assert 0 < float(m["ssm_chunk_carry"]) < 1
    finally:
        set_global_mesh(None)
