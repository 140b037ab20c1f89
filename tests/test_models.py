"""Model + SPMD train-step tests on the 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import (LlamaConfig, forward, init_params, llama_tiny,
                            loss_fn, param_logical_axes)
from ray_tpu.models.llama import num_params
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.parallel.spmd import make_lm_train_step


def _batch(cfg, B=2, S=64, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    return {"tokens": jnp.asarray(toks)}


class TestLlamaForward:
    def test_shapes_and_finite(self):
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False)
        params = init_params(cfg, jax.random.key(0))
        logits = forward(params, _batch(cfg)["tokens"], cfg)
        assert logits.shape == (2, 64, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False)
        params = init_params(cfg, jax.random.key(0))
        t1 = _batch(cfg, B=1)["tokens"]
        t2 = t1.at[0, 50].set((t1[0, 50] + 1) % cfg.vocab_size)
        l1 = forward(params, t1, cfg)
        l2 = forward(params, t2, cfg)
        np.testing.assert_allclose(np.asarray(l1[0, :50]),
                                   np.asarray(l2[0, :50]), atol=1e-5)
        assert not np.allclose(l1[0, 50:], l2[0, 50:], atol=1e-5)

    def test_loss_decreases_under_sgd(self):
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False)
        params = init_params(cfg, jax.random.key(0))
        batch = _batch(cfg)
        g = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, batch, cfg)))
        l0, grads = g(params)
        params2 = jax.tree.map(lambda p, d: p - 0.5 * d, params, grads)
        l1, _ = g(params2)
        assert l1 < l0

    def test_num_params_matches(self):
        cfg = llama_tiny()
        params = init_params(cfg, jax.random.key(0))
        total = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert total == num_params(cfg)

    def test_logical_axes_tree_matches_params(self):
        cfg = llama_tiny()
        params = init_params(cfg, jax.random.key(0))
        logical = param_logical_axes(cfg)
        ps = jax.tree.structure(params)
        ls = jax.tree.structure(
            logical, is_leaf=lambda x: isinstance(x, tuple))
        assert ps == ls
        for p, ax in zip(
                jax.tree.leaves(params),
                jax.tree.leaves(logical,
                                is_leaf=lambda x: isinstance(x, tuple))):
            assert p.ndim == len(ax)


class TestShardedTrainStep:
    def _run_steps(self, mesh_spec, cfg, n=3, B=8, S=64, devices=None):
        mesh = build_mesh(mesh_spec, devices=devices)
        init_fn, step_fn, place = make_lm_train_step(
            cfg, mesh, learning_rate=1e-2)
        params, opt = init_fn(jax.random.key(0))
        losses = []
        for i in range(n):
            batch = place(_batch(cfg, B=B, S=S, seed=i % 2))
            params, opt, metrics = step_fn(params, opt, batch)
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses))
        return losses

    def test_dp_only(self):
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False)
        losses = self._run_steps(MeshSpec(dp=8), cfg)
        assert losses[-1] < losses[0]

    def test_fsdp_tp(self):
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False)
        losses = self._run_steps(MeshSpec(dp=2, fsdp=2, tp=2), cfg)
        assert losses[-1] < losses[0]

    def test_ring_sp(self):
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False,
                                   attention_impl="ring")
        losses = self._run_steps(MeshSpec(dp=2, sp=4), cfg, B=4, S=64)
        assert losses[-1] < losses[0]

    def test_ulysses_sp(self):
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False,
                                   attention_impl="ulysses")
        losses = self._run_steps(MeshSpec(dp=2, sp=2, tp=2), cfg, B=4, S=64)
        assert losses[-1] < losses[0]

    def test_multi_slice_hybrid_mesh(self):
        """Multi-slice (DCN) training: dp split across 2 slices with tp
        inside each (reference: MEGASCALE multi-slice world + hybrid
        device mesh; dp outermost so gradient allreduce rides DCN)."""
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False)
        losses = self._run_steps(MeshSpec(dp=4, tp=2, num_slices=2), cfg)
        assert losses[-1] < losses[0]
        # Multi-slice must compute the same numbers as the flat mesh.
        l_flat = self._run_steps(MeshSpec(dp=4, tp=2), cfg, n=2)
        l_ms = self._run_steps(MeshSpec(dp=4, tp=2, num_slices=2), cfg, n=2)
        np.testing.assert_allclose(l_ms, l_flat, rtol=2e-4)

    def test_sharded_matches_single_device(self):
        """The 8-way sharded step must compute the same loss as 1 device."""
        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False)
        l_sharded = self._run_steps(MeshSpec(dp=2, fsdp=2, tp=2), cfg, n=2)
        l_single = self._run_steps(MeshSpec(), cfg, n=2,
                                   devices=jax.devices()[:1])
        np.testing.assert_allclose(l_sharded, l_single, rtol=2e-4)

    def test_ring_matches_dense(self):
        cfg_ring = llama_tiny().replace(dtype=jnp.float32, remat=False,
                                        attention_impl="ring")
        cfg_ref = llama_tiny().replace(dtype=jnp.float32, remat=False)
        l_ring = self._run_steps(MeshSpec(dp=2, sp=4), cfg_ring, n=2, B=8)
        l_ref = self._run_steps(MeshSpec(dp=8), cfg_ref, n=2, B=8)
        np.testing.assert_allclose(l_ring, l_ref, rtol=2e-4)


class TestTrainStepOptions:
    def _setup(self, **cfg_kw):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.llama import llama_tiny
        from ray_tpu.parallel import MeshSpec, build_mesh
        from ray_tpu.parallel.spmd import make_lm_train_step

        cfg = llama_tiny().replace(dtype=jnp.float32, remat=False,
                                   attention_impl="reference", **cfg_kw)
        mesh = build_mesh(MeshSpec(dp=-1))   # all (virtual) devices
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 512, (8, 256))
        mask = np.ones((8, 256), np.float32)
        mask[:2, 10:] = 0.0          # uneven masking across microbatches
        batch = {"tokens": jnp.asarray(tokens, jnp.int32),
                 "loss_mask": jnp.asarray(mask)}
        return cfg, mesh, batch

    def _run(self, cfg, mesh, batch, **step_kw):
        import jax

        from ray_tpu.parallel.spmd import make_lm_train_step

        init_fn, step_fn, place = make_lm_train_step(
            cfg, mesh, learning_rate=1e-3, **step_kw)
        params, opt = init_fn(jax.random.key(0))
        for _ in range(3):
            params, opt, m = step_fn(params, opt, place(dict(batch)))
        return float(m["loss"]), float(m["grad_norm"])

    def test_grad_accum_matches_single_step(self):
        """grad_accum is a pure memory trade: losses and grads equal the
        unaccumulated step exactly, including uneven loss masking (every
        microbatch normalizes by the FULL batch's token count)."""
        cfg, mesh, batch = self._setup()
        l1, g1 = self._run(cfg, mesh, batch)
        l3, g3 = self._run(cfg, mesh, batch, grad_accum=4)
        assert abs(l1 - l3) < 1e-4
        assert abs(g1 - g3) / g1 < 1e-3

    def test_chunked_ce_matches_fused(self):
        """loss_chunks computes the lm_head in sequence chunks under
        remat: same loss and grads as the fused logits path, with only
        one chunk's f32 logits ever resident."""
        cfg, mesh, batch = self._setup()
        l0, g0 = self._run(cfg, mesh, batch)
        cfg8, _, _ = self._setup(loss_chunks=8)
        l8, g8 = self._run(cfg8, mesh, batch)
        assert abs(l0 - l8) < 1e-4
        assert abs(g0 - g8) / g0 < 1e-3
