"""What ``tests/test_xing4.py`` and ``tests/test_xing4_layers.py`` both need:
the reference's sizes for a program configuration, and a seeded tiny model
with its selection bias and a batch.  This module holds no test.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import xing4


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    y = cfg.yarn
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers, "H": cfg.heads,
            "rq": cfg.q_lora_rank, "rkv": cfg.kv_lora_rank,
            "dn": cfg.qk_nope_head_dim, "dr": cfg.qk_rope_head_dim,
            "dv": cfg.v_head_dim, "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "Ms": cfg.moe_mlp_dim * cfg.num_shared_experts,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "n": cfg.hc_mult,
            "hc_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
            "hc_lo": cfg.hc_clamp[0], "hc_hi": cfg.hc_clamp[1],
            "mtp_weight": cfg.mtp_loss_weight, "theta": cfg.rope_theta,
            "yarn_factor": y.factor,
            "yarn_original": y.original_max_position_embeddings,
            "yarn_beta_fast": y.beta_fast, "yarn_beta_slow": y.beta_slow,
            "yarn_mscale": y.mscale, "yarn_mscale_all_dim": y.mscale_all_dim,
            "eps": cfg.norm_eps}


@functools.lru_cache(maxsize=None)
def _setup(seed=0, rows=2, seq=48, **kw):
    """Made once a configuration of this module (nothing writes into what it
    returns), the parameters under one ``jax.jit``: run eagerly the
    initialisation is one program a leaf shape."""
    cfg = xing4.xing4_tiny(**kw)

    @jax.jit
    def make(key, shake_key):
        params = xing4.init_params(cfg, key)
        # Norm weights away from one, maps that differ between tokens and
        # lanes (gains of 1, a random b), and a selection bias large enough
        # to change which experts are chosen.
        keys = iter(jax.random.split(shake_key, 256))

        def shake(path, a):
            name = str(path[-1])
            if "alpha" in name:
                return jnp.ones_like(a)
            if name.endswith("_b']"):
                return jax.random.normal(next(keys), a.shape)
            if "norm" in name:
                return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
            return a

        params = jax.tree_util.tree_map_with_path(shake, params)
        bias = 0.3 * jax.random.normal(
            next(keys), (cfg.expert_layers + 1, cfg.num_experts))
        return params, bias

    params, bias = make(jax.random.key(seed), jax.random.key(seed + 1))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (rows, seq), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (rows, seq),
                                              dtype=np.int32))}
    return cfg, params, bias, batch
