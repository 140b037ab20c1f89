"""What ``tests/test_motif.py`` and ``tests/test_motif_cell.py`` both need: the
reference's sizes for a program configuration, and a seeded tiny model with
its selection bias and a batch.  This module holds no test.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import motif


def _sizes(cfg):
    """The reference's sizes for a program configuration."""
    return {"V": cfg.vocab_size, "E": cfg.hidden, "L": cfg.layers,
            "Ld": cfg.num_dense_layers, "first_layer": cfg.first_layer,
            "H": cfg.heads, "Hkv": cfg.kv_heads,
            "noise": cfg.num_noise_heads, "rq": cfg.q_lora_rank,
            "rkv": cfg.kv_lora_rank, "dn": cfg.qk_nope_head_dim,
            "dr": cfg.qk_rope_head_dim, "dv": cfg.v_head_dim,
            "W": cfg.sliding_window, "period": cfg.sliding_window_period,
            "M": cfg.mlp_dim, "Me": cfg.moe_mlp_dim,
            "Ms": cfg.moe_mlp_dim * cfg.num_shared_experts,
            "X": cfg.num_experts, "Xh": cfg.held,
            "held_start": cfg.held_start, "k": cfg.top_k,
            "route_scale": cfg.route_scale, "n": cfg.hc_mult,
            "hc_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
            "hc_lo": cfg.hc_clamp[0], "hc_hi": cfg.hc_clamp[1],
            "poly_scale": cfg.polynorm_output_scale,
            "poly_clamp": cfg.polynorm_bias_clamp,
            "hidden_clamp": cfg.hidden_clamp, "mtp": cfg.mtp_layers,
            "mtp_weight": cfg.mtp_loss_weight, "theta": cfg.rope_theta,
            "eps": cfg.norm_eps}


@functools.lru_cache(maxsize=None)
def _setup(seed=0, rows=2, seq=32, **kw):
    """Made once a configuration of this module (nothing writes into what it
    returns), the parameters under one ``jax.jit``: run eagerly the
    initialisation is one program a leaf shape."""
    cfg = motif.motif_tiny(**kw)

    @jax.jit
    def make(key, shake_key):
        params = motif.init_params(cfg, key)
        # Norm weights away from one, maps that differ between tokens and
        # lanes (gains of 1, a random b), PolyNorm's numbers of every sign
        # with a bias that passes its clamp in some modules, and a selection
        # bias large enough to change which experts are chosen.
        keys = iter(jax.random.split(shake_key, 256))

        def shake(path, a):
            name = str(path[-1])
            if "alpha" in name:
                return jnp.ones_like(a)
            if name.endswith("_b']") or "poly" in name:
                return jax.random.normal(next(keys), a.shape)
            if "norm" in name:
                return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
            return a

        params = jax.tree_util.tree_map_with_path(shake, params)
        bias = 0.3 * jax.random.normal(
            next(keys),
            (cfg.expert_layers + cfg.mtp_layers, cfg.num_experts))
        return params, bias

    params, bias = make(jax.random.key(seed), jax.random.key(seed + 1))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (rows, seq), dtype=np.int32)),
        "loss_mask": jnp.asarray(rng.integers(0, 2, (rows, seq),
                                              dtype=np.int32))}
    return cfg, params, bias, batch
