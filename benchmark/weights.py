"""Seeded weights, made on the device in one jitted call.

The benchmark owns its weights: the program is handed them (the replica's
``build_params``, the trainer's state) and the plain reference reads the
same arrays, so neither depends on how the other initialises.  The tree
has the layout that ``ray_tpu.models.llama`` documents for a checkpoint:
``embed [V,E]``, ``lm_head [E,V]``, ``final_norm [E]`` and ``blocks`` with
a leading layer axis (``wq [L,E,H,D]``, ``wk``/``wv [L,E,Hkv,D]``,
``wo [L,H,D,E]``, ``w_gate``/``w_up [L,E,M]``, ``w_down [L,M,E]`` and the
two norms ``[L,E]``).
"""

from __future__ import annotations

from typing import Any, Dict


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the benchmark's own code needs, from a config file's keys."""
    return {"V": config["vocab_size"], "E": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "M": config["intermediate_size"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"])}


def num_params(s: Dict[str, Any]) -> int:
    layer = (s["E"] * s["H"] * s["D"] * 2 + s["E"] * s["Hkv"] * s["D"] * 2
             + 3 * s["E"] * s["M"] + 2 * s["E"])
    return 2 * s["V"] * s["E"] + s["L"] * layer + s["E"]


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a norm, which starts at one)."""
    L, E, H, K, D, M, V = (s[k] for k in ("L", "E", "H", "Hkv", "D", "M", "V"))
    return {
        "embed": ((V, E), E),
        "blocks": {
            "attn_norm": ((L, E), 0), "mlp_norm": ((L, E), 0),
            "wq": ((L, E, H, D), E), "wk": ((L, E, K, D), E),
            "wv": ((L, E, K, D), E), "wo": ((L, H, D, E), H * D),
            "w_gate": ((L, E, M), E), "w_up": ((L, E, M), E),
            "w_down": ((L, M, E), M)},
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E)}


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)), seed >> 31)


def make(s: Dict[str, Any], seed: int, shardings=None):
    """bfloat16 weights for sizes ``s`` from ``seed``, placed by
    ``shardings`` (a matching tree) where given."""
    import jax
    import jax.numpy as jnp

    tree = shapes(s)
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[1], int)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_leaf)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, fan_in) in zip(keys, leaves):
            if fan_in == 0:
                out.append(jnp.ones(shape, jnp.bfloat16))
            else:
                w = jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                out.append((w * fan_in ** -0.5).astype(jnp.bfloat16))
        return jax.tree.unflatten(treedef, out)

    # Sharding-invariant bits: the same seed gives the same weights on one
    # chip and on a mesh.
    old = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        return jax.jit(init, out_shardings=shardings)(seed_key(seed))
    finally:
        jax.config.update("jax_threefry_partitionable", old)
