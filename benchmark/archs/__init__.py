"""One module an architecture, keyed by the config file's ``model_type``:
what a runner needs of a model that is not the runner's own.  Each module has

  sizes_of(config)            the sizes the benchmark's own code needs
  program_config(s, seq, opts)  the program's model configuration
  shapes(s)                   leaf -> (shape, fan-in; 0 marks a norm weight[,
                              the value it starts at, 1 if not given])
  norms_of(params)            the RMSNorm weights, whose gradients are judged
  parameters(s)               counts: held here, and active a token
  reference()                 the plain reference's module

so the next architecture adds a module and no runner.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict


def of(config: Dict[str, Any]):
    return importlib.import_module(f"benchmark.archs.{config['model_type']}")


def is_shape(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[1], int)


def make_weights(tree, seed: int, shardings=None):
    """bfloat16 weights for a tree of (shape, fan-in[, start]) from ``seed``,
    as ``weights.make`` makes Llama's: truncated normal / sqrt(fan-in), a
    norm weight (fan-in 0) at ``start`` (one if not given), the same bits on
    one chip and on a mesh."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_shape)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, fan_in, *start) in zip(keys, leaves):
            if fan_in == 0:
                out.append(jnp.full(shape, *(start or [1.0]), jnp.bfloat16))
            else:
                w = jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                out.append((w * fan_in ** -0.5).astype(jnp.bfloat16))
        return jax.tree.unflatten(treedef, out)

    old = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        return jax.jit(init, out_shardings=shardings)(weights.seed_key(seed))
    finally:
        jax.config.update("jax_threefry_partitionable", old)
