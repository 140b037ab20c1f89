"""What more than one of ``tests/test_ops*.py`` and ``tests/test_afmoe*.py``
(and ``tests/test_evabyte.py``) needs: seeded q, k, v, a seeded expert layer,
and the readers of the paths' and the flash geometry's counters.  This
module holds no test.
"""

import jax
import jax.numpy as jnp


def _counted(name, keys):
    """A counter of the catalog as {its tags' values under ``keys``: count}."""
    from ray_tpu.util import metrics
    _by_name, acc = metrics._aggregate_snapshots()
    return {tuple(dict(tags)[k] for k in keys): value
            for tags, value in acc.get(name, {}).values()}


def _norm_paths():
    """ray_tpu_norm_path_total as {(path, rows): count}."""
    return _counted("ray_tpu_norm_path_total", ("path", "rows"))


def _qkv(key, B=2, H=4, Hkv=None, S=128, D=32, dtype=jnp.float32):
    Hkv = Hkv or H
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, H, S, D), dtype),
            jax.random.normal(ks[1], (B, Hkv, S, D), dtype),
            jax.random.normal(ks[2], (B, Hkv, S, D), dtype))


def _geometry_counts():
    """ray_tpu_flash_step_geometry_total as {kernel: {tags: count}}."""
    from ray_tpu.util import metrics
    _by_name, acc = metrics._aggregate_snapshots()
    out = {}
    for tags, value in acc.get("ray_tpu_flash_step_geometry_total",
                               {}).values():
        tags = dict(tags)
        out.setdefault(tags.pop("kernel"), {})[
            tuple(sorted(tags.items()))] = value
    return out


def _experts(T=64, E=32, M=16, X=16, Xh=4, k=4, seed=1):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (T, E)),
            jax.random.normal(ks[1], (E, X)) * 0.3,
            jax.random.normal(ks[2], (Xh, E, M)) * 0.2,
            jax.random.normal(ks[3], (Xh, E, M)) * 0.2,
            jax.random.normal(ks[4], (Xh, M, E)) * 0.2)
