"""What both kinds of runner share: the run's clock, device facts, lines."""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def device_facts(chips: int, rehearse: bool) -> Dict[str, Any]:
    """Runs in the process that holds the chips.  Fails unless it sees
    exactly ``chips`` TPU devices (a rehearsal takes any platform)."""
    import jax
    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if not rehearse and facts["platform"] != "tpu":
        raise RuntimeError(f"the worker is not on a TPU: {facts}")
    if facts["count"] != chips:
        raise RuntimeError(f"the worker sees {facts['count']} devices, the "
                           f"cell asks for {chips}: {facts}")
    return facts


def memory_stats() -> Dict[str, int]:
    """The runtime's memory readings on the fullest local device ({} where
    the backend reports none, as the CPU does)."""
    import jax
    stats = max(((d.memory_stats() or {}) for d in jax.local_devices()),
                key=lambda m: m.get("peak_bytes_in_use", 0))
    return {k: int(stats[k]) for k in (
        "peak_bytes_in_use", "bytes_in_use", "bytes_limit", "bytes_reserved",
        "peak_bytes_reserved", "largest_alloc_size") if k in stats}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 on a CPU)."""
    return memory_stats().get("peak_bytes_in_use", 0)


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Print each number compared beside its limit; all must hold (a NaN
    does not)."""
    oks = []
    for name, value in values.items():
        oks.append(bool(value <= limits[name]))
        say("correct", name=name, value=value, limit=limits[name],
            ok=oks[-1])
    return bool(oks) and all(oks)


def llama_config(s: Dict[str, Any], max_seq_len: int, **options):
    """The program's model configuration for sizes ``s`` (weights.sizes_of),
    in bfloat16; ``options`` are remat, attention_impl, loss_chunks."""
    import jax.numpy as jnp
    from ray_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"], heads=s["H"],
        kv_heads=s["Hkv"], head_dim=s["D"], mlp_dim=s["M"],
        max_seq_len=max_seq_len, rope_theta=s["theta"], norm_eps=s["eps"],
        dtype=jnp.bfloat16, **options)


def train_options(opts: Dict[str, Any]) -> Dict[str, Any]:
    """A config file's ``train`` group as ``llama_config`` options."""
    return {"remat": opts["remat"], "attention_impl": opts["attention"],
            "loss_chunks": opts["loss_chunks"]}


def mesh_shardings(mesh, cfg):
    """(parameter shardings, batch sharding) of the program's own rules on
    ``mesh``, for the scripts that place weights without a trainer."""
    import jax
    from jax.sharding import NamedSharding
    from ray_tpu.models.llama import param_logical_axes
    from ray_tpu.parallel.sharding import default_rules, named_sharding
    from ray_tpu.parallel.spmd import batch_pspec
    params = jax.tree.map(
        lambda ax: named_sharding(mesh, ax, default_rules()),
        param_logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return params, NamedSharding(mesh, batch_pspec(mesh))


def now() -> float:
    """Wall clock: the one clock that the driver process, the worker and
    the replica share."""
    return time.time()
