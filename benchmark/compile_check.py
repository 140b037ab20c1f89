#!/usr/bin/env python3
"""Compile a cell's programs at their real shapes for a described v5e:2x2.

    JAX_PLATFORMS=cpu python3 benchmark/compile_check.py <workload> [KEY=JSON ...]

No chip is needed and nothing runs: what the TPU compiler refuses here it
would refuse on the chip, and ``memory_analysis()`` says what each program
needs on one device (it counts one program, not what else the process
holds, and its temporaries over-state what the runtime reserves).  Extra
``KEY=JSON`` arguments override keys of the config's ``train`` group or
top-level sizes, to try a depth or a batch before the chip sees it.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _report(name, compiled):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "program": name, "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "kernels": text.count("tpu_custom_call"),
        "all_gathers": text.count("all-gather-start")
        + text.count(" all-gather("),
        "reduce_scatters": text.count("reduce-scatter")}), flush=True)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import common, reference, weights

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == sys.argv[1])
    config = common.load_json("configs", entry["config"] + ".json")
    mix = common.load_json("traffic", entry["traffic"] + ".json")
    for item in sys.argv[2:]:
        key, value = item.split("=", 1)
        group = config["train"] if key in config.get("train", {}) else config
        group[key] = json.loads(value)
    s = weights.sizes_of(config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = entry["chips"]
    print(json.dumps({"workload": entry["name"], "chips": chips,
                      "device_kind": topo.devices[0].device_kind,
                      "parameters": weights.num_params(s), "sizes": s}))

    if mix["kind"] == "train":
        from ray_tpu.parallel import MeshSpec, build_mesh
        from ray_tpu.parallel.spmd import make_lm_train_step
        opts, seq = config["train"], mix["seq_len"]
        rows = chips * (opts["tokens_per_chip"] // seq)
        cfg = common.llama_config(s, seq, **common.train_options(opts))
        mesh = build_mesh(MeshSpec(fsdp=chips) if chips > 1 else MeshSpec(),
                          devices=topo.devices[:chips])
        init_fn, step_fn, _ = make_lm_train_step(
            cfg, mesh, learning_rate=1e-5, param_dtype=jnp.bfloat16)
        params, opt = jax.eval_shape(init_fn, jax.random.key(0))
        batch = {k: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                 for k in ("tokens", "loss_mask")}
        _report(f"train step, {rows} rows of {seq}",
                step_fn.lower(params, opt, batch).compile())
        # The reference's loss and gradient norm on one row a chip.
        shard, rowsh = common.mesh_shardings(mesh, cfg)
        w = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            params, shard)
        t = jax.ShapeDtypeStruct((chips, seq), jnp.int32, sharding=rowsh)
        _report("reference loss and norm gradients, one row a chip",
                jax.jit(lambda w, t, m: reference.loss_and_norm_grads(
                    w, t, m, s)).lower(w, t, t).compile())
        from benchmark.kinds.train import (norms_of,
                                           program_loss_and_norm_grads)
        norms = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32,
                                           sharding=a.sharding), norms_of(w))
        _report("program loss and norm gradients, one row a chip",
                jax.jit(program_loss_and_norm_grads(cfg)).lower(
                    norms, w, {"tokens": t, "loss_mask": t}).compile())
        return 0

    # Serving: the decode program and one prefill per bucket, on one chip.
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        tuned_block_sizes)
    from ray_tpu.llm import _model
    tuned_block_sizes.get_tpu_version = lambda: 5
    tuned_block_sizes.get_device_name = lambda num_devices=None: "TPU v5"
    importlib.import_module("ray_tpu.ops.attention")._on_tpu = lambda: True
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    eo = {**config["serve"]["engine_options"], **mix["engine_options"]}
    cfg = common.llama_config(s, eo["max_seq_len"], remat=False,
                              attention_impl="reference")
    params = jax.tree.map(
        lambda leaf: sds(leaf[0], jnp.bfloat16), weights.shapes(s),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], int))
    slots, page = eo["max_slots"], eo["page_size"]
    kv = tuple(sds((eo["num_pages"], page, 2 * s["Hkv"], s["D"]),
                   jnp.bfloat16) for _ in range(s["L"]))
    _report("decode step", jax.jit(
        partial(_model.decode_step, cfg=cfg, page_size=page),
        donate_argnums=(1,)).lower(
            params, kv, sds((slots,), jnp.int32), sds((slots,), jnp.int32),
            sds((slots, math.ceil(eo["max_seq_len"] / page)), jnp.int32),
            sds((slots,), jnp.bool_)).compile())
    for bucket in eo["prefill_buckets"]:
        _report(f"prefill, bucket {bucket}", jax.jit(
            partial(_model.prefill, cfg=cfg)).lower(
                params, sds((1, bucket), jnp.int32),
                sds((), jnp.int32)).compile())
    width = eo["max_seq_len"]
    n = mix["verify_requests"]
    _report("reference margins of the served sample", jax.jit(
        lambda w, q, m: reference.served_margins(w, q, m, s)).lower(
            params, sds((n, width), jnp.int32),
            sds((n, width - 1), jnp.bool_)).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
