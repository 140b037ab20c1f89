"""A bf16 train step on a described ``{fsdp: 4}``
(``mistral-7b-v0.3.train-fsdp4``'s mesh at chip_smoke's widths) compiles for
a described v5e, without a chip.  A file a cell: ``--dist loadfile`` keeps a
file on one worker, and the step is compiled here and nowhere else.  The
fixtures and the readers of a compiled program's text are
``tests/v5e_compile.py``'s, imported: describing the topology happens inside
the fixture, in the worker that is given THIS file, never while a module is
imported.
"""

from __future__ import annotations

import pytest

from v5e_compile import (  # noqa: F401 (``topo`` is a fixture)
    MODEL, _computations, _while_bodies, topo)


@pytest.fixture(scope="module")
def fsdp4_step_text(topo):
    """The compiled text of a bf16 train step on a described ``{fsdp: 4}``
    (chip_smoke's widths, depth cut to 2 layers, the loss in 4 chunks),
    steered as ``as_on_the_chip`` steers, once for the tests that read
    it."""
    import importlib

    import jax
    import jax.numpy as jnp
    from ray_tpu.models import LlamaConfig
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step

    cfg = LlamaConfig(**{**MODEL, "layers": 2}, dtype=jnp.bfloat16,
                      remat=True, attention_impl="flash", loss_chunks=4)
    attention = importlib.import_module("ray_tpu.ops.attention")
    before = get_global_mesh(), attention._on_tpu
    attention._on_tpu = lambda: True
    try:
        mesh = build_mesh(MeshSpec(fsdp=4), devices=topo.devices)
        init_fn, step_fn, _ = make_lm_train_step(
            cfg, mesh, learning_rate=1e-4, param_dtype=jnp.bfloat16)
        params, opt = jax.eval_shape(init_fn, jax.random.key(0))
        batch = {"tokens": jax.ShapeDtypeStruct((8, 2048), jnp.int32)}
        return step_fn.lower(params, opt, batch).compile().as_text()
    finally:
        set_global_mesh(before[0])
        attention._on_tpu = before[1]


def test_fsdp4_train_step_compiles(fsdp4_step_text):
    """Mesh training with flash attention: a Mosaic kernel cannot be
    partitioned by GSPMD, so the step compiles for four chips only with
    the kernels, the rotary pair too, in shard_map islands."""
    import re

    text = fsdp4_step_text
    # Forward, recomputed forward, the one pass of the backward (a key head
    # a query head here; dq and dk/dv before PR 54); q and k into the
    # forward twice and their gradients out once.
    assert text.count("tpu_custom_call") == 9
    # Per-device batch rows x heads reach the kernel, not the global 8.
    assert "bf16[32,2048,128]" in text
    assert "rope_to_heads" in text and "rope_from_heads" in text
    # What is gathered over fsdp is parameters and the tokens, never q or k
    # on their way into a kernel; 35 gathers before the rotary kernels
    # (PR 35), 34 with them, and 34 with the head gathered once a step (PR
    # 37: 16 of them are that one gather's pieces).
    gathered = [re.search(r"= \(?([a-z0-9]+\[[0-9,]*\])", line).group(1)
                for line in text.splitlines()
                if re.search(r"= .* all-gather(-start)?\(", line)]
    assert set(gathered) <= {
        "bf16[2048,16,128]", "bf16[16,128,2048]", "bf16[2048,5632]",
        "bf16[5632,2048]", "bf16[2048,32000]", "s32[4,2,2048]"}, gathered
    assert len(gathered) <= 35


def test_the_island_takes_the_one_pass_under_mistrals_group(topo):
    """``mistral-7b-v0.3.train-fsdp4``'s flash call, 32 query heads on 8 key
    heads at 4,096 tokens, a row a chip of the ``{fsdp: 4}`` mesh (the
    step above has a key head a query head): inside the ``shard_map``
    island the backward is the one pass (PR 60), a query head a grid row
    whose share of dk / dv leaves in float32, and no dq or dk/dv kernel."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.ops import attention
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh

    before = get_global_mesh()
    try:
        mesh = build_mesh(MeshSpec(fsdp=4), devices=topo.devices)
    finally:
        set_global_mesh(before)
    sds = lambda heads: jax.ShapeDtypeStruct(
        (4, heads, 4096, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"))))

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v, impl="flash", mesh=mesh)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds(32), sds(8), sds(8)).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(c.partition(" = ")[0].lstrip("%").split(".")[0].split(
        "flash_")[1] for c in calls) == ["bwd", "fwd"], calls
    bwd = next(c for c in calls if "flash_bwd" in c.partition(" = ")[0])
    assert bwd.partition(" custom-call(")[0].count("f32[32,4096,128]") == 2
    # no limit of scoped VMEM over the default's 16 MiB, which is what the
    # compiler writes where a kernel states none
    assert all(int(n) <= 16 * 2 ** 20 for n in re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', bwd))


@pytest.mark.parametrize("what", ["no_collective_in_the_loss_s_loops",
                                  "one_gather", "one_reduce_scatter"])
def test_fsdp4_head_crosses_the_ici_once_a_step(fsdp4_step_text, what):
    """The ``{fsdp: 4}`` step under a chunked loss (parallel/fsdp.on_rows):
    the head is gathered once a step and its gradient reduce-scattered once,
    float32, outside the loss's chunk loops."""
    import re

    text = fsdp4_step_text
    if what == "one_gather":
        # One collective (one channel), which the compiler carries through
        # the forward layer loop in pieces of an asynchronous fusion.
        assert len(set(re.findall(
            r"= \(?bf16\[2048,32000\][^=]* all-gather(?:-start)?\([^)]*\), "
            r"channel_id=(\d+)", text))) == 1
        return
    if what == "one_reduce_scatter":
        assert len(re.findall(r"= f32\[512,32000\][^=]* reduce-scatter\(",
                              text)) == 1
        return
    loss = [lines for lines in _while_bodies(_computations(text))
            if any("loss" in line for line in lines)
            and not any("block/" in line for line in lines)]
    assert len(loss) == 2, len(loss)             # forward, backward
    for lines in loss:
        assert not [line for line in lines if re.search(
            r" (all-gather|all-reduce|reduce-scatter|collective-permute)"
            r"(-start)?\(", line)]
