"""Kernels and serving's programs compile for a described v5e, without a
chip: flash at the cells' shapes, the ragged paged decode, the serving
engine's decode step, Yi's one-row check program, the rotary pair.

The fixtures (``topo``, ``one_chip``, ``as_on_the_chip``,
``v5e_block_sizes``) and the readers of a compiled program's text live in
``tests/v5e_compile.py``, which says why they are fixtures and holds no
test.  A cell's whole train step is compiled in a file of its own,
``tests/test_tpu_compile_<cell>.py``.
"""

from __future__ import annotations

import math
import os
from functools import partial

import pytest

from v5e_compile import (MODEL, ROOT, _kernels, _sds,  # noqa: F401
                         as_on_the_chip, one_chip, topo, v5e_block_sizes)

# chip_smoke.py's serving shapes.
SLOTS, NUM_PAGES, PAGE, MAX_SEQ = 64, 2200, 16, 640
FLASH_SHAPE = (12, 16, 2048, 128)


def test_described_device_is_a_v5e(topo):
    assert len(topo.devices) == 4
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_flash_forward_compiles(one_chip):
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention

    x = _sds(FLASH_SHAPE, jnp.bfloat16, one_chip)
    compiled = jax.jit(partial(flash_attention, causal=True)).lower(
        x, x, x).compile()
    assert _kernels(compiled) == 1


def _flash_grads(q, k, v):
    """The jitted gradient of a causal flash attention: forward, and dq
    with dk/dv or the one pass (``flash_bwd``; PR 54)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()


def test_flash_backward_compiles(one_chip):
    import jax.numpy as jnp

    x = _sds(FLASH_SHAPE, jnp.bfloat16, one_chip)
    compiled = _flash_grads(x, x, x)
    # forward and the one pass: no group to stack on the causal square
    assert _kernels(compiled) == 2
    assert "flash_bwd" in compiled.as_text()


@pytest.mark.parametrize("batch,heads,kv_heads", [
    (4, 16, 16),        # yi-coder-1.5b.train-sft4k
    (1, 32, 8),         # mistral-7b-v0.3.train-fsdp4, one chip's share
])
def test_flash_kernels_return_what_the_roofline_reader_matches(
        one_chip, batch, heads, kv_heads):
    """benchmark/layer_metrics/flash_attn_roofline.py tells the three
    kernels apart by the result types at the end of each custom call's
    label: a kernel that returns anything its own pattern does not match,
    or that another's matches too, blinds or falsifies the per-layer
    metric.  At the benchmark cells' shapes.  The one pass's ``flash_bwd``
    (Yi's shape, PR 54; Mistral's group of four, PR 60, whose dk and dv are
    float32 shares) returns dk, dv and dq and must match NONE of the three
    patterns: the reader then reads the forward alone there, until a
    ``benchmark`` PR counts the new kernel (ROADMAP)."""
    import re

    import jax.numpy as jnp
    from benchmark.layer_metrics.flash_attn_roofline import KERNELS

    q = _sds((batch, heads, 4096, 128), jnp.bfloat16, one_chip)
    kv = _sds((batch, kv_heads, 4096, 128), jnp.bfloat16, one_chip)
    compiled = _flash_grads(q, kv, kv)
    matched = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, _, rest = line.strip().partition(" = ")
        kernel = re.search(r"flash_(fwd|dq|dkv|bwd)", name).group(1)
        assert kernel not in matched, line
        types = re.findall(r"([a-z]+[0-9]+)\[",
                           rest.partition(" custom-call(")[0])
        label = f"{name}<{','.join(types)}>"
        matched[kernel] = [which for which, pattern in KERNELS.items()
                           if re.search(pattern, label)]
    assert matched == {"fwd": ["fwd"], "bwd": []}


def test_flash_compiles_at_128k_tokens(one_chip):
    """ROADMAP R9's length: the prefetched schedule (32,896 steps) has to
    fit SMEM next to everything else."""
    import jax.numpy as jnp

    x = _sds((1, 1, 131072, 128), jnp.bfloat16, one_chip)
    assert _kernels(_flash_grads(x, x, x)) == 3


@pytest.mark.parametrize("pages_per_seq", [32, 40])
def test_ragged_paged_decode_compiles(one_chip, v5e_block_sizes,
                                      pages_per_seq):
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.paged_attention import _ragged_path

    compiled = jax.jit(_ragged_path).lower(
        _sds((SLOTS, 16, 128), jnp.bfloat16, one_chip),
        _sds((NUM_PAGES, PAGE, 32, 128), jnp.bfloat16, one_chip),
        _sds((SLOTS, pages_per_seq), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.int32, one_chip)).compile()
    assert _kernels(compiled) == 1


def test_decode_step_compiles_at_smoke_shapes(one_chip, v5e_block_sizes,
                                              as_on_the_chip):
    """The serving engine's whole decode program: 24 layers, 64 slots,
    2200 pages of 16, the ragged kernel once per layer, in HBM."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.llm import _model
    from ray_tpu.models import LlamaConfig, init_params

    cfg = LlamaConfig(**MODEL, dtype=jnp.bfloat16, remat=False)
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(partial(init_params, cfg, param_dtype=jnp.bfloat16),
                       jax.random.key(0)))
    kv = tuple(_sds((NUM_PAGES, PAGE, 2 * cfg.kv_heads, cfg.head_dim),
                    cfg.dtype, one_chip) for _ in range(cfg.layers))
    pages_per_seq = math.ceil(MAX_SEQ / PAGE)
    compiled = jax.jit(
        partial(_model.decode_step, cfg=cfg, page_size=PAGE),
        donate_argnums=(1,)).lower(
            params, kv,
            _sds((SLOTS,), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.int32, one_chip),
            _sds((SLOTS, pages_per_seq), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.bool_, one_chip)).compile()
    assert _kernels(compiled) == cfg.layers
    mem = compiled.memory_analysis()
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert resident < 15.75e9, resident     # one v5e chip's HBM budget



def test_windowed_flash_kernels_compile_and_are_named(one_chip):
    """The kernels at Trinity's shape with its window (the forward and, since
    PR 60, the backward's one pass): in the compiled program under names
    that tell them from the full-causal calls."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention

    q = _sds((4, 32, 8192, 128), jnp.bfloat16, one_chip)
    kv = _sds((4, 4, 8192, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, window=2048)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    for name in ("flash_fwd_w2048", "flash_bwd_w2048"):
        assert name in text, name
    assert "flash_dq" not in text and "flash_dkv" not in text


@pytest.mark.parametrize("batch,heads,kv_heads,seq,window,d", [
    (4, 16, 16, 4096, None, 128),   # yi-coder-1.5b.train-sft4k
    (1, 32, 8, 4096, None, 128),    # mistral-7b-v0.3.train-fsdp4, a chip's
    (1, 32, 4, 8192, None, 128),    # trinity-mini.train-moe8k, a full layer
    (1, 32, 4, 8192, 2048, 128),    # ... and a window layer
    (1, 32, 2, 8192, None, 128),    # nemotron-3-nano-30b-a3b.train-ssm8k
    (4, 32, 8, 8192, None, 64),     # lfm2-24b-a2b.train-conv8k
    (1, 32, 8, 8192, None, 64),     # ... and its one-row check program's
])
def test_flash_compiles_at_the_cells_shapes_one_kernel_a_name(
        one_chip, batch, heads, kv_heads, seq, window, d):
    """With the geometry ``_tiles`` picks at each cell's shapes: the
    forward and the backward's one pass ``flash_bwd`` are one Mosaic call
    each under today's names (the roofline readers multiply a trace's
    calls by a call's least time), dq the one pass's third result.  With
    no group (Yi's shape) dk / dv leave in the inputs' dtype; under a
    group (PR 60) a grid row is a query head and its share of dk / dv
    leaves in float32, [B * H, S, D], summed over the group outside.  At
    none of these shapes does the one pass state a limit of scoped VMEM
    (the check program below says why)."""
    import re

    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention

    q = _sds((batch, heads, seq, d), jnp.bfloat16, one_chip)
    kv = _sds((batch, kv_heads, seq, d), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, window=window)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    suffix = ("" if d == 128 else f"_d{d}") + (
        "" if window is None else f"_w{window}")
    names = sorted(re.search(r"flash_[a-z]+(_d\d+)?(_w\d+)?",
                             c.partition(" = ")[0]).group(0) for c in calls)
    assert names == sorted(f"flash_{k}{suffix}" for k in ("fwd", "bwd"))
    bwd = next(c for c in calls if "flash_bwd" in c.partition(" = ")[0])
    results = bwd.partition(" = ")[2].partition(" custom-call(")[0]
    shares = (("bf16", f"{batch * kv_heads},{seq},{d}") if heads == kv_heads
              else ("f32", f"{batch * heads},{seq},{d}"))
    assert re.findall(r"([a-z]+[0-9]+)\[([0-9,]+)\]", results) == [
        shares] * 2 + [("bf16", f"{batch * heads},1,{seq},{d}")], results
    # The one pass states no scoped VMEM limit over the default's 16 MiB
    # (see the test below).
    limits = re.findall(r'"scoped_memory_configs":\[\{"memory_space":"1",'
                        r'"offset":"0","size":"(\d+)"', bwd)
    assert all(int(n) <= 16 * 2 ** 20 for n in limits)


def test_yi_one_row_check_program_compiles(topo, as_on_the_chip):
    """``benchmark/kinds/train.program_loss_and_norm_grads`` on the check's
    one row, [1, 16, 4096, 128] at the flash calls: what a run of
    ``yi-coder-1.5b.train-sft4k`` compiles after its window.  With the one
    pass stating the 56 MiB limit its 1,024 x 1,024 steps would have had
    from ``_compiler_params``, the TPU compiler's memory-space assignment
    crashed in this program (a segmentation fault in
    ``BestFitRepacker::Finish``, here as on the chip; PR 54) while the
    4-row train step compiled: the kernel needs 11.3 MiB and states none."""
    import json

    import jax
    import jax.numpy as jnp
    from benchmark import common, weights
    from benchmark.kinds.train import norms_of, program_loss_and_norm_grads
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step

    with open(os.path.join(ROOT, "benchmark/configs/yi-coder-1.5b.json")) as f:
        config = json.load(f)
    s, seq = weights.sizes_of(config), 4096
    cfg = common.llama_config(s, seq, **common.train_options(config["train"]))
    before = get_global_mesh()
    try:
        mesh = build_mesh(MeshSpec(), devices=topo.devices[:1])
        init_fn, _, _ = make_lm_train_step(cfg, mesh,
                                           param_dtype=jnp.bfloat16)
        shard, rowsh = common.mesh_shardings(mesh, cfg)
        w = jax.tree.map(
            lambda a, sh: _sds(a.shape, a.dtype, sh),
            jax.eval_shape(init_fn, jax.random.key(0))[0], shard)
        norms = jax.tree.map(
            lambda a: _sds(a.shape, jnp.float32, a.sharding), norms_of(w))
        row = _sds((1, seq), jnp.int32, rowsh)
        text = jax.jit(program_loss_and_norm_grads(cfg)).lower(
            norms, w, {"tokens": row, "loss_mask": row}).compile().as_text()
    finally:
        set_global_mesh(before)
    assert "flash_bwd" in text and "flash_dq" not in text



@pytest.mark.parametrize("batch,heads", [(4, 16), (1, 32), (1, 8), (1, 4)])
def test_rotary_kernels_are_not_what_the_flash_reader_matches(
        one_chip, as_on_the_chip, batch, heads):
    """benchmark/layer_metrics/flash_attn_roofline.py takes every Mosaic
    call that returns one bf16 array for flash_dq: the rotate-and-place
    kernels' labels in a trace must match none of its patterns."""
    import re

    import jax
    import jax.numpy as jnp
    from benchmark.layer_metrics.flash_attn_roofline import KERNELS
    from ray_tpu.ops.rope import rope_lane_tables, rotate_heads

    def both_ways(x, g):
        out, vjp = jax.vjp(lambda x: rotate_heads(
            x, *rope_lane_tables(128, 4096)), x)
        return out, vjp(g)[0]

    x = _sds((batch, 4096, heads, 128), jnp.bfloat16, one_chip)
    g = _sds((batch, heads, 4096, 128), jnp.bfloat16, one_chip)
    text = jax.jit(both_ways).lower(x, g).compile().as_text()
    labels = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, _, rest = line.strip().partition(" = ")
        types = re.findall(r"([a-z]+[0-9]+)\[",
                           rest.partition(" custom-call(")[0])
        labels.append(f"{name}<{','.join(types)}>")
    assert len(labels) == 2 and "rope_to_heads" in labels[0] \
        and "rope_from_heads" in labels[1], labels
    for label in labels:
        assert not [k for k, pattern in KERNELS.items()
                    if re.search(pattern, label)], label
