"""Main-path programs compile for a described v5e, without a chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (``v5e:2x2``, device kind ``TPU v5 lite``): what
it refuses here — a misaligned slice, too much VMEM, a Mosaic kernel
GSPMD cannot partition — it would refuse on the chip.  Nothing runs, so
this says nothing about results or times; ``chip_smoke.py`` does.

All of it lives in THIS file, behind module-scoped fixtures that are not
``autouse``: describing the topology loads the TPU library, which only
one process may hold, so it must happen in the one xdist worker that is
given this file, after collection, never while a module is imported.
"""

from __future__ import annotations

import math
from functools import partial

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)                # ``benchmark`` is not a package dir

# chip_smoke.py's widths and serving shapes (a 1.36B model).
MODEL = dict(vocab_size=32000, hidden=2048, layers=24, heads=16, kv_heads=16,
             head_dim=128, mlp_dim=5632, max_seq_len=2048)
SLOTS, NUM_PAGES, PAGE, MAX_SEQ = 64, 2200, 16, 640
FLASH_SHAPE = (12, 16, 2048, 128)


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A program compiled for a described device is written to the
    # persistent cache but cannot be read back without the chip.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """What a program picks from the platform JAX reports (the rotary
    kernels, the paged path), which is the CPU here: steer it."""
    import importlib
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"),
                        "_on_tpu", lambda: True)


def _assert_q_and_k_cross_hbm_once(text, q_shapes, halves):
    """What PR 35 took out of a compiled train step, and what it put there:
    under ``block/attn`` no instruction of the split rotation (``split``,
    ``concatenate``), no float32 array of a q shape and no 64-lane half of
    one among the instructions the program runs on their own (the compiler
    gives those a cost; a fused instruction has none), and the
    rotate-and-place kernel pair by name."""
    import re
    own = [line for line in text.splitlines() if '"estimated_cycles"' in line]
    assert len(own) > 50
    for line in own:
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name and "block/attn" in op_name.group(1):
            assert not op_name.group(1).endswith(("/split", "/concatenate")), \
                line[:300]
        result = line.partition(" = ")[2].partition(" ")[0]
        for shape in q_shapes:
            assert f"f32[{shape}]" not in result, line[:300]
        for shape in halves:
            assert f"[{shape}]" not in result, line[:300]
    calls = [line.strip().partition(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("rope_to_heads", "rope_from_heads"):
        assert any(kernel in c for c in calls), (kernel, calls)


@pytest.fixture
def v5e_block_sizes(monkeypatch):
    """Upstream's tuned-block lookup asks jax.devices(), which is the
    CPU here: answer for the described chip instead."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        tuned_block_sizes)
    monkeypatch.setattr(tuned_block_sizes, "get_tpu_version", lambda: 5)
    monkeypatch.setattr(tuned_block_sizes, "get_device_name",
                        lambda num_devices=None: "TPU v5")


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
    (Yi's shape; PR 54) returns dk, dv and dq and must match NONE of the
    three patterns: the reader then reads the forward alone there, until a
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
    assert matched == ({"fwd": ["fwd"], "bwd": []} if heads == kv_heads
                       else {which: [which] for which in KERNELS})


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


def _computations(text):
    """{computation name: its lines} of a compiled program's text."""
    import re
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{\s*$", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _while_bodies(comps):
    """[lines of every computation a while loop's body reaches], a list a
    loop of the program."""
    import re
    called = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
    out = []
    for body in {m.group(1) for lines in comps.values() for line in lines
                 for m in [re.search(r" while\(.*body=%?([\w.\-]+)", line)]
                 if m}:
        todo, seen = [body], set()
        while todo:
            name = todo.pop()
            if name not in seen:
                seen.add(name)
                todo += [c for line in comps[name]
                         for c in called.findall(line)]
        out.append([line for name in seen for line in comps[name]])
    return out


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


def test_windowed_flash_kernels_compile_and_are_named(one_chip):
    """The three kernels at Trinity's shape with its window: in the compiled
    program under names that tell them from the full-causal calls."""
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
    for name in ("flash_fwd_w2048", "flash_dq_w2048", "flash_dkv_w2048"):
        assert name in text, name


@pytest.mark.parametrize("batch,heads,kv_heads,seq,window", [
    (4, 16, 16, 4096, None),    # yi-coder-1.5b.train-sft4k
    (1, 32, 8, 4096, None),     # mistral-7b-v0.3.train-fsdp4, a chip's share
    (1, 32, 4, 8192, None),     # trinity-mini.train-moe8k, a full layer
    (1, 32, 4, 8192, 2048),     # ... and a window layer
])
def test_flash_compiles_at_the_cells_shapes_one_kernel_a_name(
        one_chip, batch, heads, kv_heads, seq, window):
    """With the geometry ``_tiles`` picks at each cell's shapes: forward,
    dq and dk/dv are one Mosaic call each under today's names (the
    roofline readers multiply a trace's calls by a call's least time), and
    dk / dv leave in the inputs' dtype, per key head, with no float32
    array a query head behind them.  With no group to stack (Yi's shape)
    the backward is the one call ``flash_bwd``, dq its third result."""
    import re

    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention

    q = _sds((batch, heads, seq, 128), jnp.bfloat16, one_chip)
    kv = _sds((batch, kv_heads, seq, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, window=window)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    suffix = "" if window is None else f"_w{window}"
    names = sorted(re.search(r"flash_[a-z]+(_w\d+)?", c.partition(" = ")[0])
                   .group(0) for c in calls)
    one_pass = heads == kv_heads
    assert names == sorted(f"flash_{k}{suffix}" for k in (
        ("fwd", "bwd") if one_pass else ("fwd", "dq", "dkv")))
    dkv = next(c for c in calls
               if re.search("flash_(dkv|bwd)", c.partition(" = ")[0]))
    results = dkv.partition(" = ")[2].partition(" custom-call(")[0]
    assert re.findall(r"([a-z]+[0-9]+)\[([0-9,]+)\]", results) == [
        ("bf16", f"{batch * kv_heads},{seq},128")] * 2 + [
        ("bf16", f"{batch * heads},1,{seq},128")] * one_pass, results
    # The one pass states no scoped VMEM limit over the default's 16 MiB
    # (see the test below).
    limits = re.findall(r'"scoped_memory_configs":\[\{"memory_space":"1",'
                        r'"offset":"0","size":"(\d+)"', dkv)
    assert not one_pass or all(int(n) <= 16 * 2 ** 20 for n in limits)


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


def _cell_step(topo, arch, config_file, seq, **replace):
    """A cell's train step as the benchmark builds it, compiled for one
    described v5e chip with the platform's choices made as on the chip:
    {"compiled", "text", "params", "state", "config", "sizes"}."""
    import importlib
    import json
    import os

    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step

    with open(os.path.join(ROOT, "benchmark/configs", config_file)) as f:
        config = json.load(f)
    s = arch.sizes_of(config)
    cfg = arch.program_config(s, seq, config["train"])
    if replace:
        cfg = cfg.replace(**replace)
    rows = config["train"]["tokens_per_chip"] // seq
    before = get_global_mesh()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(importlib.import_module("ray_tpu.ops.attention"),
                      "_on_tpu", lambda: True)
        try:
            mesh = build_mesh(MeshSpec(), devices=topo.devices[:1])
            init_fn, step_fn, _ = make_lm_train_step(
                cfg, mesh, learning_rate=1e-5, param_dtype=jnp.bfloat16)
            params, state = jax.eval_shape(init_fn, jax.random.key(0))
            batch = {k: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                     for k in ("tokens", "loss_mask")}
            compiled = step_fn.lower(params, state, batch).compile()
        finally:
            set_global_mesh(before)
    return {"compiled": compiled, "text": compiled.as_text(),
            "params": params, "state": state, "config": config, "sizes": s}


@pytest.fixture(scope="module")
def trinity_step(topo):
    """``trinity-mini.train-moe8k``'s step (9 layers, 16 of 128 experts, 4
    rows of 8,192, full remat, flash, Pallas grouped products)."""
    from benchmark.archs import afmoe
    return _cell_step(topo, afmoe, "trinity-mini.json", 8192,
                      moe_impl="gmm")


@pytest.fixture(scope="module")
def ouro_step(topo):
    """``ouro-2.6b.train-loop4k``'s step (12 layers run 4 times, 4 rows of
    4,096, full remat, flash, 8 loss chunks a pass).  Its layers are
    ``yi-coder-1.5b.train-sft4k``'s at the same shapes, q and k
    [4, 16, 4096, 128]."""
    import json
    import os
    from benchmark.archs import ouro
    with open(os.path.join(ROOT, "benchmark/traffic/train-loop4k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, ouro, "ouro-2.6b.json", seq)


def test_trinity_train_step_compiles_at_the_cell_sizes(trinity_step, capsys):
    """The step compiles for one described v5e chip; its memory is stated
    (the temporaries over-state what the runtime reserves)."""
    from ray_tpu.parallel.spmd import StepState

    compiled, text = trinity_step["compiled"], trinity_step["text"]
    assert isinstance(trinity_step["state"], StepState)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\ntrinity-mini.train-moe8k step for a described v5e: "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    for name in ("flash_fwd_w2048", "flash_dkv_w2048", "flash_fwd",
                 "flash_dq", "gmm", "tgmm"):
        assert name in text, name
    # bf16 weights and two bf16 moments of 1,243 M parameters.
    assert 7.4e9 < mem.argument_size_in_bytes < 7.6e9
    # 9.69 GB of temporaries with the scatters (PR 29); 9.71 GB since the
    # sums over a token's rows are kernels (PR 45).
    assert mem.temp_size_in_bytes < 10.5e9
    # Rows and counts move by gathers and dense passes alone.  (Upstream's
    # grouped matmul builds its tiles' table with a scatter-add of 47
    # places, under ``experts``: not ours.)
    scatters = [line for line in text.splitlines()
                if " scatter(" in line and "block/moe" in line
                and "/experts/" not in line]
    assert not scatters, scatters[:2]


def test_ouro_train_step_compiles_at_the_cell_sizes(ouro_step, capsys):
    """The step compiles for one described v5e chip; its memory is stated
    (the temporaries over-state what the runtime reserves).  The passes are
    told apart in the program's text, which the scope readers join a trace
    with."""
    import jax
    from benchmark.archs import ouro as arch
    from ray_tpu.parallel.spmd import StepState

    compiled, text = ouro_step["compiled"], ouro_step["text"]
    assert not isinstance(ouro_step["state"], StepState)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nouro-2.6b.train-loop4k step for a described v5e: "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    assert sum(a.size for a in jax.tree.leaves(ouro_step["params"])) == \
        arch.parameters(ouro_step["sizes"])["held"] == \
        ouro_step["config"]["parameters"] == 817991681
    # bf16 weights and two bf16 moments of 818 M parameters.
    assert 4.85e9 < mem.argument_size_in_bytes < 5.0e9
    # 14.95 GB of temporaries stated, where the chip's runtime reserves
    # 8.66 GB beside 5.04 GB in use (PERF.md, PR 34): every pass's stacked
    # gradient lives until the optimizer's fused sum.
    assert mem.temp_size_in_bytes < 15.5e9
    assert "flash_dq" not in text and "flash_dkv" not in text    # PR 54
    for name in ("flash_fwd", "flash_bwd", "loop/0/", "loop/3/",
                 "block/attn", "block/mlp", "/loss/"):
        assert name in text, name


def test_q_and_k_cross_hbm_once_in_the_dense_step(ouro_step):
    """Yi's and Ouro's layer, q and k [4, 16, 4096, 128] (the flash
    kernels' view [64, 1, 4096, 128])."""
    _assert_q_and_k_cross_hbm_once(
        ouro_step["text"], ("4,16,4096,128", "64,1,4096,128",
                            "64,4096,128"), ("4,16,4096,64",))


def _assert_the_flash_kernels_walk_tiles(text, seq=8192, head=192):
    """Latent attention's kernels hold several 512 x 512 tiles a grid step
    (PR 52): each call's table of steps, its scalar-prefetch operand, is
    the shorter one (24 a head at 8,192 tokens and 8 tiles a step, 40 at
    the one pass's 4 (PR 54: the call in parts, its backward
    ``flash_bwd``), where a tile a step lists 136), and no kernel of the
    step, theirs or any
    other, states a scoped VMEM limit over Mosaic's default 16 MiB: a
    kernel that did hung Xing4.0's step in its first call (ROADMAP S11
    (5))."""
    import importlib
    import re
    A = importlib.import_module("ray_tpu.ops.attention")
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    flash = [c for c in calls if "flash_" in c.partition(" = ")[0]]
    assert len(flash) >= 3
    for call in flash:
        kind = re.match(r"%\S*flash_(fwd|dq|dkv|bwd)_", call).group(1)
        t = A._tiles(kind, seq, seq, head, 1,
                     **({"Dr": 64} if kind == "bwd" else {}))
        steps = A.block_schedule(
            seq, seq, *t.major, major="q" if t.scores == "qk" else "k"
        ).shape[1]
        one = A.block_schedule(seq, seq, t.block_q, t.block_k).shape[1]
        assert t.tiles > 1 and steps < one / 3, (kind, t, steps, one)
        assert f"s32[{steps}]" in call.partition("custom-call(")[2][:400], \
            (kind, steps, call[:400])
    for call in calls:
        for limit in re.findall(
                r'"scoped_memory_configs":\[\{"memory_space":"1",'
                r'"offset":"0","size":"(\d+)"', call):
            assert int(limit) <= 16 * 2 ** 20, call[:300]


def _q_sized_copies(text, q_shape, dtype="bf16", under="block/attn"):
    """The copies and transposes the program runs on their own (those the
    compiler gives a cost) whose result is as large as ``q_shape`` and of
    ``dtype``, under the scope ``under``: (op_name's tail, result)."""
    import math
    import re
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (" + dtype + r"\[([0-9,]+)\])\S* "
                     r"(copy|transpose)\(", line)
        if not m or '"estimated_cycles"' not in line:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        if (op_name and under in op_name.group(1) and math.prod(
                int(n) for n in m.group(2).split(",")) == math.prod(q_shape)):
            found.append((op_name.group(1)[-60:], m.group(1)))
    return found


def test_nothing_q_sized_is_copied_round_flash_in_the_dense_step(ouro_step):
    """Yi's and Ouro's layer, four rows a call (PR 49): v, the recomputed v
    and ``do`` are no longer placed head-major
    (``bse,ehd->bhsd/transpose``, ``bhsd,hde->bse/transpose``) and flash's
    ``out`` and ``dv`` are no longer re-laid for the ``wo`` / ``wv`` weight
    gradients (``block/attn/reshape``): the kernels read and write them
    where the projections hold them.  Nor is a float32 product written out
    for ``delta``: it is one pass over ``do`` and ``out``."""
    text = ouro_step["text"]
    assert not _q_sized_copies(text, (4, 16, 4096, 128))
    assert not _q_sized_copies(text, (4, 16, 4096, 128), "f32")
    assert "f32[2048,8,16,128]" not in text


def test_q_and_k_cross_hbm_once_in_the_sparse_step(trinity_step):
    """Trinity's window layers, a row at a time: q [1, 32, 8192, 128], k
    [1, 4, 8192, 128] (the flash kernels' view [4, 8, 8192, 128]); its full
    layers have no positions and only turn q and k head-major."""
    _assert_q_and_k_cross_hbm_once(
        trinity_step["text"], ("1,32,8192,128", "4,8,8192,128",
                               "1,4,8192,128", "4,8192,128"),
        ("1,32,8192,64", "1,4,8192,64"))


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


@pytest.fixture(scope="module")
def evabyte_step(topo):
    """``evabyte-6.5b.train-eva32k``'s step (4 layers, one row of 32,768
    bytes, full remat, the EVA kernels, eight heads)."""
    import json
    import os
    from benchmark.archs import evabyte
    with open(os.path.join(ROOT, "benchmark/traffic/train-eva32k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, evabyte, "evabyte-6.5b.json", seq)


def test_evabyte_train_step_compiles_at_the_cell_sizes(evabyte_step, capsys):
    """The step compiles for one described v5e chip; its memory is stated.
    The six EVA kernels are in the program's text by name, under the scopes
    the readers sum."""
    import re

    import jax
    from benchmark.archs import evabyte as arch

    compiled, text = evabyte_step["compiled"], evabyte_step["text"]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nevabyte-6.5b.train-eva32k step for a described v5e: "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    assert sum(a.size for a in jax.tree.leaves(evabyte_step["params"])) == \
        arch.parameters(evabyte_step["sizes"])["held"] == \
        evabyte_step["config"]["parameters"] == 821366784
    # bf16 weights and two bf16 moments of 821 M parameters.
    assert 4.9e9 < mem.argument_size_in_bytes < 5.0e9
    # 14.13 GB of temporaries stated, where the chip's runtime reserves
    # 9.83 GB beside 4.97 GB in use, 14.80 of 16.91 GB (PERF.md, PR 36): the
    # float32 residual stream's saved inputs and one layer's recomputation
    # and backward at a whole row of 32,768.
    assert mem.temp_size_in_bytes < 14.6e9
    calls = [line.strip().partition(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("eva_fwd_w2048c16", "eva_dq_w2048c16", "eva_dkv_w2048c16",
                   "eva_dsum_w2048c16", "eva_pool_fwd_c16",
                   "eva_pool_bwd_c16", "rope_to_heads", "rope_from_heads"):
        assert any(kernel in c for c in calls), (kernel, calls)
    # The float32 stream is row-major from the embedding to the heads:
    # ``rms_norm`` pins it (PR 39), where the compiler alone kept the
    # sequence minor (144 ``{1,2,0}`` in the parent's text) and the
    # projections ran 3 % slower round it.
    assert "f32[1,32768,4096]{1,2,0" not in text
    assert text.count("f32[1,32768,4096]{2,1,0") > 100
    from benchmark import scopes
    by = {"scopes": {scopes.scope_path(name): 1.0
                     for name in scopes.op_names(text).values()}}
    for scope in ("stack", "block/attn/eva", "block/attn/eva_pool",
                  "block/mlp", "loss"):
        assert scopes.seconds_under(by, scope) > 0, scope
    # One walk: no score array of a row's or a window's size reaches HBM
    # (the 32 heads beside two axes of a window or more: [32, 2048, 2048],
    # [1, 32, 32768, 32768], [32, 16, 2048, 4096] and the like), and no key
    # array longer than the row (k beside its summaries).
    for shape in set(re.findall(r"[a-z]+[0-9]+\[([0-9,]+)\]", text)):
        dims = [int(d) for d in shape.split(",")]
        assert not (32 in dims and sum(d >= 2048 for d in dims) >= 2), shape
        assert 32768 + 2048 not in dims, shape


@pytest.fixture(scope="module")
def xing4_step(topo):
    """``xing4.0-29b-a4b.train-mhc8k``'s step (1 dense + 4 expert layers and
    the prediction module, 8 of 64 experts, rows of 8,192 a layer at a time,
    full remat, flash at 192 / 128, Pallas grouped products)."""
    import json
    import os
    from benchmark.archs import xing4_0
    with open(os.path.join(ROOT, "benchmark/traffic/train-mhc8k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, xing4_0, "xing4.0-29b-a4b.json", seq,
                      moe_impl="gmm")


def test_xing4_train_step_compiles_at_the_cell_sizes(xing4_step, capsys):
    """The step compiles for one described v5e chip with the Mosaic kernels
    in it: the three flash kernels at head sizes 192 / 128 by name, taking q
    and k in parts with no operand or result 192 or 256 wide, and the
    grouped products; its memory is stated; the scopes the readers sum are
    in its text."""
    import re

    import jax
    from benchmark import scopes
    from benchmark.archs import xing4_0 as arch

    compiled, text = xing4_step["compiled"], xing4_step["text"]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nxing4.0-29b-a4b.train-mhc8k step for a described v5e: "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    assert sum(a.size for a in jax.tree.leaves(xing4_step["params"])) == \
        arch.parameters(xing4_step["sizes"])["held"] == \
        xing4_step["config"]["parameters"] == 913473348
    # bf16 weights and two bf16 moments of 913 M parameters.
    assert 5.4e9 < mem.argument_size_in_bytes < 5.6e9
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("flash_fwd_d192v128", "flash_bwd_d192v128",
                   "gmm", "tgmm", "hc_collect_n4",
                   "hc_deposit_n4", "hc_deposit_bwd_n4", "hc_pre_bwd_n4",
                   "hc_collect_bwd_n4"):
        assert any(kernel in c.partition(" = ")[0] for c in calls), kernel
    # The four-lane stream lies row-major wherever it is held (PR 42: the
    # ``jnp`` passes kept it sequence-minor, 159 times in this text), and no
    # ``copy`` stands beside a pass's kernel: none of one row's stream.
    assert "bf16[1,4,8192,3584]{3,2,1,0" in text
    assert "bf16[1,4,8192,3584]{2,3,1,0" not in text
    assert not re.search(r"= bf16\[1,4,8192,3584\]\S* copy\(", text)
    # What crosses HBM at a flash call is the parts the projections wrote
    # (PR 50): q's 128 lanes without position, the result and their
    # gradients as rows of 32 heads (4,096 lanes), a head's key and value
    # side by side in the one product's result (8,192), the rotary parts 64
    # wide; nothing concatenated (192) and nothing padded (256).
    for call in calls:
        if "flash_" in call.partition(" = ")[0]:
            widths = {int(dims.split(",")[-1]) for dims in re.findall(
                r"bf16\[([0-9,]+)\]", call)}
            assert widths == {4096, 8192, 64}, call[:300]
    _assert_the_flash_kernels_walk_tiles(text)
    by = {"scopes": {scopes.scope_path(name): 1.0
                     for name in scopes.op_names(text).values()}}
    for scope in ("block/hc/maps", "block/hc/collect", "block/hc/deposit",
                  "block/attn/mla", "block/moe/experts", "mtp",
                  "mtp/block/hc", "loss"):
        assert scopes.seconds_under(by, scope) > 0, scope


def test_nothing_q_sized_moves_round_latent_attention_s_kernels(xing4_step):
    """Latent attention, a row a call (PR 50): the kernels take q and k in
    the parts the projections write, so under ``block/attn`` the step runs
    no copy or transpose as large as q (192 wide), ``kv`` (256), v / the
    result (128) or the rotary part (64); the one rotary key head is never
    laid under 32 heads; nothing as large as q is concatenated (the
    parent's q and k were, ``block/attn/reshape`` writing
    ``bf16[32,1,8192,192]`` and ``bf16[32,8192,192]`` six times each), and
    no instruction there writes a 192-wide array at all."""
    import math
    import re
    text = xing4_step["text"]
    for width in (192, 256, 128, 64):
        assert not _q_sized_copies(text, (1, 32, 8192, width)), width
    own = [line for line in text.splitlines()
           if '"estimated_cycles"' in line and "block/attn" in line]
    assert len(own) > 100
    for line in own:
        m = re.match(
            r"\s*(?:ROOT )?%\S+ = (\w+)\[([0-9,]*)\]\S* ([\w\-]+)\(", line)
        if not m:       # a tuple's: the kernels', checked by their widths
            continue
        dims = [int(n) for n in m.group(2).split(",") if n]
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert not (m.group(3) == "broadcast" and dims == [1, 32, 8192, 64]), \
            line[:300]
        assert dims[-1:] != [192], line[:300]
        assert not (op_name.endswith("/concatenate")
                    and math.prod(dims) >= 32 * 8192 * 128), line[:300]


@pytest.fixture(scope="module")
def nemotron_step(topo):
    """``nemotron-3-nano-30b-a3b.train-ssm8k``'s step (``MEMEM*EMEMEM*``:
    6 Mamba-2, 5 expert and 2 attention layers, unrolled; 16 of 128 experts,
    4 rows of 8,192 a layer at a time, full remat, flash at 32 : 2 heads,
    Pallas grouped products).  The longest compile of this file."""
    import json
    import os
    from benchmark.archs import nemotron_h
    with open(os.path.join(ROOT, "benchmark/traffic/train-ssm8k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, nemotron_h, "nemotron-3-nano-30b-a3b.json", seq,
                      moe_impl="gmm")


def test_nemotron_train_step_compiles_at_the_cell_sizes(nemotron_step,
                                                        capsys):
    """The step compiles for one described v5e chip with the Mosaic kernels
    in it: the three flash kernels at 16 query heads a key head and the
    grouped products of un-gated experts (two a pass) and the chunked scan's
    pair, under the scope its reader sums; its memory is stated; the scopes the readers sum are in its
    text; the whole share's count is the configuration's."""
    import jax
    from benchmark import scopes
    from benchmark.archs import nemotron_h as arch

    compiled, text = nemotron_step["compiled"], nemotron_step["text"]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nnemotron-3-nano-30b-a3b.train-ssm8k step for a described "
              f"v5e: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    assert sum(a.size for a in jax.tree.leaves(nemotron_step["params"])) == \
        arch.parameters(nemotron_step["sizes"])["held"] == \
        nemotron_step["config"]["parameters"] == 1267091328
    # bf16 weights and two bf16 moments of 1,267 M parameters; with the
    # step's temporaries at four rows they fit the chip's 16.91 GB by this
    # count, which over-states: 97 % here where the chip's allocator reads
    # 80.6 % held (PERF.md section 4, PR 43).
    assert 7.5e9 < mem.argument_size_in_bytes < 7.7e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.91e9
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv", "gmm", "tgmm",
                   "ssd_fwd_q128", "ssd_bwd_q128"):
        assert any(kernel in c.partition(" = ")[0] for c in calls), kernel
    names = scopes.op_names(text)
    for c in calls:
        if c.startswith("%ssd_") or c.startswith("ssd_"):
            assert "block/ssm/scan" in names[
                c.partition(" = ")[0].lstrip("%")], c[:200]
    # One row of 32 query heads on 2 key heads: four stacks of 8 heads, two
    # behind each key head, and K / V cross HBM 2 heads wide.
    assert any("bf16[4,8,8192,128]" in c and "bf16[2,8192,128]" in c
               for c in calls if "flash_fwd" in c.partition(" = ")[0])
    by = {"scopes": {scopes.scope_path(name): 1.0
                     for name in scopes.op_names(text).values()}}
    for scope in ("block/ssm/proj", "block/ssm/conv", "block/ssm/scan",
                  "block/ssm/norm", "block/ssm", "block/attn",
                  "block/moe/experts", "block/moe/shared", "loss"):
        assert scopes.seconds_under(by, scope) > 0, scope


@pytest.mark.parametrize("step,T,k,E", [
    ("trinity_step", 8192, 8, 2048), ("xing4_step", 8192, 4, 3584),
    ("nemotron_step", 8192, 6, 2688)], ids=["trinity", "xing4", "nemotron"])
def test_rows_leave_the_experts_buffer_by_the_rows_in_use(step, T, k, E,
                                                         request):
    """In the three sparse cells' compiled steps the sums over a token's
    rows are the Mosaic kernels (``tokens_from_rows`` under ``combine``,
    ``rows_of_tokens``' backward under ``dispatch``, in the branch that takes
    the buffer at once and in the slices'), inside Mosaic's default scoped
    VMEM (the compile refuses more), by names no roofline reader's pattern
    takes for another's; no array of all T * k slots is in the text, and
    no scatter of rows."""
    import re

    from benchmark import scopes
    text = request.getfixturevalue(step)["text"]
    names = scopes.op_names(text)
    calls = [line.strip().partition(" = ")[0].lstrip("%")
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    under = {"rows_sum_weighted": "block/moe/combine",
             "rows_sum": "block/moe/dispatch"}
    seen = {}
    for call in calls:
        kernel = re.sub(r"[.\d]+$", "", call)
        if kernel in under:
            assert under[kernel] in scopes.scope_path(names[call]), call
            seen[kernel] = seen.get(kernel, 0) + 1
        else:
            assert "rows_sum" not in kernel, call
    assert set(seen) == set(under) and min(seen.values()) >= 2, seen
    for pattern in ("gmm", "flash_", "ssd_", "hc_", "ragged-dot"):
        assert not any(pattern in kernel for kernel in under)
    assert f"[{T},{k},{E}]" not in text and f"[{T * k},{E}]" not in text
    scatters = [line for line in text.splitlines()
                if " scatter(" in line and "block/moe" in line
                and "/experts/" not in line]
    assert not scatters, scatters[:2]



@pytest.fixture(scope="module")
def lfm2_step(topo):
    """``lfm2-24b-a2b.train-conv8k``'s step (``cacccaccc``: 7 gated short
    convolutions and 2 attention layers at 32 : 8 heads of 64, one dense and
    8 expert layers with no shared expert, unrolled; 8 of 64 experts, 4 rows
    of 8,192, full remat, flash, Pallas grouped products, a tied head)."""
    import json
    import os
    from benchmark.archs import lfm2_moe
    with open(os.path.join(ROOT, "benchmark/traffic/train-conv8k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, lfm2_moe, "lfm2-24b-a2b.json", seq,
                      moe_impl="gmm")


def test_lfm2_train_step_compiles_at_the_cell_sizes(lfm2_step, capsys):
    """The step compiles for one described v5e chip with the Mosaic kernels
    in it: the three flash kernels at a head size of 64, under the names
    their reader matches, and the grouped products; its memory is stated;
    the scopes the readers sum are in its text; there is one [V, E] leaf for
    the embedding and the head; the whole share's count is the
    configuration's."""
    import jax
    from benchmark import scopes
    from benchmark.archs import lfm2_moe as arch

    compiled, text = lfm2_step["compiled"], lfm2_step["text"]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nlfm2-24b-a2b.train-conv8k step for a described v5e: "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    params = lfm2_step["params"]
    assert "lm_head" not in params and params["embed"].shape == (8192, 2048)
    assert sum(a.size for a in jax.tree.leaves(params)) == \
        arch.parameters(lfm2_step["sizes"])["held"] == \
        lfm2_step["config"]["parameters"] == 832651520
    # bf16 weights and two bf16 moments of 833 M parameters.
    assert 4.9e9 < mem.argument_size_in_bytes < 5.1e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 0.9 * 16.91e9
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    name = lambda c: c.partition(" = ")[0].lstrip("%")
    for kernel in ("flash_fwd_d64", "flash_dq_d64", "flash_dkv_d64", "gmm",
                   "tgmm"):
        assert any(name(c).startswith(kernel) for c in calls), kernel
    # No 128-wide flash kernel's name: a reader tells the two by name.
    assert not any(name(c).startswith(k + ".") or name(c) == k for c in calls
                   for k in ("flash_fwd", "flash_dq", "flash_dkv"))
    # Four rows of 32 query heads on 8 key heads: four heads stacked behind
    # each key head, and K / V cross HBM 64 wide.
    assert any("bf16[32,4,8192,64]" in c and "bf16[32,8192,64]" in c
               for c in calls if name(c).startswith("flash_fwd_d64"))
    by = {"scopes": {scopes.scope_path(n): 1.0
                     for n in scopes.op_names(text).values()}}
    for scope in ("block/conv/proj", "block/conv/gate", "block/conv",
                  "block/attn", "block/attn/rope", "block/attn/qk_norm",
                  "block/mlp", "block/moe/experts", "block/moe/route",
                  "loss"):
        assert scopes.seconds_under(by, scope) > 0, scope
    assert not scopes.seconds_under(by, "block/moe/shared")
