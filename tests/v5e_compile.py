"""What every ``tests/test_tpu_compile*.py`` needs to compile a program for a
described v5e, without a chip: the fixtures and the readers of a compiled
program's text.  This module holds no test.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (``v5e:2x2``, device kind ``TPU v5 lite``): what
it refuses here — a misaligned slice, too much VMEM, a Mosaic kernel
GSPMD cannot partition — it would refuse on the chip.  Nothing runs, so
this says nothing about results or times; ``chip_smoke.py`` does.

The fixtures are module-scoped and not ``autouse``, and a test file takes
them by import (``from v5e_compile import topo  # noqa: F401``): describing
the topology loads the TPU library, so it must happen inside a fixture, in
the xdist worker that is given the file, after collection, never while a
module is imported.  A cell's whole train step is one file's fixture
(``tests/test_tpu_compile_<cell>.py``): ``--dist loadfile`` keeps a file on
one worker, and a step is compiled in exactly one file.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)                # ``benchmark`` is not a package dir

# chip_smoke.py's widths (a 1.36B model).
MODEL = dict(vocab_size=32000, hidden=2048, layers=24, heads=16, kv_heads=16,
             head_dim=128, mlp_dim=5632, max_seq_len=2048)


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


def _cell_step(topo, arch, config_file, seq, batch_keys=("tokens",
                                                        "loss_mask"),
               **replace):
    """A cell's train step as the benchmark builds it, compiled for one
    described v5e chip with the platform's choices made as on the chip:
    {"compiled", "text", "params", "state", "config", "sizes"}.
    ``batch_keys``: the [rows, seq] int32 arrays of a batch.

    ``topo`` is what ``tests/conftest.py``'s order of the files keys on: a
    file one of whose cases asks for that fixture (by way of a module's own
    fixture, as ``granite_step(topo)``) is handed out early, so that the
    150-300 s of this call do not end the run.  A compile file that builds
    its step another way still takes ``topo``, or it goes out last with
    the files of few cases (``tests/test_conftest.py`` fails on it)."""
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
                     for k in batch_keys}
            compiled = step_fn.lower(params, state, batch).compile()
        finally:
            set_global_mesh(before)
    return {"compiled": compiled, "text": compiled.as_text(),
            "params": params, "state": state, "config": config, "sizes": s}


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


def _assert_rows_leave_the_experts_buffer_by_the_rows_in_use(text, T, k, E):
    """In a sparse cell's compiled step the sums over a token's
    rows are the Mosaic kernels (``tokens_from_rows`` under ``combine``,
    ``rows_of_tokens``' backward under ``dispatch``, in the branch that takes
    the buffer at once and in the slices'), inside Mosaic's default scoped
    VMEM (the compile refuses more), by names no roofline reader's pattern
    takes for another's; no array of all T * k slots is in the text, and
    no scatter of rows."""
    import re

    from benchmark import scopes
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


def _assert_the_experts_buffer_has(text, rows, never):
    """In a cell's compiled step the arrays of the expert layers that lead
    with the dropless buffer's rows lead with ``rows`` (twice the expected
    load at the share of the experts the cell holds,
    ``ops/moe.buffer_rows``), and none under ``block/moe`` leads with
    ``never``, the rows another share's tiers would give."""
    import re
    lead = {}
    for line in text.splitlines():
        if "block/moe" in line:
            for n in re.findall(r"\[(\d+),\d+\]", line.partition(" = ")[2]
                                .partition("metadata=")[0]):
                lead[int(n)] = lead.get(int(n), 0) + 1
    assert lead.get(rows, 0) > 0 and never not in lead, sorted(lead.items())
