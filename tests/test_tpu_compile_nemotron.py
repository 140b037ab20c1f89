"""``nemotron-3-nano-30b-a3b.train-ssm8k``'s train step compiles for a
described v5e, without a chip.  A file a cell: ``--dist loadfile`` keeps a
file on one worker, and the step is compiled here and nowhere else.  The
fixtures and the readers of a compiled program's text are
``tests/v5e_compile.py``'s, imported: describing the topology happens inside
the fixture, in the worker that is given THIS file, never while a module is
imported.
"""

from __future__ import annotations

import pytest

from v5e_compile import (  # noqa: F401 (``topo`` is a fixture)
    ROOT, _assert_rows_leave_the_experts_buffer_by_the_rows_in_use, _cell_step,
    _kernels, topo)


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
    grouped products of un-gated experts (two a pass), the chunked scan's
    pair and the convolution's, under the scopes their readers sum; its
    memory is stated; the scopes the readers sum are in its
    text; the whole share's count is the configuration's."""
    import re

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
    for kernel in ("flash_fwd", "flash_bwd", "gmm", "tgmm",
                   "ssd_fwd_q128", "ssd_bwd_q128"):
        assert any(kernel in c.partition(" = ")[0] for c in calls), kernel
    # The backward is the one pass under the group of 16 (PR 60): a query
    # head a grid row, 16 float32 shares of dk / dv a key head.
    assert not any(k in c.partition(" = ")[0] for c in calls
                   for k in ("flash_dq", "flash_dkv"))
    assert any("f32[32,8192,128]" in c.partition(" custom-call(")[0]
               for c in calls if "flash_bwd" in c.partition(" = ")[0])
    names = scopes.op_names(text)
    for c in calls:
        if c.startswith("%ssd_") or c.startswith("ssd_"):
            assert "block/ssm/scan" in names[
                c.partition(" = ")[0].lstrip("%")], c[:200]
    # The mixers' convolution is the Pallas pair under the scope its reader
    # sums, at Mosaic's own scoped limit (none stated).
    for kernel in ("ssm_conv_fwd", "ssm_conv_bwd"):
        assert any(kernel in c.partition(" = ")[0] for c in calls), kernel
    for c in calls:
        if c.lstrip("%").startswith("ssm_conv_"):
            assert "block/ssm/conv" in names[
                c.partition(" = ")[0].lstrip("%")], c[:200]
            assert re.findall(
                r'"scoped_memory_configs":\[\{"memory_space":"1",'
                r'"offset":"0","size":"(\d+)"', c) == [str(16 * 2 ** 20)]
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


@pytest.mark.parametrize("T,k,E", [(8192, 6, 2688)], ids=["nemotron"])
def test_rows_leave_the_experts_buffer_by_the_rows_in_use(nemotron_step, T, k,
                                                         E):
    """The sums over a token's rows in this cell's compiled step (what is
    asserted: the helper's docstring)."""
    _assert_rows_leave_the_experts_buffer_by_the_rows_in_use(
        nemotron_step["text"], T, k, E)
