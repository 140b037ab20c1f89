"""``lfm2-24b-a2b.train-conv8k``'s train step compiles for a described v5e,
without a chip.  A file a cell: ``--dist loadfile`` keeps a file on one
worker, and the step is compiled here and nowhere else.  The fixtures and
the readers of a compiled program's text are ``tests/v5e_compile.py``'s,
imported: describing the topology happens inside the fixture, in the worker
that is given THIS file, never while a module is imported.
"""

from __future__ import annotations

import pytest

from v5e_compile import (  # noqa: F401 (``topo`` is a fixture)
    ROOT, _cell_step, _kernels, topo)


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
    for kernel in ("flash_fwd_d64", "flash_bwd_d64", "gmm", "tgmm"):
        assert any(name(c).startswith(kernel) for c in calls), kernel
    # The backward is the one pass under the group of four (PR 60): no dq
    # or dk/dv kernel, and each query head's dk / dv a float32 share.
    assert not any("flash_dq" in name(c) or "flash_dkv" in name(c)
                   for c in calls)
    assert all(c.partition(" custom-call(")[0].count("f32[128,8192,64]") == 2
               for c in calls if name(c).startswith("flash_bwd_d64"))
    # No 128-wide flash kernel's name: a reader tells the two by name.
    assert not any(name(c).startswith(k + ".") or name(c) == k for c in calls
                   for k in ("flash_fwd", "flash_dq", "flash_dkv",
                             "flash_bwd"))
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
