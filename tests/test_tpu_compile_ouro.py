"""``ouro-2.6b.train-loop4k``'s train step compiles for a described v5e,
without a chip.  A file a cell: ``--dist loadfile`` keeps a file on one
worker, and the step is compiled here and nowhere else.  The fixtures and
the readers of a compiled program's text are ``tests/v5e_compile.py``'s,
imported: describing the topology happens inside the fixture, in the worker
that is given THIS file, never while a module is imported.
"""

from __future__ import annotations

import pytest

from v5e_compile import (  # noqa: F401 (``topo`` is a fixture)
    ROOT, _assert_q_and_k_cross_hbm_once, _cell_step, _kernels,
    _q_sized_copies, topo)


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
