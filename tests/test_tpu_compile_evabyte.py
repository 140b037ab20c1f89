"""``evabyte-6.5b.train-eva32k``'s train step compiles for a described v5e,
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
