"""``motif-3-beta.train-gdla8k``'s train step compiles for a described v5e,
without a chip.  A file a cell: ``--dist loadfile`` keeps a file on one
worker, and the step is compiled here and nowhere else.  The fixtures and
the readers of a compiled program's text are ``tests/v5e_compile.py``'s,
imported: describing the topology happens inside the fixture, in the worker
that is given THIS file, never while a module is imported.
"""

from __future__ import annotations

import json
import os
import re

import pytest

from v5e_compile import (  # noqa: F401 (``topo`` is a fixture)
    ROOT, _assert_the_experts_buffer_has, _cell_step, _kernels, topo)

CELL = "motif-3-beta.train-gdla8k"


@pytest.fixture(scope="module")
def motif_step(topo):
    """The cell's step (4 sparse layers on a four-lane stream, 80 query
    heads over 16 key heads at 128 + 64 / 128, three window layers and one
    full under one scanned body, 8 of 384 experts, one row of 8,192, full
    remat, Pallas grouped products)."""
    from benchmark.archs import Motif
    with open(os.path.join(ROOT, "benchmark/traffic/train-gdla8k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, Motif, "motif-3-beta.json", seq, moe_impl="gmm")


def test_motif_train_step_compiles_at_the_cell_sizes(motif_step, capsys):
    """The step compiles for one described v5e chip with the Mosaic kernels
    in it: the grouped flash kernels at 192 / 128, full and windowed, by
    name and inside the DEFAULT scoped VMEM (no limit stated), the
    hyper-connection kernels, the grouped products; the parameter count is
    the config file's (1,168 M: the issue's 1,412 M, which the model counts
    to the parameter at five layers, less the leading dense layer's 243.5 M,
    dropped as the issue's next cut because with it the step held 99.8 % of
    the chip); the compiler's memory figure (7.0 + 12.3 GB, which
    over-states: the chip's runtime holds 7.1 + 8.3) beside the issue's
    15.1 GB for five layers; the scopes the readers sum are in its text."""
    import jax
    from benchmark import scopes
    from benchmark.archs import Motif as arch

    compiled, text = motif_step["compiled"], motif_step["text"]
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    with capsys.disabled():
        print(f"\n{CELL} step for a described v5e: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB (the issue reckoned "
              f"15.1 GB in all with a dense layer), {_kernels(compiled)} "
              f"kernels")
    assert sum(a.size for a in jax.tree.leaves(motif_step["params"])) == \
        arch.parameters(motif_step["sizes"])["held"] == \
        motif_step["config"]["parameters"] == 1168156920
    # bf16 weights and two bf16 moments of 1,168 M parameters, resident:
    # 41 % of the chip's 16.9 GB.  The compiler's count of the step's own
    # temporaries over-states what the runtime reserves (12.29 GB here,
    # 8.27 GB on the chip: PERF.md section 6, PR 57), as the other cells'.
    assert 6.9e9 < mem.argument_size_in_bytes < 7.1e9
    assert total < 20.0e9
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [c.partition(" = ")[0] for c in calls]
    for kernel in ("flash_fwd_d192v128", "flash_bwd_d192v128",
                   "flash_fwd_d192v128_w128", "flash_dq_d192v128_w128",
                   "flash_dkv_d192v128_w128", "hc_collect_n4",
                   "hc_deposit_n4", "gmm", "tgmm", "rope_to_heads"):
        assert any(kernel in n for n in names), kernel
    for call, name in zip(calls, names):
        if "flash_" in name:
            assert '"scoped_memory_configs":[]' in call, call[:200]
            used = re.search(r'"used_scoped_memory_configs":\[\{"memory_'
                             r'space":"1","offset":"0","size":"(\d+)"', call)
            assert used and int(used.group(1)) < 16 * 2 ** 20, name
    by = {"scopes": {scopes.scope_path(name): 1.0
                     for name in scopes.op_names(text).values()}}
    for scope in ("block/attn/mla/q", "block/attn/mla/kv_a",
                  "block/attn/mla/kv_b", "block/attn/mla/diff",
                  "block/attn/mla/gate", "block/attn/mla/out",
                  "block/attn_window", "block/attn_full", "polynorm",
                  "block/hc/maps", "block/hc/collect", "block/hc/deposit",
                  "block/moe/route", "block/moe/experts",
                  "block/moe/shared"):
        assert scopes.seconds_under(by, scope) > 0, scope


def test_the_experts_buffer_is_twice_the_expected_load(motif_step):
    """8 of 384 experts held and 8 choices a token: a row of 8,192 tokens goes
    through 4,096 rows (16 tiers), not the 16,384 of four."""
    _assert_the_experts_buffer_has(motif_step["text"], 4096, 16384)
