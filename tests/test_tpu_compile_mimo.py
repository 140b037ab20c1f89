"""``mimo-v2-flash.train-sink8k``'s train step compiles for a described v5e,
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

CELL = "mimo-v2-flash.train-sink8k"


@pytest.fixture(scope="module")
def mimo_step(topo):
    """The cell's step (6 sparse layers W W W W W F, 16 of 64 query heads
    over 2 of 8 / 1 of 4 key heads at 192 / 128, a sink on the window
    layers, 8 of 256 experts, rows of 8,192 a row a layer call, full remat,
    Pallas grouped products)."""
    from benchmark.archs import mimo_v2_flash
    with open(os.path.join(ROOT, "benchmark/traffic/train-sink8k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, mimo_v2_flash, "mimo-v2-flash.json", seq,
                      moe_impl="gmm")


def test_mimo_train_step_compiles_at_the_cell_sizes(mimo_step, capsys):
    """The step compiles for one described v5e chip with the Mosaic kernels
    in it: the window layers' forward by its sink's name, their dq and
    dk/dv, the full layer's 192 / 128 kernels in one part under a group of
    16, all inside the DEFAULT scoped VMEM (no limit stated), the grouped
    products; the parameter count is the config file's and the issue's; the
    compiler's memory figure; the scopes the readers sum are in its text."""
    import jax
    from benchmark import scopes
    from benchmark.archs import mimo_v2_flash as arch

    compiled, text = mimo_step["compiled"], mimo_step["text"]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n{CELL} step for a described v5e: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, {_kernels(compiled)} "
              f"kernels")
    assert sum(a.size for a in jax.tree.leaves(mimo_step["params"])) == \
        arch.parameters(mimo_step["sizes"])["held"] == \
        mimo_step["config"]["parameters"] == 1510789200
    # bf16 weights and two bf16 moments of 1,511 M parameters, resident:
    # 54 % of the chip's 16.9 GB.
    assert 9.0e9 < mem.argument_size_in_bytes < 9.2e9
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [c.partition(" = ")[0] for c in calls]
    for kernel in ("flash_fwd_d192v128_w128_sink", "flash_dq_d192v128_w128",
                   "flash_dkv_d192v128_w128", "flash_fwd_d192v128.",
                   "flash_dq_d192v128.", "flash_dkv_d192v128.", "gmm",
                   "tgmm"):
        assert any(kernel in n + "." for n in names), (kernel, names)
    # a sink's forward and no other forward on the window layers
    assert not any(re.search(r"flash_fwd_d192v128_w128[.\d]*$", n)
                   for n in names), names
    for call, name in zip(calls, names):
        if "flash_" in name:
            assert '"scoped_memory_configs":[]' in call, call[:200]
            used = re.search(r'"used_scoped_memory_configs":\[\{"memory_'
                             r'space":"1","offset":"0","size":"(\d+)"', call)
            assert used and int(used.group(1)) < 16 * 2 ** 20, name
    by = {"scopes": {scopes.scope_path(name): 1.0
                     for name in scopes.op_names(text).values()}}
    for scope in ("block/attn", "block/attn_window", "block/attn_full",
                  "attn/sink_grad", "block/moe/route", "block/moe/experts"):
        assert scopes.seconds_under(by, scope) > 0, scope
    assert scopes.seconds_under(by, "block/moe/shared") == 0


def test_the_experts_buffer_is_twice_the_expected_load(mimo_step):
    """8 of 256 experts held and 8 choices a token: a row of 8,192 tokens goes
    through 4,096 rows (16 tiers), not the 16,384 of four."""
    _assert_the_experts_buffer_has(mimo_step["text"], 4096, 16384)
