"""``kanana-2-30b-a3b.train-mla8k``'s train step compiles for a described v5e,
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
    ROOT, _assert_the_flash_kernels_walk_tiles, _cell_step, _kernels,
    _q_sized_copies, topo)

CELL = "kanana-2-30b-a3b.train-mla8k"


@pytest.fixture(scope="module")
def kanana_step(topo):
    """The cell's step (1 dense + 11 expert layers, 16 of 128 experts, rows
    of 8,192, full remat, flash at 128 + 64 / 128, Pallas grouped
    products)."""
    from benchmark.archs import deepseek_v3
    with open(os.path.join(ROOT, "benchmark/traffic/train-mla8k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, deepseek_v3, "kanana-2-30b-a3b.json", seq,
                      moe_impl="gmm")


def test_kanana_train_step_compiles_at_the_cell_sizes(kanana_step, capsys):
    """The step compiles for one described v5e chip with the Mosaic kernels
    in it: the three flash kernels at head sizes 192 / 128 by name, taking q
    and k in parts, and the grouped products; the parameter count is the
    config file's and the issue's; its memory is stated; the scopes the
    readers sum are in its text, latent attention's four products each
    under its own."""
    import jax
    from benchmark import scopes
    from benchmark.archs import deepseek_v3 as arch

    compiled, text = kanana_step["compiled"], kanana_step["text"]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n{CELL} step for a described v5e: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    assert sum(a.size for a in jax.tree.leaves(kanana_step["params"])) == \
        arch.parameters(kanana_step["sizes"])["held"] == \
        kanana_step["config"]["parameters"] == 1356783616
    # bf16 weights and two bf16 moments of 1,357 M parameters.
    assert 8.1e9 < mem.argument_size_in_bytes < 8.25e9
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("flash_fwd_d192v128", "flash_bwd_d192v128",
                   "gmm", "tgmm", "rope_to_heads",
                   "rope_from_heads"):
        assert any(kernel in c.partition(" = ")[0] for c in calls), kernel
    # What crosses HBM at a flash call is the parts the projections wrote
    # (PR 50), as in Xing4.0's step: rows of 32 heads (4,096 lanes), a
    # head's key and value side by side (8,192), the rotary parts 64 wide;
    # nothing concatenated (192) and nothing padded (256).
    for call in calls:
        if "flash_" in call.partition(" = ")[0]:
            widths = {int(dims.split(",")[-1]) for dims in re.findall(
                r"bf16\[([0-9,]+)\]", call)}
            assert widths == {4096, 8192, 64}, call[:300]
    # Several tiles a grid step, inside the default scoped VMEM (PR 52).
    _assert_the_flash_kernels_walk_tiles(text)
    by = {"scopes": {scopes.scope_path(name): 1.0
                     for name in scopes.op_names(text).values()}}
    for scope in ("block/attn/mla/q", "block/attn/mla/kv_a",
                  "block/attn/mla/kv_b", "block/attn/mla/out",
                  "block/attn/rope", "block/attn/mla", "block/moe/experts",
                  "block/moe/shared", "block/mlp"):
        assert scopes.seconds_under(by, scope) > 0, scope
    # ``loss``, ``embed`` and ``final_norm`` are entered straight under
    # ``jax.grad`` in ``xing4.loss_and_report`` and read ``jvp(loss)``,
    # which ``scopes.py`` drops (PERF.md section 7): not asserted.


def test_nothing_q_sized_moves_round_the_kernels_without_a_bottleneck(
        kanana_step):
    """``test_nothing_q_sized_moves_round_latent_attention_s_kernels`` of
    Xing4.0's step, for the query projection without a bottleneck (``wq``
    sliced as a weight) and ``layer_rows`` rows a call: under ``block/attn``
    no copy or transpose as large as q (192 wide), ``kv`` (256), v / the
    result (128) or the rotary part (64), the one rotary key head never laid
    under 32 heads, nothing as large as q concatenated, and no instruction
    there writes a 192-wide array at all."""
    import math
    text = kanana_step["text"]
    rows = kanana_step["config"]["train"]["layer_rows"]
    for width in (192, 256, 128, 64):
        assert not _q_sized_copies(text, (rows, 32, 8192, width)), width
    own = [line for line in text.splitlines()
           if '"estimated_cycles"' in line and "block/attn" in line]
    assert len(own) > 50
    for line in own:
        m = re.match(
            r"\s*(?:ROOT )?%\S+ = (\w+)\[([0-9,]*)\]\S* ([\w\-]+)\(", line)
        if not m:       # a tuple's: the kernels', checked by their widths
            continue
        dims = [int(n) for n in m.group(2).split(",") if n]
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert not (m.group(3) == "broadcast"
                    and dims == [rows, 32, 8192, 64]), line[:300]
        assert dims[-1:] != [192], line[:300]
        assert not (op_name.endswith("/concatenate")
                    and math.prod(dims) >= rows * 32 * 8192 * 128), line[:300]
