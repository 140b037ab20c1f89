"""``xing4.0-29b-a4b.train-mhc8k``'s train step compiles for a described v5e,
without a chip.  A file a cell: ``--dist loadfile`` keeps a file on one
worker, and the step is compiled here and nowhere else.  The fixtures and
the readers of a compiled program's text are ``tests/v5e_compile.py``'s,
imported: describing the topology happens inside the fixture, in the worker
that is given THIS file, never while a module is imported.
"""

from __future__ import annotations

import pytest

from v5e_compile import (  # noqa: F401 (``topo`` is a fixture)
    ROOT, _assert_rows_leave_the_experts_buffer_by_the_rows_in_use,
    _assert_the_flash_kernels_walk_tiles, _cell_step, _kernels,
    _q_sized_copies, topo)


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


@pytest.mark.parametrize("T,k,E", [(8192, 4, 3584)], ids=["xing4"])
def test_rows_leave_the_experts_buffer_by_the_rows_in_use(xing4_step, T, k,
                                                         E):
    """The sums over a token's rows in this cell's compiled step (what is
    asserted: the helper's docstring)."""
    _assert_rows_leave_the_experts_buffer_by_the_rows_in_use(
        xing4_step["text"], T, k, E)
