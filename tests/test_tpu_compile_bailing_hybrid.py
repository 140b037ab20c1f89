"""``ling-3.0-flash.train-kda8k``'s train step compiles for a described v5e,
without a chip.  A file a cell: ``--dist loadfile`` keeps a file on one
worker, and the step is compiled here and nowhere else.  The fixtures and
the readers of a compiled program's text are ``tests/v5e_compile.py``'s,
imported: describing the topology happens inside the fixture, in the worker
that is given THIS file, never while a module is imported.
"""

from __future__ import annotations

import json
import os

import pytest

from v5e_compile import (  # noqa: F401 (``topo`` is a fixture)
    ROOT, _assert_the_experts_buffer_has, _cell_step, _kernels, topo)

CELL = "ling-3.0-flash.train-kda8k"


@pytest.fixture(scope="module")
def ling_step(topo):
    """The cell's step (1 dense + 6 sparse layers, six KDA mixers and one
    of latent attention, 16 of 512 experts, rows of 8,192, full remat, the
    delta rule's Pallas pair, flash at 128 + 64 / 128, Pallas grouped
    products)."""
    from benchmark.archs import bailing_hybrid
    with open(os.path.join(ROOT, "benchmark/traffic/train-kda8k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, bailing_hybrid, "ling-3.0-flash.json", seq,
                      moe_impl="gmm")


def test_ling_train_step_compiles_at_the_cell_sizes(ling_step, capsys):
    """The step compiles for one described v5e chip with the Mosaic kernels
    in it: the delta rule's pair and the passes before and after it
    (``kda_in_fwd`` / ``kda_in_bwd``, ``kda_norm_fwd`` / ``kda_norm_bwd``)
    by name and inside the DEFAULT scoped VMEM (no limit stated), latent
    attention's flash kernels at 192 / 128, the
    grouped products; the parameter count is the config file's and the
    issue's; the scopes the readers sum are in its text."""
    import re

    import jax
    from benchmark import scopes
    from benchmark.archs import bailing_hybrid as arch

    compiled, text = ling_step["compiled"], ling_step["text"]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n{CELL} step for a described v5e: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    assert sum(a.size for a in jax.tree.leaves(ling_step["params"])) == \
        arch.parameters(ling_step["sizes"])["held"] == \
        ling_step["config"]["parameters"] == 1167571904
    # bf16 weights and two bf16 moments of 1,168 M parameters (A_log and
    # dt_bias, 25 k numbers, in float32).
    assert 7.0e9 < mem.argument_size_in_bytes < 7.1e9
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("kda_fwd_c128", "kda_bwd_c128", "kda_in_fwd", "kda_in_bwd",
                   "kda_norm_fwd", "kda_norm_bwd", "flash_fwd_d192v128",
                   "flash_bwd_d192v128", "gmm", "tgmm", "rope_to_heads"):
        assert any(kernel in c.partition(" = ")[0] for c in calls), kernel
    # The pass between the projections and the scan, forward (and
    # recomputed) and backward, runs under the convolution's scope (what
    # ``kda_conv_roofline`` divides by), the one after the scan under the
    # norm's.
    named = scopes.op_names(text)
    for kernel, scope in (("kda_in_fwd", "conv"), ("kda_in_bwd", "conv"),
                          ("kda_norm_fwd", "norm"), ("kda_norm_bwd", "norm")):
        under = [scopes.scope_path(op) for name, op in named.items()
                 if name.startswith(kernel)]
        assert under and all(f"block/attn/kda/{scope}" in u
                             for u in under), (kernel, under[:3])
    for call in calls:
        if "kda_" in call.partition(" = ")[0]:
            assert '"scoped_memory_configs":[]' in call, call[:200]
            used = re.search(r'"used_scoped_memory_configs":\[\{"memory_'
                             r'space":"1","offset":"0","size":"(\d+)"', call)
            assert used and int(used.group(1)) < 16 * 2 ** 20
    by = {"scopes": {scopes.scope_path(name): 1.0
                     for name in scopes.op_names(text).values()}}
    for scope in ("block/attn/kda/proj", "block/attn/kda/conv",
                  "block/attn/kda/gate", "block/attn/kda/scan",
                  "block/attn/kda/norm", "block/attn/kda/out",
                  "block/attn/mla/q", "block/attn/mla/gate",
                  "block/attn/mla/out", "block/moe/route",
                  "block/moe/experts", "block/moe/shared", "block/mlp"):
        assert scopes.seconds_under(by, scope) > 0, scope


def test_the_experts_buffer_is_twice_the_expected_load(ling_step):
    """16 of 512 experts held and 8 choices a token: a row of 8,192 tokens goes
    through 4,096 rows (16 tiers), not the 16,384 of four."""
    _assert_the_experts_buffer_has(ling_step["text"], 4096, 16384)
