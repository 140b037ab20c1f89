"""``granite-4.0-h-micro.train-pack32k``'s train step compiles for a
described v5e, without a chip.  A file a cell: ``--dist loadfile`` keeps a
file on one worker, and the step is compiled here and nowhere else.  The
fixtures and the readers of a compiled program's text are
``tests/v5e_compile.py``'s, imported: describing the topology happens inside
the fixture, in the worker that is given THIS file, never while a module is
imported.
"""

from __future__ import annotations

import pytest

from v5e_compile import ROOT, _cell_step, _kernels, topo  # noqa: F401


@pytest.fixture(scope="module")
def granite_step(topo):
    """``granite-4.0-h-micro.train-pack32k``'s step: nine Mamba-2 layers and
    one attention layer, each with its feed-forward of 8,192, unrolled; one
    packed row of 32,768 with segment ids, full remat, the tied head's loss
    in chunks."""
    import json
    import os
    from benchmark.archs import granitemoehybrid
    with open(os.path.join(ROOT, "benchmark/traffic/train-pack32k.json")) as f:
        seq = json.load(f)["seq_len"]
    return _cell_step(topo, granitemoehybrid, "granite-4.0-h-micro.json", seq,
                      batch_keys=("tokens", "loss_mask", "segment_ids"))


def test_granite_train_step_compiles_at_the_cell_sizes(granite_step, capsys):
    """The step compiles for one described v5e chip with the Mosaic kernels
    in it: the scan's pair at the published chunk over slices of the one
    wide group, under the scope its reader sums, the convolution's pair
    (``ssm_conv_fwd`` / ``ssm_conv_bwd``) feeding it, and the three flash
    kernels of a call with documents under names of their own; its memory
    is stated and inside the chip; no kernel states a scoped limit over
    Mosaic's default; the parameters are the configuration's."""
    import re

    import jax
    from benchmark import scopes
    from benchmark.archs import granitemoehybrid as arch

    compiled, text = granite_step["compiled"], granite_step["text"]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\ngranite-4.0-h-micro.train-pack32k step for a described "
              f"v5e: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    assert sum(a.size for a in jax.tree.leaves(granite_step["params"])) == \
        arch.parameters(granite_step["sizes"])["held"] == \
        granite_step["config"]["parameters"] == 951991232
    # bf16 weights and two bf16 moments of 952 M parameters
    assert 5.6e9 < mem.argument_size_in_bytes < 5.8e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.91e9
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    chunk = granite_step["config"]["train"]["chunk"]
    for kernel in (f"ssd_fwd_q{chunk}", f"ssd_bwd_q{chunk}",
                   "flash_seg_fwd_d64", "flash_seg_dq_d64",
                   "flash_seg_dkv_d64"):
        assert any(kernel in c.partition(" = ")[0] for c in calls), kernel
    # no call without documents in this step
    assert not any(re.match(r"%?flash_(fwd|dq|dkv|bwd)", c) for c in calls)
    names = scopes.op_names(text)
    scans = [c for c in calls if c.lstrip("%").startswith("ssd_")]
    # nine mixers: forward, recomputed forward and backward each
    assert len(scans) == 27, len(scans)
    for c in scans:
        assert "block/ssm/scan" in names[
            c.partition(" = ")[0].lstrip("%")], c[:200]
    # The convolution is the Pallas pair, a call a part (X, B, C): three
    # forward, three recomputed and three backward a mixer, under the scope
    # its reader sums, at Mosaic's own scoped limit (none stated); the
    # scan's kernels read what the pair wrote, and no ``split`` of the
    # convolution's result stands between.
    convs = [c for c in calls if c.lstrip("%").startswith("ssm_conv_")]
    assert sum("ssm_conv_fwd" in c.partition(" = ")[0] for c in convs) == 54
    assert sum("ssm_conv_bwd" in c.partition(" = ")[0] for c in convs) == 27
    for c in convs:
        assert "block/ssm/conv" in names[
            c.partition(" = ")[0].lstrip("%")], c[:200]
        assert re.findall(r'"scoped_memory_configs":\[\{"memory_space":"1",'
                          r'"offset":"0","size":"(\d+)"', c) == [
                              str(16 * 2 ** 20)], c[:300]
    for c in scans:
        if "ssd_fwd" in c.partition(" = ")[0]:
            fed = c.partition("custom-call(")[2].split(", ")[:3]
            assert all(o.lstrip("%").startswith("ssm_conv_fwd")
                       for o in fed), c[:300]
    # The one group of 64 heads goes through in 8 slices of 512 channels:
    # a slice's share of dB and dC leaves in float32.
    assert any("f32[1,32768,1024]" in c.partition(" custom-call(")[0]
               for c in scans if "ssd_bwd" in c.partition(" = ")[0])
    for call in calls:
        for limit in re.findall(
                r'"scoped_memory_configs":\[\{"memory_space":"1",'
                r'"offset":"0","size":"(\d+)"', call):
            assert int(limit) <= 100 * 2 ** 20, call[:300]
    # the first forward's operations keep their scopes (no ``jvp(block/..``)
    assert not any("jvp(block" in n for n in names.values())
    for scope in ("block/ssm/proj", "block/ssm/conv", "block/ssm/scan",
                  "block/ssm/norm", "block/attn", "block/mlp", "loss"):
        assert any(scope in n for n in names.values()), scope
