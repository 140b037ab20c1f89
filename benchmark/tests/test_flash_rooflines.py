"""The seven attention rooflines on hand-made traces: each reads the forward
AND the backward's one pass (``flash_bwd…``, PRs 54 and 60; counted since
PR 61), by name, and the seconds in its denominator are the trace's total for
the ``flash_(fwd|dq|dkv|bwd)`` kernels of its cell."""

import json

import pytest

from benchmark import roofline, trace
from benchmark.layer_metrics import (flash_attn_roofline, flash_d64_roofline,
                                     gdla_attn_roofline,
                                     gdla_window_roofline, gqa_attn_roofline,
                                     mla_attn_roofline, window_attn_roofline)

V5E = "TPU v5 lite"
STEP = "jit_train_step(1)/"
FLASH = r"/flash_(fwd|dq|dkv|bwd)"
#: what is in a cell's trace beside its flash kernels, some of it named to
#: tempt a pattern
OTHERS = {"fusion": 3.0, "rope_to_heads<bf16,s32>": 0.2, "gmm<bf16>": 0.4,
          "eva_dq_w2048c<bf16>": 0.3, "closed_call<bf16>": 0.1}


def _facts(labels, sizes, rows_a_call=1, seq=8192, **more):
    """``labels``: {label: (seconds, calls, (kind, d_qk, d_v, window))}."""
    ops = {**{k: (v, 7) for k, v in OTHERS.items()},
           **{k: v[:2] for k, v in labels.items()}}
    return {"trace": {"busy_s": 10.0,
                      "op_seconds": {STEP + k: v[0] for k, v in ops.items()},
                      "op_counts": {STEP + k: v[1] for k, v in ops.items()}},
            "seq_len": seq, "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": sizes, "rows_a_call": rows_a_call}, **more}


def _least(labels, batch, heads, kv_heads, seq, call=roofline.flash_call):
    return sum(calls * roofline.least_seconds(
        *call(kind, batch, heads, kv_heads, seq, *rest), V5E)
        for _s, calls, (kind, *rest) in labels.values())


def _spent_said(capsys, reader):
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith(f"[kernels] reader={reader} ")]
    assert len(said) == 1, said
    matched = json.loads(said[0].partition("matched=")[2])
    return sum(v[1] for v in matched.values()), set(matched)


# label -> (seconds over the traced steps, calls, how it is counted)
YI = {"flash_fwd<bf16,f32>": (0.37, 144, ("fwd", 128)),
      "flash_bwd<bf16,bf16,bf16>": (0.34, 72, ("bwd", 128))}
MISTRAL = {"flash_fwd<bf16,f32>": (0.09, 48, ("fwd", 128)),
           "flash_bwd<f32,f32,bf16>": (0.19, 24, ("bwd", 128)),
           # a ring shard's rectangle keeps the pair
           "flash_dq<bf16>": (0.05, 3, ("dq", 128)),
           "flash_dkv<f32,f32>": (0.06, 3, ("dkv", 128))}


@pytest.mark.parametrize("labels,rows,heads,kv_heads", [
    (YI, 4, 16, 16), (MISTRAL, 1, 32, 8)])
def test_flash_attn_roofline(capsys, labels, rows, heads, kv_heads):
    facts = _facts(labels, None, seq=4096, rows=rows, cell={"sizes": {
        "H": heads, "Hkv": kv_heads, "D": 128}})
    least = _least(labels, rows, heads, kv_heads, 4096,
                   roofline.flash_attention_call)
    spent = sum(v[0] for v in labels.values())
    assert flash_attn_roofline.read(facts) == pytest.approx(
        100 * least / spent)
    said, matched = _spent_said(capsys, "flash_attn_roofline")
    assert matched == set(labels)
    assert said == pytest.approx(
        trace.ops_matching(facts["trace"], FLASH)[0]) == pytest.approx(spent)
    # the forward alone, which is what the reading was from PR 54 to PR 61
    for table in ("op_seconds", "op_counts"):
        facts["trace"][table] = {k: v for k, v in facts["trace"][
            table].items() if "flash_" not in k or "flash_fwd" in k}
    fwd = {k: v for k, v in labels.items() if k.startswith("flash_fwd")}
    assert flash_attn_roofline.read(facts) == pytest.approx(
        100 * _least(fwd, rows, heads, kv_heads, 4096,
                     roofline.flash_attention_call) / fwd[
                         "flash_fwd<bf16,f32>"][0])


TRINITY = {"flash_fwd_w<bf16,f32>": (0.35, 168, ("fwd", 128, 128, 2048)),
           "flash_bwd_w<f32,f32,bf16>": (0.40, 84, ("bwd", 128, 128, 2048)),
           "flash_fwd<bf16,f32>": (0.18, 48, ("fwd", 128)),
           "flash_bwd<f32,f32,bf16>": (0.20, 24, ("bwd", 128))}
NEMOTRON = {"flash_fwd<bf16,f32>": (0.089, 48, ("fwd", 128)),
            "flash_bwd<f32,f32,bf16>": (0.206, 24, ("bwd", 128))}
LFM2 = {"flash_fwd_d<bf16,f32>": (0.098, 12, ("fwd", 64)),
        "flash_bwd_d<f32,f32,bf16>": (0.206, 6, ("bwd", 64))}
KANANA = {"flash_fwd_d192v<bf16,f32>": (1.68, 144, ("fwd", 192, 128)),
          "flash_bwd_d192v<bf16,bf16,bf16,bf16>": (
              1.81, 144, ("bwd", 192, 128))}


@pytest.mark.parametrize("reader,labels,sizes,rows,kv_heads", [
    (window_attn_roofline, TRINITY,
     {"H": 32, "Hkv": 4, "D": 128, "window": 2048}, 1, 4),
    (gqa_attn_roofline, NEMOTRON,
     {"H": 32, "Hkv": 2, "D": 128, "Hm": 64}, 1, 2),
    (flash_d64_roofline, LFM2, {"H": 32, "Hkv": 8, "D": 64}, 4, 8),
    (mla_attn_roofline, KANANA,
     {"H": 32, "dn": 128, "dr": 64, "dv": 128}, 1, 32)])
def test_a_cell_s_reader_counts_its_forward_and_its_one_pass(
        capsys, reader, labels, sizes, rows, kv_heads):
    name = reader.__name__.rsplit(".", 1)[1]
    facts = _facts(labels, sizes, rows_a_call=rows)
    least = _least(labels, rows, 32, kv_heads, 8192)
    spent = sum(v[0] for v in labels.values())
    got = reader.read(facts)
    assert got == pytest.approx(100 * least / spent) and 0 < got < 105
    said, matched = _spent_said(capsys, name)
    assert matched == set(labels)
    assert said == pytest.approx(
        trace.ops_matching(facts["trace"], FLASH)[0]) == pytest.approx(spent)


MOTIF = {"flash_fwd_d192v<bf16,f32>": (0.044, 3, ("fwd", 192, 128)),
         "flash_bwd_d192v<f32,bf16,bf16>": (0.095, 3, ("bwd", 192, 128)),
         "flash_fwd_d192v128_w<bf16,f32>": (
             0.020, 9, ("fwd", 192, 128, 128)),
         "flash_dq_d192v128_w<bf16>": (0.021, 9, ("dq", 192, 128, 128)),
         "flash_dkv_d192v128_w<f32,bf16>": (
             0.024, 9, ("dkv", 192, 128, 128))}


def test_motif_s_two_readers_part_its_kernels_between_them(capsys):
    sizes = {"H": 80, "Hkv": 16, "dn": 128, "dr": 64, "dv": 128, "W": 128}
    facts = _facts(MOTIF, sizes)
    total = 0.0
    for reader, mine in ((gdla_attn_roofline, lambda k: "_w<" not in k),
                         (gdla_window_roofline, lambda k: "_w<" in k)):
        labels = {k: v for k, v in MOTIF.items() if mine(k)}
        spent = sum(v[0] for v in labels.values())
        assert reader.read(facts) == pytest.approx(
            100 * _least(labels, 1, 80, 16, 8192) / spent)
        said, matched = _spent_said(
            capsys, reader.__name__.rsplit(".", 1)[1])
        assert matched == set(labels) and said == pytest.approx(spent)
        total += said
    assert total == pytest.approx(
        trace.ops_matching(facts["trace"], FLASH)[0])
    # ``mla_attn_roofline`` would take the full layers' kernels at a key
    # head a QUERY head, five times the keys' and values' bytes: operations
    # bind, so it would print ``gdla_attn_roofline``'s number a second time,
    # and the cell is not in its list (PR 61)
    assert roofline.flash_call("bwd", 1, 80, 80, 8192, 192, 128)[1] > \
        roofline.flash_call("bwd", 1, 80, 16, 8192, 192, 128)[1]
    assert mla_attn_roofline.read(facts) == gdla_attn_roofline.read(facts)


def test_nothing_to_read_is_nothing():
    bare = _facts({}, {"H": 32, "Hkv": 4, "D": 128, "window": 2048,
                       "Hm": 64, "dn": 128, "dr": 64, "dv": 128, "W": 128},
                  rows=4, cell={"sizes": {"H": 32, "Hkv": 4, "D": 128}})
    for reader in (flash_attn_roofline, window_attn_roofline,
                   gqa_attn_roofline, flash_d64_roofline, mla_attn_roofline,
                   gdla_attn_roofline, gdla_window_roofline):
        assert reader.read(bare) is None
        assert reader.read({"trace": None, "arch": None}) is None
