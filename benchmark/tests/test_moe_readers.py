"""The sparse cell's counts by hand, and its readers on the labels and
seconds a traced run of ``trinity-mini.train-moe8k`` left on a TPU v5e
(seed 2147488002, three traced steps of 4 rows of 8,192, a layer taking one
row at a time; PERF.md section 6, PR 29), with that run's own readings."""

import pytest

from benchmark import roofline, roofline_moe, scopes
from benchmark.layer_metrics import (grouped_mm_roofline, mfu_active_pct,
                                     moe_device_share, window_attn_roofline)

V5E = "TPU v5 lite"
STEP = "jit_train_step(9335851888007664993)/"
#: label -> (seconds, calls) over the three traced steps.  Calls: 7 window
#: and 2 full layers, 4 rows, one row a call; a forward runs twice (remat).
RECORDED = {
    "fusion": (2.578906650000106, 5000),
    "flash_fwd_w<bf16,f32>": (0.41739078299999777, 7 * 4 * 2 * 3),
    "flash_dkv_w<f32,f32>": (0.36969468999999966, 7 * 4 * 3),
    "flash_dq_w<bf16>": (0.2583604679999999, 7 * 4 * 3),
    "flash_fwd<bf16,f32>": (0.214334302999998, 2 * 4 * 2 * 3),
    "flash_dkv<f32,f32>": (0.19674530099999887, 2 * 4 * 3),
    "flash_dq<bf16>": (0.14012293400000153, 2 * 4 * 3),
    "gmm<bf16>": (0.319558606999997, 8 * 4 * 7 * 3),
    "tgmm<bf16>": (0.11844713500000337, 8 * 4 * 3 * 3),
    "convolution_add_fusion": (0.2635201599999998, 300),
    "copy": (0.1497106080000931, 900),
}
SIZES = {"E": 2048, "H": 32, "Hkv": 4, "D": 128, "Me": 1024, "Xh": 16,
         "window": 2048}


def recorded_facts(**arch):
    return {
        "trace": {"busy_s": 6.122054547000045, "window_s": 6.128137924,
                  "devices": 1,
                  "op_seconds": {STEP + k: v[0] for k, v in RECORDED.items()},
                  "op_counts": {STEP + k: v[1] for k, v in RECORDED.items()}},
        "device": {"kind": V5E, "count": 1}, "seq_len": 8192,
        "tokens_per_step": 32768,
        "arch": {"sizes": SIZES, "expert_layers": 8, "rows_a_call": 1,
                 "parameters": {"always": 386871552, "expert": 6291456},
                 # the run's three traced steps held 96,744.125 in all
                 "moe_traced": [{"moe_held_assignments": 96744.125 / 3}] * 3,
                 **arch}}


def test_visible_pairs_by_hand():
    assert roofline.visible_pairs(8192) == 8192 * 8193 / 2 == 33558528
    # the first 2,048 rows see a triangle, the other 6,144 see 2,048 each
    assert roofline.visible_pairs(8192, 2048) == \
        2048 * 2049 / 2 + 6144 * 2048 == 14681088
    assert roofline.visible_pairs(8192, 8192) == \
        roofline.visible_pairs(8192, 9000) == 33558528
    assert roofline.visible_pairs(4, 2) == 3 + 2 * 2


def test_banded_flash_call_by_hand():
    # ``roofline.flash_call`` at one head size and a window: until PR 61
    # this file's own ``roofline_moe.banded_flash_call``
    ops, moved = roofline.flash_call("fwd", 1, 32, 4, 8192, 128,
                                     window=2048)
    assert ops == 2 * 2 * 32 * 14681088 * 128
    q, kv, lse = 32 * 8192 * 128 * 2, 4 * 8192 * 128 * 2, 32 * 8192 * 4
    assert moved == 2 * q + 2 * kv + lse
    # compute bound on a v5e: 1.22 ms against 0.17 ms
    assert roofline.least_seconds(ops, moved, V5E) == \
        pytest.approx(ops / 197e12)
    full, _ = roofline.flash_call("fwd", 1, 32, 4, 8192, 128)
    dq, _ = roofline.flash_call("dq", 1, 32, 4, 8192, 128)
    dkv, moved = roofline.flash_call("dkv", 1, 32, 4, 8192, 128)
    assert (dq, dkv) == (1.5 * full, 2 * full)
    assert full == roofline.flash_attention_call(
        "fwd", 1, 32, 4, 8192, 128)[0] * 8193 / 8192    # the diagonal's half
    assert moved == 2 * q + 4 * kv + 2 * lse


def test_expert_products_by_hand():
    # 32,768 rows to 16 held experts: 12 products' worth (3 a pass, forward,
    # recomputed, backward twice) of 2 * 2048 * 1024 operations a row.
    ops, moved = roofline_moe.expert_products(32768, 2048, 1024, 16)
    assert ops == 12 * 2 * 32768 * 2048 * 1024 == 1649267441664
    assert moved == 12 * 2 * (32768 * (2048 + 1024) + 16 * 2048 * 1024)
    assert roofline.least_seconds(ops, moved, V5E) == \
        pytest.approx(ops / 197e12)                     # 8.4 ms a layer
    assert roofline_moe.expert_products(0, 2048, 1024, 16)[0] == 0


def test_readers_give_the_recorded_runs_readings():
    facts = recorded_facts()
    assert window_attn_roofline.read(facts) == pytest.approx(58.404, abs=1e-3)
    assert grouped_mm_roofline.read(facts) == pytest.approx(45.145, abs=1e-3)
    assert mfu_active_pct.read(facts) == pytest.approx(21.3426, abs=1e-4)


def test_window_calls_are_told_from_full_calls_with_or_without_digits():
    """A trace's label drops the digits of ``flash_fwd_w2048``; the reader
    takes either form, and a full layer's call never as a window layer's."""
    facts = recorded_facts()
    want = window_attn_roofline.read(facts)
    for table in ("op_seconds", "op_counts"):
        facts["trace"][table] = {
            k.replace("_w<", "_w2048<"): v
            for k, v in facts["trace"][table].items()}
    assert window_attn_roofline.read(facts) == pytest.approx(want)
    only_full = recorded_facts()
    for table in ("op_seconds", "op_counts"):
        only_full["trace"][table] = {
            k: v for k, v in only_full["trace"][table].items()
            if "_w<" not in k}
    # 136 against 70 block pairs: the full layers alone read higher
    assert only_full["trace"]["op_seconds"] and \
        window_attn_roofline.read(only_full) == pytest.approx(66.837, abs=1e-3)


def test_the_one_pass_is_counted_beside_the_recorded_pair():
    """The recorded run is PR 29's, from before the one pass; since PR 60
    the cell's backward is ``flash_bwd_w2048`` and ``flash_bwd``, whose
    labels end in ``<f32,f32,bf16>``, and since PR 61 the reader counts
    them: five products a tile over the band or the triangle."""
    facts = recorded_facts()
    before = window_attn_roofline.read(facts)
    added = {"flash_bwd_w<f32,f32,bf16>": (0.40, 7 * 4 * 3),
             "flash_bwd<f32,f32,bf16>": (0.20, 2 * 4 * 3)}
    for k, (seconds, calls) in added.items():
        facts["trace"]["op_seconds"][STEP + k] = seconds
        facts["trace"]["op_counts"][STEP + k] = calls
    pair = sum(v[0] for k, v in RECORDED.items() if k.startswith("flash_"))
    least = before / 100 * pair + sum(
        calls * roofline.least_seconds(*roofline.flash_call(
            "bwd", 1, 32, 4, 8192, 128, window=window), V5E)
        for calls, window in ((7 * 4 * 3, 2048), (2 * 4 * 3, None)))
    assert window_attn_roofline.read(facts) == pytest.approx(
        100 * least / (pair + 0.60))


def test_grouped_products_are_matched_by_kernel_name():
    import re
    rx = re.compile(grouped_mm_roofline.KERNELS)
    matched = {k for k in RECORDED if rx.search(STEP + k)}
    assert matched == {"gmm<bf16>", "tgmm<bf16>"}
    assert rx.search("jit_train_step(1)/ragged-dot<>")      # off the TPU
    facts = recorded_facts()
    facts["arch"]["moe_traced"] = []
    assert grouped_mm_roofline.read(facts) is None
    assert mfu_active_pct.read(facts) is None


HLO = '''
HloModule jit_train_step

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %m = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(train_step)/forward_backward/jvp()/while/body/closed_call/block/moe/cond/branch_1_fun/dispatch/mul" source_file="x.py" source_line=3}
}

ENTRY %main {
  %fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/forward_backward/jvp()/while/body/closed_call/block/moe/cond/branch_1_fun/dispatch/gather" source_file="x.py"}
  %gmm.3 = bf16[8]{0} custom-call(bf16[8]{0} %fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/forward_backward/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/block/moe/cond/branch_1_fun/experts/pallas_call"}
  %fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %gmm.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/forward_backward/jvp()/while/body/closed_call/block/attn/bse,ehd->bhsd/dot_general"}
  %while.2 = (s32[]) while((s32[]) %t), body=%b, metadata={op_name="jit(train_step)/forward_backward/jvp()/while"}
  ROOT %copy.1 = bf16[8]{0} copy(bf16[8]{0} %fusion.9)
}
'''


def test_scope_path_keeps_the_named_scopes_alone():
    assert scopes.scope_path(
        "jit(train_step)/forward_backward/transpose(jvp())/while/body/"
        "closed_call/checkpoint/rematted_computation/block/moe/cond/"
        "branch_0_fun/combine/scatter-add") == \
        "forward_backward/block/moe/combine"
    assert scopes.scope_path(
        "jit(train_step)/forward_backward/jvp()/while/body/closed_call/"
        "block/moe/route/jit(take_along_axis)/gather") == \
        "forward_backward/block/moe/route"
    assert scopes.scope_path("jit(train_step)/optimizer/mul") == "optimizer"
    assert scopes.scope_path("params['moe']['wq']") == ""


def test_seconds_by_scope_joins_trace_and_program_text():
    ev = lambda name, a, b: (f"%{name} = bf16[8]{{0}} fusion(bf16[8] %x)",
                             a, b)
    loaded = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ev("fusion.7", 0.0, 1.0), ev("gmm.3", 1.0, 1.5),
        ev("fusion.9", 1.5, 3.5), ev("copy.1", 3.5, 3.75),
        ("%while.2 = (s32[]) while((s32[]) %t), body=%b", 0.0, 3.75)]}},
        "host": []}
    by = scopes.seconds_by_scope(loaded, HLO)
    assert by["scopes"] == {
        "forward_backward/block/moe/dispatch": 1.0,
        "forward_backward/block/moe/experts": 0.5,
        "forward_backward/block/attn": 2.0}
    assert by["named_s"] == 3.5 and by["ops_s"] == 3.75   # the copy: no name
    assert scopes.seconds_under(by, "block/moe") == 1.5
    assert scopes.seconds_under(by, "block/moe/experts") == 0.5
    assert scopes.seconds_under(by, "block/mo") == 0.0
    facts = recorded_facts(scopes=by)
    facts["trace"]["busy_s"] = 3.75
    assert moe_device_share.read(facts) == pytest.approx(40.0)
    assert moe_device_share.read(recorded_facts(scopes=None)) is None
    assert moe_device_share.read(recorded_facts()) is None
    assert scopes.seconds_by_scope({"devices": {}, "host": []}, HLO) == {
        "scopes": {}, "named_s": 0.0, "ops_s": 0.0}
