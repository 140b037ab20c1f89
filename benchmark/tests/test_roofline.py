"""Operations and bytes of each kernel at one shape, counted by hand."""

import pytest

from benchmark import roofline


def test_flash_forward_by_hand():
    # 1 row, 16 heads, 4096 x 4096 scores of depth 128, causal.
    ops, moved = roofline.flash_attention_call("fwd", 1, 16, 16, 4096, 128)
    one_matmul = 2 * 16 * 4096 * 4096 * 128 / 2         # 34.4 GFLOP
    assert ops == 2 * one_matmul == 68719476736.0
    tensor = 16 * 4096 * 128 * 2                        # 16 MiB in bf16
    assert moved == 4 * tensor + 16 * 4096 * 4
    # compute bound on a v5e: 0.35 ms against 0.08 ms
    assert roofline.least_seconds(ops, moved, "TPU v5 lite") == \
        pytest.approx(ops / 197e12)


def test_flash_backward_counts_its_matmuls():
    fwd, _ = roofline.flash_attention_call("fwd", 2, 32, 8, 4096, 128)
    dq, _ = roofline.flash_attention_call("dq", 2, 32, 8, 4096, 128)
    dkv, moved = roofline.flash_attention_call("dkv", 2, 32, 8, 4096, 128)
    assert (dq, dkv) == (1.5 * fwd, 2 * fwd)
    q, kv, lse = 2 * 32 * 4096 * 128 * 2, 2 * 8 * 4096 * 128 * 2, \
        2 * 32 * 4096 * 4
    assert moved == 2 * q + 4 * kv + 2 * lse


def test_paged_decode_by_hand():
    # 64 slots holding 12,800 tokens, 16 heads of 128, one layer.
    ops, moved = roofline.paged_attention_call(12800, 64, 16, 16, 128)
    assert ops == 4 * 16 * 128 * 12800
    assert moved == 12800 * 2 * 16 * 128 * 2 + 2 * 64 * 16 * 128 * 2
    # memory bound: 105 MB at 819 GB/s
    assert roofline.least_seconds(ops, moved, "TPU v5 lite") == \
        pytest.approx(moved / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_mfu_is_taken_over_the_traced_steps_busy_seconds():
    """Yi's 1,476,495,360 parameters, 3 traced steps of 4 x 4,096 tokens in
    4.855 s of device time: 6 * P * 49,152 / 4.855 / 197e12 = 45.5 %."""
    from benchmark.layer_metrics import mfu_pct
    sizes = {"V": 64000, "E": 2048, "L": 24, "H": 16, "Hkv": 16, "D": 128,
             "M": 5504}
    facts = {"trace": {"busy_s": 4.855193045}, "trace_steps": 3,
             "tokens_per_step": 16384, "cell": {"sizes": sizes},
             "device": {"count": 1, "kind": "TPU v5 lite"}}
    assert mfu_pct.read(facts) == pytest.approx(45.525, abs=1e-3)
    # four chips share the tokens; each chip's busy seconds are the same
    facts["device"]["count"], facts["tokens_per_step"] = 4, 4 * 16384
    assert mfu_pct.read(facts) == pytest.approx(45.525, abs=1e-3)
    assert mfu_pct.read({**facts, "trace": None}) is None
