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


#: (batch, query heads, key heads, tokens, d_qk, d_v, window): a group of
#: 1 (Yi's and Ouro's call), of 8 with a window (Trinity-Mini's window
#: layers), of 4 at 64 (LFM2's), 192 / 128 with a key head a query head
#: (Kanana's, Xing4.0's and Ling's) and under a group of 5 (Motif's)
ONE_PASS_SHAPES = [(4, 16, 16, 4096, 128, 128, None),
                   (1, 32, 4, 8192, 128, 128, 2048),
                   (4, 32, 8, 8192, 64, 64, None),
                   (1, 32, 32, 8192, 192, 128, None),
                   (1, 80, 16, 8192, 192, 128, None)]


@pytest.mark.parametrize("shape", ONE_PASS_SHAPES)
def test_the_one_pass_by_hand(shape):
    """``bwd``: S and dP once and three gradients, five products a tile,
    ``3 d_qk + 2 d_v`` channels a pair; q, k, v, do, the log-sum-exp and
    delta in and dq, dk, dv out, keys and values and their gradients once a
    KEY head; the float32 shares a group's query heads leave are not the
    algorithm's."""
    b, h, hkv, seq, d_qk, d_v, window = shape
    ops, moved = roofline.flash_call("bwd", b, h, hkv, seq, d_qk, d_v, window)
    pairs = seq * (seq + 1) / 2 if window is None else \
        window * (window + 1) / 2 + (seq - window) * window
    assert ops == 2 * b * h * pairs * (3 * d_qk + 2 * d_v)
    q, do = b * h * seq * d_qk * 2, b * h * seq * d_v * 2
    k, v = b * hkv * seq * d_qk * 2, b * hkv * seq * d_v * 2
    lse = b * h * seq * 4
    assert moved == (q + k + v + do + 2 * lse) + (q + k + v)
    # the pair forms S and dP twice and reads its operands twice
    dq = roofline.flash_call("dq", b, h, hkv, seq, d_qk, d_v, window)
    dkv = roofline.flash_call("dkv", b, h, hkv, seq, d_qk, d_v, window)
    assert dq[0] + dkv[0] - ops == 2 * b * h * pairs * (d_qk + d_v)
    assert dq[1] + dkv[1] - moved == q + k + v + do + 2 * lse
    # bound by its operations on a v5e at every cell's shape
    assert roofline.least_seconds(ops, moved, "TPU v5 lite") == \
        pytest.approx(ops / 197e12)


@pytest.mark.parametrize("which,products", [("fwd", 2), ("dq", 3),
                                            ("dkv", 4), ("bwd", 5)])
def test_the_first_cells_door_counts_half_the_square(which, products):
    """``flash_attention_call`` is ``flash_call`` with ``seq * seq / 2``
    pairs, the diagonal's half left out, as ``flash_attn_roofline`` has
    counted since PR 26: 1 / seq under the triangle's count."""
    ops, moved = roofline.flash_attention_call(which, 2, 32, 8, 4096, 128)
    assert ops == products * 2 * 2 * 32 * (4096 * 4096 / 2) * 128
    whole = roofline.flash_call(which, 2, 32, 8, 4096, 128)
    assert whole[0] == ops * 4097 / 4096 and whole[1] == moved


def test_kernels_share_says_what_it_matched(capsys):
    import json
    reduced = {"op_seconds": {"jit_step/flash_fwd<bf16,f32>": 0.5,
                              "jit_step/flash_bwd<f32,f32,bf16>": 1.0,
                              "jit_step/fusion": 4.0},
               "op_counts": {"jit_step/flash_fwd<bf16,f32>": 10,
                             "jit_step/flash_bwd<f32,f32,bf16>": 5,
                             "jit_step/fusion": 100}}
    got = roofline.kernels_share(
        "a_reader", reduced, "TPU v5 lite", r"/flash_(fwd|bwd)<",
        lambda m: (197e12 * (0.01 if m.group(1) == "fwd" else 0.1), 1.0))
    assert got == pytest.approx(100 * (10 * 0.01 + 5 * 0.1) / 1.5)
    line = capsys.readouterr().out.strip()
    assert line.startswith("[kernels] reader=a_reader spent_s=1.5 ")
    matched = json.loads(line.partition("matched=")[2])
    assert matched == {
        "flash_fwd<bf16,f32>": [10, 0.5, pytest.approx(0.1)],
        "flash_bwd<f32,f32,bf16>": [5, 1.0, pytest.approx(0.5)]}
    assert roofline.kernels_share("a_reader", reduced, "TPU v5 lite",
                                  r"/no_such_kernel", None) is None


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
