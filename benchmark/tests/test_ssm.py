"""The counts and readers that the cell ``nemotron-3-nano-30b-a3b.train-ssm8k``
brought, by hand at its shapes, and its rehearsal on the CPU.  (Cases for
``test_roofline.py`` and ``test_rehearsal.py``, kept in a file of their own:
a PR that adds a cell edits no file the benchmark already has.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import roofline, roofline_moe, roofline_ssm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = "TPU v5 lite"
CELL = "nemotron-3-nano-30b-a3b.train-ssm8k"
SIZES = {"E": 2688, "L": 13, "kinds": "MEMEM*EMEMEM*", "H": 32, "Hkv": 2,
         "D": 128, "Hm": 64, "P": 64, "N": 128, "G": 8, "K": 4, "Q": 128,
         "Me": 1856, "Ms": 3712, "X": 128, "Xh": 16}


def test_scan_and_convolution_by_hand():
    # One token of one mixer: X and y 4,096 wide, B and C 1,024, 64 steps.
    ops, moved = roofline_ssm.scan_passes(1, 64, 64, 8, 128, 128, passes=1)
    assert ops == 2 * 128 * (1024 + 4096) + 4 * 64 * 64 * 128 == 3407872
    assert moved == 2 * (4096 + 4096 + 1024 + 1024 + 64)
    # 16,384 tokens, four passes: memory bound on a v5e (1.65 ms for 1.13).
    ops, moved = roofline_ssm.scan_passes(16384, 64, 64, 8, 128, 128)
    assert ops == 4 * 16384 * 3407872
    assert roofline.least_seconds(ops, moved, V5E) == pytest.approx(
        moved / 819e9)
    ops, moved = roofline_ssm.conv_passes(16384, 6144, 4)
    assert ops == 4 * 2 * 4 * 16384 * 6144
    assert moved == 4 * 2 * 16384 * 6144 * 2


@pytest.mark.parametrize("assignments", [0.0, 1536.0, 12288.0])
def test_two_products_are_two_thirds_of_three(assignments):
    three = roofline_moe.expert_products(assignments, 2688, 1856, 16)
    two = roofline_ssm.ungated_expert_products(assignments, 2688, 1856, 16)
    assert two[0] == pytest.approx(three[0] * 2 / 3)
    assert two[1] == pytest.approx(three[1] * 2 / 3)
    # by hand: up and down, 2 * 2688 * 1856 operations a row each, 4 passes
    assert two[0] == pytest.approx(4 * 2 * 2.0 * assignments * 2688 * 1856)


def _facts(scopes=None, ops=None, sizes=SIZES):
    return {"trace": {"busy_s": 10.0, "op_seconds": ops or {},
                      "op_counts": {k: 6 for k in ops or {}}},
            "trace_steps": 3, "tokens_per_step": 16384, "seq_len": 8192,
            "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": sizes, "rows_a_call": 1, "expert_layers": 5,
                     "moe_traced": [{"moe_held_assignments": 12288.0}] * 3,
                     "scopes": scopes and {"scopes": scopes}}}


def test_readers_of_the_new_scopes_and_kernels():
    from benchmark.layer_metrics import (expert2_mm_roofline,
                                         gqa_attn_roofline, grouped_mm_roofline,
                                         ssd_scan_roofline, ssm_conv_roofline,
                                         ssm_device_share)
    scopes = {"forward_backward/block/ssm/scan": 0.3,
              "forward_backward/block/ssm/conv": 0.1,
              "forward_backward/block/ssm/proj": 0.5,
              "forward_backward/block/ssm/norm": 0.1,
              "forward_backward/block/moe/experts": 2.0}
    facts = _facts(scopes)
    assert ssm_device_share.read(facts) == pytest.approx(10.0)
    tokens = 3 * 16384
    least = 6 * 4 * tokens * 2 * (4096 + 4096 + 1024 + 1024 + 64) / 819e9
    assert ssd_scan_roofline.read(facts) == pytest.approx(100 * least / 0.3)
    least = 6 * 4 * 2 * tokens * 6144 * 2 / 819e9
    assert ssm_conv_roofline.read(facts) == pytest.approx(100 * least / 0.1)
    ops = {"jit_train_step/flash_fwd<bf16,f32>": 0.1,
           "jit_train_step/flash_dkv<bf16,bf16>": 0.2,
           # the one pass under the group (PR 60), counted since PR 61
           "jit_train_step/flash_bwd<f32,f32,bf16>": 0.25,
           "jit_train_step/flash_fwd_w<bf16,f32>": 9.0,
           "jit_train_step/gmm<bf16>": 0.4, "jit_train_step/tgmm<bf16>": 0.2}
    facts = _facts(ops=ops)
    want = 6 * sum(roofline.least_seconds(*roofline.flash_call(
        w, 1, 32, 2, 8192, 128), V5E) for w in ("fwd", "dkv", "bwd"))
    assert gqa_attn_roofline.read(facts) == pytest.approx(100 * want / 0.55)
    two = expert2_mm_roofline.read(facts)
    assert two == pytest.approx(grouped_mm_roofline.read(facts) * 2 / 3)
    assert 0 < two < 100
    # A program without the scopes or the kernels, or another model's sizes:
    # nothing, and no error.
    empty = _facts({"forward_backward/block/moe/experts": 1.0},
                   {"jit_train_step/flash_fwd_w<bf16,f32>": 9.0})
    for reader in (ssd_scan_roofline, ssm_conv_roofline, ssm_device_share,
                   gqa_attn_roofline):
        assert reader.read(empty) is None
    other = _facts(scopes, ops, sizes={"E": 2048, "L": 9, "H": 32, "D": 128})
    for reader in (ssd_scan_roofline, ssm_conv_roofline, gqa_attn_roofline,
                   expert2_mm_roofline):
        assert reader.read(other) is None
        assert reader.read({"trace": None, "arch": None}) is None


def test_carry_reader_finds_nothing_without_a_session(tmp_path, monkeypatch):
    from benchmark.layer_metrics import ssm_chunk_carry
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    assert ssm_chunk_carry.read({}) is None


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row under the same key, but the three
    keys ``reduced`` names; the count is the arch module's."""
    from benchmark import common
    from benchmark.archs import nemotron_h as arch
    config = common.load_json("configs", "nemotron-3-nano-30b-a3b.json")
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "layer_norm_epsilon": 1e-05, "expand": 2, "intermediate_size": 1856}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["hybrid_override_pattern"].startswith("MEMEM*EMEMEM*E")
    assert len(config["hybrid_override_pattern"]) == 52
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["share"]["router_outputs"] == 128
    s = arch.sizes_of(config)
    assert s["kinds"] == "MEMEM*EMEMEM*"
    counts = arch.parameters(s)
    assert counts["held"] == config["parameters"] == 1267091328
    assert counts["expert"] == 2 * 2688 * 1856


def test_the_cell_rehearses_and_names_no_device_metric():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 3), "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    named = set(last["metrics_named"])
    assert "ssm_chunk_carry" in named
    assert "moe_load_max_over_mean" in named
    assert not {n for n in named if "roofline" in n or "idle" in n
                or "mfu" in n or "share" in n}
    assert "[correct] name=norm_grad_distance" in done.stdout
    assert "ssm_alone_distance=" in done.stdout
